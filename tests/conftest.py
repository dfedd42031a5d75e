"""Shared test fixtures.

Mirrors the reference's session-scoped synthetic-dataset strategy
(``petastorm/tests/conftest.py:89-138``, ``tests/test_common.py``), but datasets
are written with the pyarrow-native ``materialize_dataset`` instead of Spark.

JAX runs on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware.
"""

import os

# Must be set before jax is imported anywhere in the test process.
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (_flags + ' --xla_force_host_platform_device_count=8').strip()

import pytest  # noqa: E402

#: Test modules covered by the ``no_dangling_petastorm_threads`` teardown
#: fixture — the reader-lifecycle lanes, where every test constructs (and
#: must fully tear down) pools/watchdogs/emitters/readahead threads. A
#: leaked ``petastorm-tpu-*`` thread fails the LEAKING test, not whichever
#: later test happened to enumerate threads (the PR 4 assertion in
#: test_tracing, promoted to a shared guard).
_THREAD_GUARDED_MODULES = frozenset({
    'test_tracing', 'test_health', 'test_sharedcache', 'test_readahead',
    'test_workers_pool', 'test_transport', 'test_latency', 'test_autotune',
    'test_chaos', 'test_podelastic',
})

#: Test modules that run under the lockdep-lite harness
#: (``petastorm_tpu.test_util.lockdep``) when ``PETASTORM_TPU_LOCKDEP=1``:
#: the lanes exercising the concurrency-critical modules' real lock
#: interleavings. Opt-in because the harness is a diagnostic, not a
#: production layer; ``ci/run_tests.sh`` runs these lanes with it on.
_LOCKDEP_MODULES = frozenset({
    'test_sharedcache', 'test_health', 'test_workers_pool', 'test_latency',
    'test_autotune', 'test_chaos', 'test_podelastic',
})


def _short_module_name(request) -> str:
    return request.module.__name__.rsplit('.', 1)[-1]


@pytest.fixture(autouse=True)
def no_dangling_petastorm_threads(request):
    """Teardown guard for the reader-lifecycle lanes: any ``petastorm-tpu-*``
    thread the test leaves behind (beyond a settle window for daemons
    mid-exit) fails the test itself."""
    if _short_module_name(request) not in _THREAD_GUARDED_MODULES:
        yield
        return
    from petastorm_tpu.test_util.threads import (petastorm_threads,
                                                 wait_for_no_new_threads)
    before = petastorm_threads()
    yield
    leaked = wait_for_no_new_threads(before)
    assert not leaked, (
        'test leaked petastorm-tpu threads: {} (Reader.stop()/join() — or '
        'the component\'s own stop() — must reap every thread it '
        'started)'.format(leaked))


@pytest.fixture(autouse=True)
def lockdep_guard(request):
    """Opt-in lockdep-lite harness (PETASTORM_TPU_LOCKDEP=1): tracks every
    lock the concurrency-critical modules create during the test, fails on
    lock-order inversion cycles and on blocking calls under a tracked lock
    — including violations raised on worker threads and swallowed by their
    exception funnels (re-raised here at teardown)."""
    enabled = os.environ.get('PETASTORM_TPU_LOCKDEP', '').strip().lower()
    if (_short_module_name(request) not in _LOCKDEP_MODULES
            or enabled in ('', '0', 'false', 'off')):
        yield
        return
    from petastorm_tpu.test_util import lockdep
    with lockdep.lockdep_enabled() as registry:
        yield registry
    registry.assert_clean()


# old-style hookwrapper (works on all pytest 7.x): this fallback exists
# precisely for bare environments that may predate pluggy 1.2's wrapper=True
@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """SIGALRM fallback for ``@pytest.mark.timeout`` when pytest-timeout is
    not installed (round-4 verdict: unenforced timeout marks made a hung
    ``jax.distributed`` child able to hang the slow lane indefinitely). The
    real plugin takes precedence when present; this fallback covers bare
    environments on any SIGALRM-capable platform."""
    import signal
    marker = item.get_closest_marker('timeout')
    limit = None
    if marker is not None:
        # positional @timeout(N) or keyword @timeout(timeout=N) — both are
        # pytest-timeout's documented forms; missing either would recreate
        # the silently-inert guard this hook exists to eliminate
        limit = marker.args[0] if marker.args else marker.kwargs.get('timeout')
    if (limit is None or item.config.pluginmanager.hasplugin('timeout')
            or not hasattr(signal, 'SIGALRM')):
        yield
        return
    seconds = int(limit)

    def on_alarm(signum, frame):
        raise TimeoutError(
            'test exceeded its @pytest.mark.timeout({}) guard '
            '(conftest SIGALRM fallback)'.format(seconds))

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


def ref_attention(q, k, v, causal=True):
    """Dense-softmax attention reference shared by the kernel test modules."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    d = q.shape[-1]
    s = jnp.einsum('...qd,...kd->...qk', q, k) / np.sqrt(d)
    if causal:
        l_q, l_k = q.shape[-2], k.shape[-2]
        mask = np.tril(np.ones((l_q, l_k), bool))
        s = jnp.where(mask, s, -1e30)
    return jnp.einsum('...qk,...kd->...qd', jax.nn.softmax(s, axis=-1), v)


def make_ngram_lm_parts(window, chunk, d_model, n_layers, n_heads, d_ff,
                        vocab):
    """The NGram LM loop shared by the streaming and indexed NGram test
    modules: the window spec and a ``step_fn`` that concatenates a window's
    timestep chunks on device into one (B, window·chunk) sequence and runs
    the LM train step on the shift-by-one (inputs, targets)."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models import transformer_lm as tlm
    from petastorm_tpu.ngram import NGram

    config = tlm.TransformerConfig(vocab_size=vocab, d_model=d_model,
                                   n_heads=n_heads, n_layers=n_layers,
                                   d_ff=d_ff, max_seq_len=window * chunk)
    params = tlm.init(jax.random.PRNGKey(0), config)
    optimizer, step = tlm.make_train_step(config)
    state = {'params': params, 'opt': optimizer.init(params)}
    fields = {0: ['ts', 'tokens']}
    fields.update({i: ['tokens'] for i in range(1, window)})
    ngram = NGram(fields, delta_threshold=1, timestamp_field='ts')

    @jax.jit
    def concat_and_step(params, opt_state, chunks):
        seq = jnp.concatenate(chunks, axis=1)        # (B, window*chunk)
        return step(params, opt_state, seq[:, :-1], seq[:, 1:])

    def step_fn(batch):
        chunks = [batch[i]['tokens'] for i in range(window)]
        state['params'], state['opt'], loss = concat_and_step(
            state['params'], state['opt'], chunks)
        return loss

    return ngram, step_fn


@pytest.fixture(scope='session')
def synthetic_dataset(tmp_path_factory):
    """Full-featured petastorm_tpu dataset (images, matrices, scalars,
    nullables) + the expected decoded rows."""
    from petastorm_tpu.test_util.dataset_gen import create_test_dataset
    path = tmp_path_factory.mktemp('synthetic') / 'dataset'
    url = 'file://' + str(path)
    data = create_test_dataset(url, range(100))
    return SyntheticDataset(url=url, path=str(path), data=data)


@pytest.fixture(scope='session')
def scalar_dataset(tmp_path_factory):
    """Scalars-only dataset (no codecs needing decode)."""
    from petastorm_tpu.test_util.dataset_gen import create_test_scalar_dataset
    path = tmp_path_factory.mktemp('scalar') / 'dataset'
    url = 'file://' + str(path)
    data = create_test_scalar_dataset(url, 100)
    return SyntheticDataset(url=url, path=str(path), data=data)


@pytest.fixture(scope='session')
def non_petastorm_dataset(tmp_path_factory):
    """A plain parquet store with no petastorm_tpu metadata (foreign store)."""
    from petastorm_tpu.test_util.dataset_gen import create_non_petastorm_dataset
    path = tmp_path_factory.mktemp('foreign') / 'dataset'
    url = 'file://' + str(path)
    data = create_non_petastorm_dataset(url, 100)
    return SyntheticDataset(url=url, path=str(path), data=data)


@pytest.fixture(scope='session')
def wide_dataset(tmp_path_factory):
    """1000-column int32 parquet store (reference's
    ``many_columns_non_petastorm_dataset``, ``tests/conftest.py:89-138``):
    stresses schema inference, column projection and row assembly at width."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = tmp_path_factory.mktemp('wide') / 'dataset'
    path.mkdir(parents=True)
    n_cols, n_rows = 1000, 60
    # col_k[row r] = r * 1000 + k — every cell value is position-determined
    data = {'col_{:04d}'.format(k):
            np.arange(n_rows, dtype=np.int32) * 1000 + k
            for k in range(n_cols)}
    pq.write_table(pa.table(data), str(path / 'part_0.parquet'),
                   row_group_size=20)
    return SyntheticDataset(url='file://' + str(path), path=str(path),
                            data={'n_cols': n_cols, 'n_rows': n_rows})


class SyntheticDataset:
    def __init__(self, url, path, data):
        self.url = url
        self.path = path
        self.data = data
