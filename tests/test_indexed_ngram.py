"""IndexedNGramLoader: deterministic NGram window batches with O(1) exact
resume (closes the round-3 streaming-checkpoint caveat for NGram pipelines).

Ground truth throughout: the streaming NGram reader
(``make_reader(schema_fields=NGram(...))``) — the indexed loader must
produce exactly the same window universe with the same per-timestep values.
"""

import numpy as np
import pytest

from petastorm_tpu import make_reader
from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
from petastorm_tpu.errors import NoDataAvailableError
from petastorm_tpu.etl.dataset_metadata import materialize_dataset
from petastorm_tpu.indexed_ngram import make_indexed_ngram_loader
from petastorm_tpu.ngram import NGram, valid_window_starts
from petastorm_tpu.unischema import Unischema, UnischemaField

SeqSchema = Unischema('SeqSchema', [
    UnischemaField('ts', np.int64, (), ScalarCodec(), False),
    UnischemaField('value', np.float32, (3,), NdarrayCodec(), False),
    UnischemaField('label', np.int32, (), ScalarCodec(), False),
])


def _write(path, timestamps, rows_per_file=10, shuffle_rows=False):
    url = 'file://' + str(path)
    rows = [{'ts': np.int64(t),
             'value': np.full(3, t, dtype=np.float32),
             'label': np.int32(t % 7)} for t in timestamps]
    if shuffle_rows:
        # shuffle WITHIN each file's row range so groups hold the same ts
        # sets but storage order is not ts-sorted
        rng = np.random.default_rng(0)
        shuffled = []
        for start in range(0, len(rows), rows_per_file):
            chunk = rows[start:start + rows_per_file]
            rng.shuffle(chunk)
            shuffled.extend(chunk)
        rows = shuffled
    with materialize_dataset(url, SeqSchema, row_group_size_mb=100,
                             rows_per_file=rows_per_file) as w:
        w.write_rows(rows)
    return url


def _ngram(length=3, delta_threshold=1, timestamp_overlap=True, fields=None):
    fields = fields or {i: ['ts', 'value', 'label'] for i in range(length)}
    return NGram(fields, delta_threshold=delta_threshold,
                 timestamp_field='ts', timestamp_overlap=timestamp_overlap)


def _streaming_windows(url, ngram):
    """All windows from the streaming reader as {offset: {field: value}}."""
    with make_reader(url, schema_fields=ngram, reader_pool_type='dummy',
                     shuffle_row_groups=False, num_epochs=1) as reader:
        return [{off: {f: getattr(nt, f) for f in nt._fields}
                 for off, nt in w.items()} for w in reader]


def _indexed_windows(loader):
    """All windows from one epoch of the indexed loader, un-batched."""
    out = []
    for batch in loader:
        n = len(next(iter(batch[loader._offsets[0]].values())))
        for i in range(n):
            out.append({off: {f: cols[f][i] for f in cols}
                        for off, cols in batch.items()})
    return out


def _window_key(w, ngram):
    return int(w[sorted(w)[0]]['ts'])


# ---------------------------------------------------------------------------
# unit: window-start computation
# ---------------------------------------------------------------------------

def test_valid_starts_contiguous():
    ts = np.arange(10)
    np.testing.assert_array_equal(
        valid_window_starts(ts, 3, 1, True), np.arange(8))


def test_valid_starts_gap_rejected():
    ts = np.asarray([0, 1, 2, 10, 11, 12])
    np.testing.assert_array_equal(
        valid_window_starts(ts, 3, 1, True), [0, 3])


def test_valid_starts_non_overlapping_greedy():
    ts = np.arange(10)
    # span 3, no overlap: windows at 0, 3, 6 (ts ranges [0-2], [3-5], [6-8])
    np.testing.assert_array_equal(
        valid_window_starts(ts, 3, 1, False), [0, 3, 6])


def test_valid_starts_span_one():
    np.testing.assert_array_equal(
        valid_window_starts(np.asarray([5, 9]), 1, 1, True), [0, 1])


# ---------------------------------------------------------------------------
# equivalence with the streaming reader
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('case', ['contiguous', 'gapped', 'no_overlap',
                                  'gapped_offsets', 'unsorted_storage'])
def test_window_universe_matches_streaming_reader(tmp_path, case):
    if case == 'contiguous':
        ts, ngram = list(range(40)), _ngram(3)
    elif case == 'gapped':
        ts = list(range(15)) + list(range(20, 40))
        ngram = _ngram(3)
    elif case == 'no_overlap':
        ts, ngram = list(range(40)), _ngram(3, timestamp_overlap=False)
    elif case == 'gapped_offsets':
        ts = list(range(40))
        ngram = _ngram(fields={0: ['ts', 'value'], 2: ['ts', 'label']})
    else:   # unsorted_storage: rows not ts-ordered within groups
        ts, ngram = list(range(40)), _ngram(2)
    url = _write(tmp_path / case, ts,
                 shuffle_rows=(case == 'unsorted_storage'))

    expected = _streaming_windows(url, ngram)
    loader = make_indexed_ngram_loader(url, ngram, batch_size=4,
                                       num_epochs=1, shuffle=False,
                                       workers_count=2)
    got = _indexed_windows(loader)
    # drop_last trims the tail: indexed yields a prefix-of-universe multiple
    # of batch_size; compare as keyed dicts over the common universe
    assert loader.total_windows == len(expected)
    assert len(got) == (len(expected) // 4) * 4
    exp_by_key = {_window_key(w, ngram): w for w in expected}
    assert len(exp_by_key) == len(expected)
    for w in got:
        exp = exp_by_key[_window_key(w, ngram)]
        assert sorted(w.keys()) == sorted(exp.keys())
        for off in w:
            assert set(w[off].keys()) == set(exp[off].keys())
            for f in w[off]:
                np.testing.assert_array_equal(w[off][f], exp[off][f],
                                              err_msg='{}/{}'.format(off, f))


# ---------------------------------------------------------------------------
# determinism + resume
# ---------------------------------------------------------------------------

def _digest_stream(loader):
    out = []
    for batch in loader:
        cursor = (loader.epoch, loader.batch)
        key = tuple(int(t) for t in batch[0]['ts'])
        out.append((key, cursor))
    return out


def test_stream_deterministic_across_worker_counts(tmp_path):
    url = _write(tmp_path / 'det', list(range(50)))
    streams = []
    for workers in (1, 4):
        loader = make_indexed_ngram_loader(url, _ngram(3), batch_size=8,
                                           num_epochs=2, seed=11,
                                           workers_count=workers)
        streams.append(_digest_stream(loader))
    assert streams[0] == streams[1]
    assert len(streams[0]) == 2 * loader.batches_per_epoch


def test_shuffle_changes_order_keeps_universe(tmp_path):
    url = _write(tmp_path / 'shuf', list(range(50)))
    ngram = _ngram(2)
    plain = make_indexed_ngram_loader(url, ngram, batch_size=4,
                                      num_epochs=1, shuffle=False)
    shuffled = make_indexed_ngram_loader(url, ngram, batch_size=4,
                                         num_epochs=1, seed=3, shuffle=True)
    a = [t for key, _ in _digest_stream(plain) for t in key]
    b = [t for key, _ in _digest_stream(shuffled) for t in key]
    assert a != b
    # drop_last trims total%batch windows — WHICH ones depends on the
    # shuffle, so the consumed sets may differ by up to that many per side
    dropped = plain.total_windows % 4
    assert len(set(a) ^ set(b)) <= 2 * dropped


def test_mid_epoch_resume_byte_exact(tmp_path):
    url = _write(tmp_path / 'resume', list(range(60)))
    ngram = _ngram(3)
    kwargs = dict(batch_size=8, num_epochs=2, seed=7, workers_count=2)
    full = _digest_stream(make_indexed_ngram_loader(url, ngram, **kwargs))
    assert len(full) >= 6

    # consume 3 batches, save the cursor, resume in a fresh loader
    first = make_indexed_ngram_loader(url, ngram, **kwargs)
    it = iter(first)
    for _ in range(3):
        next(it)
    state = first.state_dict()
    it.close()
    first.close()

    resumed = make_indexed_ngram_loader(url, ngram, **kwargs)
    resumed.load_state_dict(state)
    rest = _digest_stream(resumed)
    assert rest == full[3:]


def test_epoch_shuffles_differ(tmp_path):
    url = _write(tmp_path / 'epochs', list(range(50)))
    loader = make_indexed_ngram_loader(url, _ngram(2), batch_size=4,
                                       num_epochs=2, seed=5)
    stream = _digest_stream(loader)
    per_epoch = len(stream) // 2
    e0 = [k for k, _ in stream[:per_epoch]]
    e1 = [k for k, _ in stream[per_epoch:]]
    assert e0 != e1
    # each epoch consumes all windows minus a shuffle-dependent drop_last tail
    flat0 = {t for k in e0 for t in k}
    flat1 = {t for k in e1 for t in k}
    dropped = loader.total_windows % loader.batch_size
    assert len(flat0 ^ flat1) <= 2 * dropped


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def _streaming_windows_with(url, ngram, **reader_kwargs):
    with make_reader(url, schema_fields=ngram, reader_pool_type='dummy',
                     shuffle_row_groups=False, num_epochs=1,
                     **reader_kwargs) as reader:
        return [{off: {f: getattr(nt, f) for f in nt._fields}
                 for off, nt in w.items()} for w in reader]


def _assert_windows_match(got, expected, batch_size):
    """Indexed windows (drop_last-trimmed) value-equal the streaming universe,
    keyed by each window's first-offset ts."""
    assert len(got) == (len(expected) // batch_size) * batch_size
    exp_by_key = {int(w[sorted(w)[0]]['ts']): w for w in expected}
    assert len(exp_by_key) == len(expected)
    for w in got:
        exp = exp_by_key[int(w[sorted(w)[0]]['ts'])]
        assert sorted(w.keys()) == sorted(exp.keys())
        for off in w:
            assert set(w[off].keys()) == set(exp[off].keys())
            for f in w[off]:
                np.testing.assert_array_equal(w[off][f], exp[off][f],
                                              err_msg='{}/{}'.format(off, f))


def test_predicate_matches_streaming_reader(tmp_path):
    """Predicate drops rows BEFORE window formation — the indexed loader's
    window universe and values must equal the streaming reader's under the
    same predicate (VERDICT r04 ask #7; reference semantics
    ``py_dict_reader_worker.py:188-252``)."""
    from petastorm_tpu.predicates import in_lambda
    url = _write(tmp_path / 'pred', list(range(40)))
    ngram = _ngram(2)
    # label == ts % 7: rejecting label 3 drills holes into the ts sequence,
    # so surviving neighbors exceed delta_threshold and windows die with them
    predicate = in_lambda(['label'], lambda v: v['label'] != 3)
    expected = _streaming_windows_with(url, ngram, predicate=predicate)
    assert 0 < len(expected) < 39        # predicate really bit
    loader = make_indexed_ngram_loader(url, ngram, batch_size=4,
                                       num_epochs=1, shuffle=False,
                                       predicate=predicate)
    assert loader.total_windows == len(expected)
    _assert_windows_match(_indexed_windows(loader), expected, 4)
    loader.close()


def test_predicate_survivor_windows_span_dropped_rows(tmp_path):
    """With a loose delta_threshold, windows FORM ACROSS dropped rows (the
    survivors become adjacent) — semantics shared with the streaming path."""
    from petastorm_tpu.predicates import in_lambda
    url = _write(tmp_path / 'pred2', list(range(30)))
    ngram = _ngram(2, delta_threshold=10)
    predicate = in_lambda(['label'], lambda v: v['label'] % 2 == 0)
    expected = _streaming_windows_with(url, ngram, predicate=predicate)
    loader = make_indexed_ngram_loader(url, ngram, batch_size=2,
                                       num_epochs=1, shuffle=False,
                                       predicate=predicate)
    assert loader.total_windows == len(expected)
    _assert_windows_match(_indexed_windows(loader), expected, 2)
    loader.close()


def test_transform_matches_streaming_reader(tmp_path):
    """Columnar TransformSpec at assembly agrees value-exactly with the
    streaming reader's per-row transform (a row-wise func works under both
    contracts via numpy broadcasting)."""
    from petastorm_tpu.transform import TransformSpec
    url = _write(tmp_path / 'tx', list(range(30)))
    ngram = _ngram(2)

    def double_value(d):
        d = dict(d)
        d['value'] = d['value'] * 2
        return d

    spec = TransformSpec(double_value, removed_fields=['label'])
    expected = _streaming_windows_with(url, ngram, transform_spec=spec)
    assert expected and 'label' not in expected[0][0]
    assert float(expected[0][0]['value'][0]) == 2 * float(expected[0][0]['ts'])
    loader = make_indexed_ngram_loader(url, ngram, batch_size=4,
                                       num_epochs=1, shuffle=False,
                                       transform_spec=spec)
    _assert_windows_match(_indexed_windows(loader), expected, 4)
    loader.close()


def test_predicate_and_transform_resume_exact(tmp_path):
    """state_dict resume stays byte-exact with predicate + transform active
    (the stream is still a pure function of the cursor)."""
    from petastorm_tpu.predicates import in_lambda
    from petastorm_tpu.transform import TransformSpec
    url = _write(tmp_path / 'ptx', list(range(40)))
    args = dict(num_epochs=2, seed=5, shuffle=True,
                predicate=in_lambda(['label'], lambda v: v['label'] != 0),
                transform_spec=TransformSpec(
                    lambda d: dict(d, value=d['value'] + 1)))
    ngram = _ngram(2)
    full = make_indexed_ngram_loader(url, ngram, batch_size=4, **args)
    batches = list(full)
    state_at = 3
    resumed = make_indexed_ngram_loader(url, ngram, batch_size=4, **args)
    resumed.load_state_dict({'epoch': 3 // resumed.batches_per_epoch,
                             'batch': 3 % resumed.batches_per_epoch,
                             'version': 1})
    got = list(resumed)
    assert len(got) == len(batches) - state_at
    for a, b in zip(batches[state_at:], got):
        for off in a:
            for f in a[off]:
                np.testing.assert_array_equal(a[off][f], b[off][f])
    full.close()
    resumed.close()


def test_reader_narrowed_to_ngram_fields(tmp_path):
    """The loader must not decode columns the NGram never references."""
    url = _write(tmp_path / 'narrow', list(range(20)))
    ngram = _ngram(fields={0: ['ts', 'label'], 1: ['label']})
    loader = make_indexed_ngram_loader(url, ngram, batch_size=4,
                                       num_epochs=1, shuffle=False)
    # narrowing lives on the loader (explicit gather columns), NOT as a
    # mutation of the possibly-shared dataset's schema
    assert set(loader._read_fields) == {'ts', 'label'}
    assert set(loader._dataset.schema.fields) == {'ts', 'label', 'value'}
    batch = next(iter(loader))
    assert set(batch[0].keys()) == {'ts', 'label'}
    assert set(batch[1].keys()) == {'label'}


def test_too_few_windows_raises(tmp_path):
    url = _write(tmp_path / 'tiny', list(range(5)), rows_per_file=5)
    with pytest.raises(NoDataAvailableError, match='windows|rows'):
        make_indexed_ngram_loader(url, _ngram(3), batch_size=16)


class TestShardedIndexedNGram:
    """Global jax.Array window batches over the virtual 8-device CPU mesh."""

    def _mesh(self):
        import jax
        from petastorm_tpu.parallel import make_mesh
        devices = jax.devices('cpu')
        if len(devices) < 8:
            pytest.skip('needs 8 CPU devices')
        return make_mesh({'data': 8}, devices=devices)

    def test_global_batches_match_host_loader(self, tmp_path):
        import jax
        url = _write(tmp_path / 'sharded', list(range(60)))
        ngram = _ngram(2)
        mesh = self._mesh()
        kwargs = dict(batch_size=8, num_epochs=1, seed=4, workers_count=2)
        host = make_indexed_ngram_loader(url, ngram, **kwargs)
        sharded = make_indexed_ngram_loader(url, ngram, mesh=mesh, **kwargs)
        host_batches = list(host)
        got = 0
        for hb, sb in zip(host_batches, sharded):
            for off in (0, 1):
                for field in hb[off]:
                    arr = sb[off][field]
                    assert isinstance(arr, jax.Array)
                    assert arr.sharding.is_fully_addressable
                    np.testing.assert_array_equal(np.asarray(arr),
                                                  hb[off][field])
            got += 1
        assert got == len(host_batches) > 0

    def test_predicate_and_transform_match_host_loader(self, tmp_path):
        """Predicate + columnar transform compose with mesh sharding: the
        sharded stream equals the host loader's under identical config
        (the sub-batch slice happens AFTER window addressing, so filtering
        and transforming commute with sharding)."""
        import jax

        from petastorm_tpu.predicates import in_lambda
        from petastorm_tpu.transform import TransformSpec
        url = _write(tmp_path / 'sharded_pt', list(range(60)))
        ngram = _ngram(2, delta_threshold=5)
        mesh = self._mesh()
        kwargs = dict(batch_size=8, num_epochs=1, seed=2, workers_count=2,
                      predicate=in_lambda(['label'],
                                          lambda v: v['label'] != 4),
                      transform_spec=TransformSpec(
                          lambda d: dict(d, value=d['value'] * 10)))
        host_batches = list(make_indexed_ngram_loader(url, ngram, **kwargs))
        assert host_batches
        sharded = make_indexed_ngram_loader(url, ngram, mesh=mesh, **kwargs)
        got = 0
        for hb, sb in zip(host_batches, sharded):
            for off in hb:
                for field in hb[off]:
                    arr = sb[off][field]
                    assert isinstance(arr, jax.Array)
                    np.testing.assert_array_equal(np.asarray(arr),
                                                  hb[off][field])
            # predicate really applied (label 4 absent)...
            ts0 = np.asarray(sb[0]['ts'])
            assert 4 not in {int(x) % 7 for x in ts0}
            # ...and the transform really ran (value = ts * 10, not ts):
            # without this, a loader that silently dropped transform_spec in
            # BOTH modes would still pass the host-vs-sharded comparison
            np.testing.assert_array_equal(
                np.asarray(sb[0]['value']),
                np.repeat(ts0[:, None] * 10, 3, axis=1).astype(np.float32))
            got += 1
        assert got == len(host_batches)

    def test_resume_matches_host_loader(self, tmp_path):
        url = _write(tmp_path / 'sharded_resume', list(range(60)))
        ngram = _ngram(2)
        mesh = self._mesh()
        kwargs = dict(batch_size=8, num_epochs=2, seed=9, workers_count=2)
        full = [tuple(int(t) for t in b[0]['ts'])
                for b in make_indexed_ngram_loader(url, ngram, **kwargs)]
        sharded = make_indexed_ngram_loader(url, ngram, mesh=mesh, **kwargs)
        it = iter(sharded)
        for _ in range(3):
            next(it)
        state = sharded.state_dict()
        it.close()
        sharded.close()
        resumed = make_indexed_ngram_loader(url, ngram, mesh=mesh, **kwargs)
        resumed.load_state_dict(state)
        rest = [tuple(int(t) for t in np.asarray(b[0]['ts'])) for b in resumed]
        assert rest == full[3:]

    def test_indivisible_batch_rejected(self, tmp_path):
        url = _write(tmp_path / 'sharded_bad', list(range(30)))
        with pytest.raises(ValueError, match='divide evenly'):
            make_indexed_ngram_loader(url, _ngram(2), batch_size=6,
                                      mesh=self._mesh())


@pytest.mark.slow
def test_indexed_ngram_bench_runs(tmp_path):
    """The indexed NGram loader feeds three LM train steps end to end."""
    from conftest import make_ngram_lm_parts
    from petastorm_tpu.test_util.dataset_gen import \
        generate_timeseries_token_dataset

    url = 'file://' + str(tmp_path / 'bench_tok')
    generate_timeseries_token_dataset(url, rows=96, chunk=16, vocab=256)
    ngram, step_fn = make_ngram_lm_parts(window=2, chunk=16, d_model=32,
                                         n_layers=1, n_heads=2, d_ff=64,
                                         vocab=256)
    loader = make_indexed_ngram_loader(url, ngram, batch_size=4,
                                       num_epochs=1, seed=0,
                                       workers_count=2)
    steps = samples = 0
    try:
        for batch in loader:
            assert np.isfinite(float(step_fn(batch)))
            steps += 1
            samples += int(batch[0]['tokens'].shape[0])
            if steps == 3:
                break
    finally:
        loader.close()
    assert steps == 3 and samples == 12


def test_feeds_lm_train_step(tmp_path):
    """Windows → concatenated sequence → one LM step (the resume-capable
    variant of the NGram → LM loop)."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models import transformer_lm as tlm

    TokSchema = Unischema('Tok', [
        UnischemaField('ts', np.int64, (), ScalarCodec(), False),
        UnischemaField('tokens', np.int32, (8,), NdarrayCodec(), False),
    ])
    url = 'file://' + str(tmp_path / 'tok')
    rng = np.random.default_rng(0)
    with materialize_dataset(url, TokSchema, rows_per_file=16) as w:
        w.write_rows({'ts': np.int64(i),
                      'tokens': rng.integers(0, 64, 8, dtype=np.int32)}
                     for i in range(48))
    ngram = NGram({0: ['ts', 'tokens'], 1: ['tokens']}, delta_threshold=1,
                  timestamp_field='ts')
    loader = make_indexed_ngram_loader(url, ngram, batch_size=4,
                                       num_epochs=1, seed=0)
    cfg = tlm.TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_seq_len=16,
                                dtype=jnp.float32)
    params = tlm.init(jax.random.PRNGKey(0), cfg)
    optimizer, step = tlm.make_train_step(cfg)
    opt_state = optimizer.init(params)
    batch = next(iter(loader))
    seq = jnp.concatenate([jnp.asarray(batch[0]['tokens']),
                           jnp.asarray(batch[1]['tokens'])], axis=1)
    params, opt_state, loss = step(params, opt_state, seq[:, :-1],
                                   seq[:, 1:])
    assert np.isfinite(float(loss))
