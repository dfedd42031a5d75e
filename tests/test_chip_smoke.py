"""``chip_smoke.py``'s phases at tiny widths on the CPU (jnp attention), its
refusal to run without a TPU, and the other guards of the chip path: no CPU
fallback, the compile cache's place, flash attention on a mesh."""

import importlib.util
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from petastorm_tpu.models import transformer_lm as tlm

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), '..')
SCRIPT = os.path.join(ROOT, 'chip_smoke.py')

TINY = tlm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                             n_layers=1, d_ff=64, max_seq_len=32,
                             attention='flash')


@pytest.fixture(scope='module')
def cpus():
    devices = jax.devices('cpu')
    if len(devices) < 4:
        pytest.skip('needs 4 CPU devices')
    return devices


def _tiny(**kw):
    defaults = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                    d_ff=64, max_seq_len=32, dtype=jnp.float32)
    defaults.update(kw)
    return tlm.TransformerConfig(**defaults)


@pytest.fixture(scope='module')
def smoke():
    spec = importlib.util.spec_from_file_location('chip_smoke', SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lm_phase(smoke, tmp_path):
    out = smoke.lm_phase(str(tmp_path), TINY, rows=64, batch_size=4, steps=3)
    assert len(out['losses']) == 3
    assert not out['kernel']            # jnp attention on the CPU
    assert out['rows_decoded_device'] >= 3 * 4


def test_image_phase(smoke, tmp_path):
    out = smoke.image_phase(str(tmp_path), rows=8, batch_size=4, steps=2,
                            image_size=32, num_classes=10, widths=(8, 16))
    assert len(out['losses']) == 2


def test_data_parallel_phase(smoke, tmp_path):
    devices = jax.devices()[:4]
    out = smoke.data_parallel_phase(str(tmp_path), TINY, devices, rows=64,
                                    global_batch=8, steps=2)
    assert len(out['losses']) == len(out['one_chip_losses']) == 2
    assert out['rows_decoded_device'] > 0


@pytest.mark.parametrize('limit,message', [
    ('LOSS_RTOL', 'step-0 loss .* off the blockwise reference'),
    ('GRAD_RTOL', 'flash loss or gradient off the blockwise reference')])
def test_phase_fails_off_the_reference(smoke, tmp_path, monkeypatch, limit,
                                       message):
    monkeypatch.setattr(smoke, limit, -1.0)
    with pytest.raises(smoke.SmokeFailure, match=message):
        smoke.lm_phase(str(tmp_path), TINY, rows=16, batch_size=4, steps=1)


@pytest.mark.parametrize('phase', ['lm', 'data_parallel'])
def test_loose_limits_fail_the_control(smoke, tmp_path, monkeypatch, phase):
    """Limits a wrong mask or half the batch would pass are refused."""
    monkeypatch.setattr(smoke, 'LOSS_RTOL', float('inf'))
    monkeypatch.setattr(smoke, 'GRAD_RTOL', float('inf'))
    with pytest.raises(smoke.SmokeFailure, match='limits are too loose'):
        if phase == 'lm':
            smoke.lm_phase(str(tmp_path), TINY, rows=16, batch_size=4,
                           steps=1)
        else:
            smoke.data_parallel_phase(str(tmp_path), TINY, jax.devices()[:4],
                                      rows=16, global_batch=8, steps=1)


def test_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS='cpu', TMPDIR=str(tmp_path))
    proc = subprocess.run([sys.executable, SCRIPT], cwd=str(tmp_path),
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert 'no TPU found' in proc.stderr
    assert '"ok"' not in proc.stdout
    assert os.listdir(str(tmp_path)) == []     # no store was generated


def test_device_decode_planning_raises_on_a_broken_backend(monkeypatch):
    from petastorm_tpu.ops.decode import jax_backend_available

    def broken():
        raise RuntimeError('backend failed to initialize')

    monkeypatch.setattr(jax, 'devices', broken)
    with pytest.raises(RuntimeError, match='failed to initialize'):
        jax_backend_available()


def test_compile_cache_placement(tmp_path, monkeypatch):
    from petastorm_tpu.utils import configure_compile_cache
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/from/outside')
    assert configure_compile_cache(str(tmp_path)) == '/from/outside'
    assert jax.config.jax_compilation_cache_dir == saved
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR')
    try:
        path = configure_compile_cache(str(tmp_path))
        assert path == str(tmp_path / '.jax_cache')
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update('jax_compilation_cache_dir', saved)


class TestFlashOnMesh:
    """attention='flash' with a mesh runs the kernel under shard_map (Mosaic
    kernels cannot be partitioned by GSPMD); on a CPU mesh the jnp backend
    must give the single-device loss."""

    @pytest.mark.parametrize('axes', [{'data': 4}, {'data': 2, 'model': 2},
                                      {'data': 2, 'seq': 2}])
    @pytest.mark.parametrize('packed', [False, True])
    def test_loss_matches_single_device(self, cpus, axes, packed):
        from petastorm_tpu.parallel import make_mesh
        cfg = _tiny(attention='flash')
        params = tlm.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32)
        seg = (jnp.asarray(np.repeat([[0, 1]], 4, 0).repeat(16, 1))
               if packed else None)
        ref = tlm.loss_fn(params, toks, toks, cfg, segment_ids=seg)
        mesh = make_mesh(axes, devices=cpus[:4])
        out = jax.jit(lambda p, t, s: tlm.loss_fn(p, t, t, cfg, mesh,
                                                  segment_ids=s))(
            params, toks, seg)
        np.testing.assert_allclose(float(out), float(ref), rtol=1e-5)

    def test_mesh_step_compiles_once(self, cpus, caplog):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from petastorm_tpu.parallel import make_mesh
        cfg = _tiny(attention='flash', n_layers=1)
        mesh = make_mesh({'data': 4}, devices=cpus[:4])
        optimizer, step = tlm.make_train_step(cfg, mesh)
        params = jax.device_put(tlm.init(jax.random.PRNGKey(0), cfg),
                                NamedSharding(mesh, P()))
        opt_state = optimizer.init(params)
        toks = jax.device_put(jnp.zeros((8, 32), jnp.int32),
                              NamedSharding(mesh, P('data')))
        with caplog.at_level(logging.WARNING), jax.log_compiles(True):
            for _ in range(3):
                params, opt_state, _ = step(params, opt_state, toks, toks)
        compiles = [r for r in caplog.records
                    if 'Finished XLA compilation of jit(step)' in r.message]
        assert len(compiles) == 1
