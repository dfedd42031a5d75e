"""Compile the main path's Pallas kernels for a described TPU v5e (2x2).

Nothing runs: the TPU compiler, installed here, compiles for a chip that is
described and not attached, and refuses what the chip would refuse
(misaligned tiles, too much VMEM, a Mosaic kernel GSPMD cannot partition).
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

# B, H, L, d_head of the flagship LM's attention (TransformerConfig())
B, H, L, DH = 8, 8, 2048, 64


@pytest.fixture(scope='module')
def topo():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip('no v5e:2x2 topology can be described here: {}'.format(e))


@pytest.fixture(scope='module', autouse=True)
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', saved)


def _qkv(sharding):
    return [jax.ShapeDtypeStruct((B, H, L, DH), jnp.bfloat16,
                                 sharding=sharding) for _ in range(3)]


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_forward(topo):
    from petastorm_tpu.ops.attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, backend='pallas')

    assert 'tpu_custom_call' in _hlo(
        fwd, *_qkv(SingleDeviceSharding(topo.devices[0])))


def test_flash_fused_backward(topo):
    from petastorm_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, backend='pallas',
                            bwd='pallas')
        return jnp.sum(o.astype(jnp.float32))

    hlo = _hlo(jax.grad(loss, argnums=(0, 1, 2)),
               *_qkv(SingleDeviceSharding(topo.devices[0])))
    # forward (with lse) + dq kernel + dk/dv kernel
    assert hlo.count('tpu_custom_call') >= 3


@pytest.mark.parametrize('precision', ['default', 'highest'])
def test_flash_segmented_backward_at_lm_widths(topo, precision):
    """The packed LM's attention, forward and fused backward, at its
    per-chip widths: bf16 (4, 12, 2048, 64) with segment ids, the kernels'
    MXU operands bf16, also under a global 'highest' matmul precision
    (which Mosaic refuses for bf16 operands if the kernels pass it on)."""
    from petastorm_tpu.ops.attention import flash_attention

    chip = SingleDeviceSharding(topo.devices[0])
    qkv = [jax.ShapeDtypeStruct((4, 12, 2048, 64), jnp.bfloat16,
                                sharding=chip) for _ in range(3)]
    seg = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=chip)

    def loss(q, k, v, seg):
        o = flash_attention(q, k, v, causal=True, backend='pallas',
                            bwd='pallas', segment_ids=seg)
        return jnp.sum(o.astype(jnp.float32))

    with jax.default_matmul_precision(precision):
        hlo = _hlo(jax.grad(loss, argnums=(0, 1, 2)), *qkv, seg)
    assert hlo.count('tpu_custom_call') >= 3


def test_flash_sharded_over_data_mesh(topo):
    """The LM's flash call on a data-parallel mesh: GSPMD refuses a bare
    Mosaic kernel, so it must sit inside shard_map over 'data'."""
    from petastorm_tpu.models.transformer_lm import _flash_attention_sharded
    from petastorm_tpu.parallel import make_mesh

    mesh = make_mesh({'data': 4}, devices=topo.devices)

    def loss(q, k, v):
        o = _flash_attention_sharded(q, k, v, mesh)
        return jnp.sum(o.astype(jnp.float32))

    sharding = NamedSharding(mesh, P('data'))
    hlo = _hlo(jax.value_and_grad(loss, argnums=(0, 1, 2)), *_qkv(sharding))
    assert 'tpu_custom_call' in hlo


def test_ring_attention_data_seq_mesh(topo):
    from petastorm_tpu.parallel import make_mesh
    from petastorm_tpu.parallel.ring import make_ring_attention

    mesh = make_mesh({'data': 2, 'seq': 2}, devices=topo.devices)
    ring = make_ring_attention(mesh, 'seq')

    def loss(q, k, v):
        return jnp.sum(ring(q, k, v).astype(jnp.float32))

    sharding = NamedSharding(mesh, P('data', None, 'seq', None))
    hlo = _hlo(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(sharding))
    assert 'tpu_custom_call' in hlo
    assert 'collective-permute' in hlo


def test_lm_loss_writes_no_float32_logits(topo):
    """The LM's loss and gradient on a data-parallel mesh write no float32
    copy of the per-chip (B, L, vocab) logits: the cross-entropy reads the
    bf16 logits and keeps its float32 arithmetic inside its fusions."""
    from petastorm_tpu.models import transformer_lm as tlm
    from petastorm_tpu.parallel import make_mesh

    cfg = tlm.TransformerConfig(vocab_size=50304, d_model=256, n_heads=4,
                                n_layers=2, d_ff=1024, max_seq_len=512,
                                attention='flash')
    batch, seq, chips = 16, 512, 4
    mesh = make_mesh({'data': chips}, devices=topo.devices)
    shapes = jax.eval_shape(lambda: tlm.init(jax.random.PRNGKey(0), cfg))
    params = jax.tree_util.tree_map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        shapes, tlm.param_specs(cfg, mesh),
        is_leaf=lambda x: isinstance(x, P))
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32,
        sharding=NamedSharding(mesh, tlm.batch_spec(mesh)))

    hlo = _hlo(jax.value_and_grad(
        lambda p, x, y: tlm.loss_fn(p, x, y, cfg, mesh)), params, tokens,
        tokens)
    logits_f32 = 'f32[{},{},{}]'.format(batch // chips, seq, cfg.vocab_size)
    # a fusion's output types stand between '=' and its opcode
    written = [line.strip() for line in hlo.splitlines()
               if ' fusion(' in line
               and logits_f32 in line.split(' fusion(')[0].split(' = ')[-1]]
    assert 'tpu_custom_call' in hlo
    assert not written, written
