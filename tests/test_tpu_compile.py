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
