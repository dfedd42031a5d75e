"""Flash-attention kernel tests: interpret-mode (CPU CI) correctness of the
kv-streaming Pallas kernel and its custom_vjp backward, plus a TPU-gated
equality test that runs when real hardware is present.

Reference note: the reference has no attention at all (SURVEY §5.7); this
kernel exists for the TPU build's long-context stack.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ref_attention as _exact

from petastorm_tpu.ops.attention import blockwise_attention, flash_attention

# kernels / model training are minutes-scale: the fast lane skips the classes
# that run them, and keeps the jaxpr checks, which only trace
slow = pytest.mark.slow

_RNG = np.random.default_rng(0)


@pytest.fixture()
def cpu():
    """Pin exactness tests to a CPU device — the session may have an
    accelerator attached (bf16 MXU matmuls would blur the comparisons)."""
    with jax.default_device(jax.devices('cpu')[0]):
        yield


def _mk(b, h, lq, lk, d, dtype=jnp.float32):
    q = jnp.asarray(_RNG.standard_normal((b, h, lq, d)), dtype)
    k = jnp.asarray(_RNG.standard_normal((b, h, lk, d)), dtype)
    v = jnp.asarray(_RNG.standard_normal((b, h, lk, d)), dtype)
    return q, k, v


def _kernel_dots(jaxpr):
    """The ``dot_general`` equations inside each ``pallas_call`` of
    ``jaxpr``, one list per kernel, in trace order."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subjaxprs(eqn):
        for value in eqn.params.values():
            for x in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(x, ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, Jaxpr):
                    yield x

    def dots(j):
        for eqn in j.eqns:
            if eqn.primitive.name == 'dot_general':
                yield eqn
            for sub in subjaxprs(eqn):
                yield from dots(sub)

    def kernels(j):
        for eqn in j.eqns:
            if eqn.primitive.name == 'pallas_call':
                yield list(dots(eqn.params['jaxpr']))
            else:
                for sub in subjaxprs(eqn):
                    yield from kernels(sub)

    return list(kernels(jaxpr))


@pytest.mark.parametrize('segmented', [False, True])
@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('grad', [False, True])
def test_kernel_mxu_operands_follow_input_dtype(dtype, segmented, grad):
    """Every MXU product inside the forward, dq and dk/dv kernels takes
    operands in the inputs' dtype (bf16 tiles stay bf16, p and ds are cast to
    it) and accumulates in float32. Under a global 'highest' matmul precision
    float32 products keep it and bf16 ones take the default."""
    q = jnp.zeros((2, 2, 256, 64), dtype)
    seg = jnp.zeros((2, 256), jnp.int32) if segmented else None

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128, backend='pallas',
                               segment_ids=seg)

    if grad:
        fn = jax.grad(lambda q, k, v: jnp.sum(
            attend(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))
    else:
        fn = attend
    with jax.default_matmul_precision('highest'):
        kernels = _kernel_dots(jax.make_jaxpr(fn)(q, q, q).jaxpr)
    # forward: q·kᵀ, p·v; dq: q·kᵀ, do·vᵀ, ds·k; dk/dv: those and p·do, ds·q
    assert [len(d) for d in kernels] == ([2, 3, 4] if grad else [2])
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    for eqn in (e for d in kernels for e in d):
        assert [x.aval.dtype for x in eqn.invars] == [dtype, dtype], eqn
        assert eqn.params['preferred_element_type'] == jnp.float32, eqn
        assert eqn.params['precision'] == (precision, precision), eqn


@slow
class TestFlashInterpret:
    @pytest.mark.parametrize('lq,lk,causal', [
        (256, 256, True), (256, 256, False),
        (200, 200, True),           # non-divisible: internal padding
        (128, 384, False),          # cross lengths
        (300, 130, True),           # ragged both ways
    ])
    def test_forward_matches_exact(self, cpu, lq, lk, causal):
        q, k, v = _mk(2, 3, lq, lk, 64)
        out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                              backend='interpret')
        ref = _exact(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize('bwd', ['pallas', 'jnp'])
    @pytest.mark.parametrize('lq,lk,causal', [(192, 192, True),
                                              (100, 70, False)])
    def test_grad_matches_blockwise_autodiff(self, cpu, lq, lk, causal, bwd):
        q, k, v = _mk(2, 2, lq, lk, 32)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=64, block_k=64,
                backend='interpret', bwd=bwd) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(blockwise_attention(
                q, k, v, causal=causal, block_k=64) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, rtol=1e-3)

    @pytest.mark.parametrize('lq,lk,causal', [
        (256, 256, True),
        (200, 200, True),           # non-divisible: internal padding
        (128, 384, False),          # cross lengths
        (300, 130, True),           # ragged both ways, padded q rows
    ])
    def test_pallas_bwd_matches_jnp_bwd(self, cpu, lq, lk, causal):
        """The two backward implementations of the SAME custom_vjp (fused
        Pallas kernels vs the kv-block jnp scan) must agree bit-tightly —
        identical math, identical residuals, no MXU in interpret mode."""
        q, k, v = _mk(2, 2, lq, lk, 64)
        do = jnp.asarray(_RNG.standard_normal((2, 2, lq, 64)), jnp.float32)

        def run(bwd):
            def f(q, k, v):
                return flash_attention(q, k, v, causal=causal, block_q=64,
                                       block_k=64, backend='interpret',
                                       bwd=bwd)
            _, vjp = jax.vjp(f, q, k, v)
            return vjp(do)

        gp, gj = run('pallas'), run('jnp')
        for a, b in zip(gp, gj):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize('h,hkv,causal', [
        (8, 2, True), (8, 2, False),
        (4, 1, True),               # MQA: one shared kv head
        (6, 3, False),
    ])
    def test_gqa_matches_repeated_kv(self, cpu, h, hkv, causal):
        """Grouped-query attention: q with H heads over kv with Hkv heads
        must equal MHA over explicitly repeated kv — forward and gradients
        (dk/dv group-summed to the kv head shapes)."""
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.standard_normal((2, h, 200, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, hkv, 200, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, hkv, 200, 32)), jnp.float32)
        g = h // hkv
        kr, vr = jnp.repeat(k, g, axis=-3), jnp.repeat(v, g, axis=-3)

        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              backend='interpret')
        ref = blockwise_attention(q, kr, vr, causal=causal, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

        def loss_gqa(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=64, block_k=64,
                backend='interpret') ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(blockwise_attention(
                q, jnp.repeat(k, g, axis=-3), jnp.repeat(v, g, axis=-3),
                causal=causal, block_k=64) ** 2)

        gp = jax.grad(loss_gqa, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        assert gp[1].shape == k.shape and gp[2].shape == v.shape
        for a, b in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, rtol=1e-3)

    def test_gqa_bad_head_ratio_rejected(self, cpu):
        q = jnp.ones((2, 8, 64, 32))
        k = jnp.ones((2, 3, 64, 32))
        with pytest.raises(ValueError, match='multiple of kv heads'):
            flash_attention(q, k, k, backend='interpret')
        with pytest.raises(ValueError, match='multiple of kv heads'):
            flash_attention(q, k, k, backend='jnp')

    def test_bf16_forward(self, cpu):
        q, k, v = _mk(1, 2, 128, 128, 64, jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              backend='interpret')
        ref = _exact(*(x.astype(jnp.float32) for x in (q, k, v)), True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=3e-2)

    @pytest.mark.parametrize('case', ['plain', 'segments', 'gqa'])
    def test_bf16_grads_match_float32_oracle(self, cpu, case):
        """bf16 inputs (bf16 MXU operands, float32 accumulation) against
        the float32 jnp path on the same values: output and gradients within
        1e-2 of the oracle's largest value. These cases read 1.8e-3 to
        5.3e-3; with float32 operands in the kernels, 1.8e-3 to 3.7e-3."""
        rng = np.random.default_rng(11)
        hkv = 2 if case == 'gqa' else 4
        q, k, v, do = (jnp.asarray(rng.standard_normal((1, h, 512, 64)),
                                   jnp.bfloat16)
                       for h in (4, hkv, hkv, 4))
        seg = None
        if case == 'segments':
            seg = jnp.asarray(np.repeat([0, 1, 2], [200, 180, 132])[None])

        def run(backend, *args):
            o, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128,
                backend=backend, segment_ids=seg), *args)
            return (o,) + vjp(do.astype(o.dtype))

        got = run('interpret', q, k, v)
        want = run('jnp', *(x.astype(jnp.float32) for x in (q, k, v)))
        for a, b in zip(got, want):
            assert a.dtype == jnp.bfloat16
            b = np.asarray(b)
            err = np.max(np.abs(np.asarray(a, np.float32) - b))
            assert err <= 1e-2 * np.max(np.abs(b)), err

    def test_jnp_backend_is_blockwise(self, cpu):
        q, k, v = _mk(1, 1, 64, 64, 16)
        a = flash_attention(q, k, v, causal=True, backend='jnp')
        b = blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@slow
@pytest.mark.skipif(jax.default_backend() != 'tpu',
                    reason='needs real TPU hardware')
class TestFlashTPU:
    """Hardware equality: Pallas kernel vs blockwise at matching (MXU bf16
    multiply) precision; validated manually on v5e, runs in TPU CI."""

    @pytest.mark.parametrize('dtype,tol', [(jnp.float32, 3e-3),
                                           (jnp.bfloat16, 2e-2)])
    def test_forward_matches_blockwise(self, dtype, tol):
        q, k, v = _mk(2, 4, 1024, 1024, 64, dtype)
        ref = blockwise_attention(q, k, v, causal=True, block_k=256)
        out = flash_attention(q, k, v, causal=True, backend='pallas')
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                    - out.astype(jnp.float32))))
        assert err < tol, err

    @pytest.mark.parametrize('lq,lk,causal,dtype,tol', [
        (1024, 1024, True, jnp.float32, 1e-2),
        (1000, 1000, True, jnp.float32, 1e-2),   # non-divisible lengths
        (512, 768, False, jnp.bfloat16, 5e-2),
    ])
    def test_backward_kernels_match_blockwise(self, lq, lk, causal, dtype,
                                              tol):
        """Fused Pallas backward (dq + dk/dv kernels) vs blockwise autodiff
        on hardware; tolerance is relative (MXU bf16-multiply rounding)."""
        q, k, v = _mk(2, 4, lq, lk, 64, dtype)

        def loss_pallas(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, backend='pallas',
                bwd='pallas').astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(blockwise_attention(
                q, k, v, causal=causal, block_k=256).astype(jnp.float32) ** 2)

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gb):
            a32, b32 = (np.asarray(x, np.float32) for x in (a, b))
            rel = np.max(np.abs(a32 - b32)) / (np.max(np.abs(b32)) + 1e-9)
            assert rel < tol, rel

    def test_gqa_on_hardware(self):
        """GQA via the kv head map (no repeated kv in HBM) vs repeated-kv
        blockwise, forward and gradients."""
        rng = np.random.default_rng(9)
        q = jnp.asarray(rng.standard_normal((2, 8, 512, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 2, 512, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 2, 512, 64)), jnp.float32)
        kr, vr = jnp.repeat(k, 4, axis=-3), jnp.repeat(v, 4, axis=-3)

        out = flash_attention(q, k, v, causal=True, backend='pallas')
        ref = blockwise_attention(q, kr, vr, causal=True, block_k=256)
        rel = float(jnp.max(jnp.abs(out - ref))) / float(jnp.max(jnp.abs(ref)))
        assert rel < 1e-2, rel

        gp = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, backend='pallas') ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(blockwise_attention(
            q, jnp.repeat(k, 4, -3), jnp.repeat(v, 4, -3), causal=True,
            block_k=256) ** 2), argnums=(0, 1, 2))(q, k, v)
        assert gp[1].shape == k.shape
        for a, b in zip(gp, gr):
            rel = (float(jnp.max(jnp.abs(a - b)))
                   / (float(jnp.max(jnp.abs(b))) + 1e-9))
            assert rel < 1e-2, rel

    def test_flash_ring_on_hardware(self):
        """Single-chip {'seq': 1} mesh drives the full ring-flash custom_vjp
        (per-chunk kernels under shard_map) on hardware."""
        from petastorm_tpu.parallel import make_mesh
        from petastorm_tpu.parallel.ring import make_ring_attention
        q, k, v = _mk(2, 4, 512, 512, 64)
        mesh = make_mesh({'seq': 1}, devices=jax.devices()[:1])
        fn = make_ring_attention(mesh, 'seq', causal=True, impl='pallas')
        ref = blockwise_attention(q, k, v, causal=True, block_k=256)
        out = fn(q, k, v)
        rel = float(jnp.max(jnp.abs(out - ref))) / float(jnp.max(jnp.abs(ref)))
        assert rel < 1e-2, rel
        gp = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(lambda q, k, v: jnp.sum(blockwise_attention(
            q, k, v, causal=True, block_k=256) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gb):
            rel = (float(jnp.max(jnp.abs(a - b)))
                   / (float(jnp.max(jnp.abs(b))) + 1e-9))
            assert rel < 1e-2, rel

    def test_train_step_with_flash(self):
        from petastorm_tpu.models import transformer_lm as tlm
        cfg = tlm.TransformerConfig(vocab_size=512, d_model=128, n_heads=2,
                                    n_layers=2, d_ff=256, max_seq_len=256,
                                    attention='flash')
        params = tlm.init(jax.random.PRNGKey(0), cfg)
        opt, step = tlm.make_train_step(cfg)
        st = opt.init(params)
        toks = jnp.asarray(_RNG.integers(0, 512, (4, 256)), jnp.int32)
        params, st, loss = step(params, st, toks, toks)
        assert np.isfinite(float(loss))
