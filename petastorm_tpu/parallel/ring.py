"""Ring attention: exact attention over sequences sharded across devices.

Each device holds one sequence chunk of q/k/v. K/V chunks rotate around the
mesh axis with ``jax.lax.ppermute`` (XLA lowers this to ICI neighbor sends)
while every device folds each visiting chunk into its online-softmax
accumulators — compute on chunk j overlaps the transfer of chunk j+1, so the
ring latency hides behind the attention FLOPs. Memory per device stays
O(L_local²-free): only (o, m, l) accumulators and one in-flight kv chunk.

This is the long-context/sequence-parallel capability the data-side NGram
assembler (``petastorm_tpu/ngram.py``) feeds; model-side it composes with data
and tensor parallelism over the same mesh (axes 'data'/'seq'/'model').

Use inside ``jax.shard_map`` with q/k/v partitioned over ``axis_name`` on the
sequence dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from petastorm_tpu.ops.attention import (
    _NEG_INF, _FlashDims, _flash_backward_from_prepared,
    _prepare_flash_bwd_q_side, attention_block_step, finalize_attention,
    flash_attention_with_lse, merge_attention_chunks)


def resolve_ring_impl(impl, mesh=None) -> str:
    """Resolve the per-chunk compute implementation. An explicit ``impl``
    wins; otherwise pick 'pallas' exactly when the devices that will run the
    shard_map are TPUs — the MESH decides, not ``jax.default_backend()``
    (a CPU mesh on a TPU-attached host must get the jnp path)."""
    if impl is not None:
        return impl
    if mesh is not None:
        platform = next(iter(mesh.devices.flat)).platform
    else:
        platform = jax.default_backend()
    return 'pallas' if platform == 'tpu' else 'jnp'


def ring_attention(q, k, v, axis_name: str, *, causal: bool = True,
                   impl: str = None, block_q: int = 256, block_k: int = 512):
    """Exact (optionally causal) attention over a ring-sharded sequence.

    Args:
        q, k, v: local chunks ``(..., L_local, D)``; the global sequence is the
            concatenation of chunks in mesh-axis order.
        axis_name: mesh axis the sequence is sharded over.
        causal: mask by *global* token positions.
        impl: per-chunk compute — 'pallas' runs every visiting chunk through
            the fused flash kernels (forward AND backward, via a ring-aware
            custom_vjp), 'jnp' the blockwise online-softmax update (any
            backend, plain autodiff), 'interpret' the Pallas interpreter
            (CI on CPU). Default (None): by ``jax.default_backend()`` —
            callers that know the mesh should resolve via
            :func:`resolve_ring_impl` instead (``make_ring_attention`` does),
            so CPU meshes on TPU-attached hosts get the jnp path.
        block_q, block_k: kernel block sizes for the Pallas path.

    Returns the local output chunk ``(..., L_local, D)`` in q's dtype.
    """
    impl = resolve_ring_impl(impl)
    if impl in ('pallas', 'interpret'):
        # GQA (kv with fewer heads) flows through natively: the per-chunk
        # flash kernels read shared kv via the head map, and fewer kv heads
        # also shrink the rotating ppermute payload.
        return _ring_flash(q, k, v, axis_name, causal, block_q, block_k,
                           impl == 'interpret')
    if impl != 'jnp':
        raise ValueError("impl must be 'pallas', 'jnp' or 'interpret', "
                         "got %r" % (impl,))
    if q.shape[:-2] != k.shape[:-2]:
        # the jnp block update needs matching head counts; repeat kv here so
        # both impls accept the same GQA inputs (the Pallas path stays the
        # memory-efficient one). _FlashDims validates the head ratio with
        # the same error the Pallas path raises.
        _FlashDims(q.shape, k.shape, block_q, block_k)
        group = q.shape[-3] // k.shape[-3]
        k = jnp.repeat(k, group, axis=-3)
        v = jnp.repeat(v, group, axis=-3)
    orig_dtype = q.dtype
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    l_local = q.shape[-2]

    q_pos = my_idx * l_local + jnp.arange(l_local)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend(k_cur, v_cur, o, m, l, ring_step):
        src_idx = (my_idx - ring_step) % n       # whose chunk we hold this step
        k_pos = src_idx * l_local + jnp.arange(l_local)
        return attention_block_step(
            q32, k_cur, v_cur, o, m, l,
            q_positions=q_pos, k_positions=k_pos, causal=causal)

    def step(carry, ring_step):
        k_cur, v_cur, o, m, l = carry
        o, m, l = attend(k_cur, v_cur, o, m, l, ring_step)
        # Rotate kv to the next device; XLA overlaps this with the next
        # iteration's compute when possible.
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, o, m, l), None

    # Derive accumulators from q32 so they carry the same shard_map
    # varying-axes type as the rotating kv chunks (scan carry typing).
    o0 = jnp.zeros_like(q32)
    m0 = jnp.full_like(q32[..., 0], -1e30)
    l0 = jnp.zeros_like(q32[..., 0])
    # n-1 rotate-and-attend steps, then fold the last visiting chunk without
    # rotating it onward (the n-th ppermute's output is never read).
    (k_fin, v_fin, o, m, l), _ = jax.lax.scan(
        step, (k32, v32, o0, m0, l0), jnp.arange(n - 1))
    o, m, l = attend(k_fin, v_fin, o, m, l, n - 1)
    return finalize_attention(o, l).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Pallas-kernel ring: per-chunk flash forward/backward + logsumexp merge
# ---------------------------------------------------------------------------

def _chunk_case(src_idx, my_idx):
    """0 = fully visible (src strictly before my queries), 1 = diagonal
    (local causal mask), 2 = fully masked (src strictly after)."""
    return jnp.where(src_idx == my_idx, 1,
                     jnp.where(src_idx < my_idx, 0, 2))


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, block_q, block_k,
                         interpret):
    """Forward ring with per-chunk flash kernels. Returns ``(o, lse)`` — o in
    q's dtype, lse float32 ``(..., L_local)`` = the GLOBAL per-row logsumexp
    (saved as the backward's residual).

    Chunks are globally position-aligned, so causality degenerates to three
    whole-chunk cases (``_chunk_case``); the diagonal chunk runs the causal
    kernel, earlier chunks the non-causal one, later chunks are skipped."""
    orig_dtype = q.dtype
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def chunk_summary(k_cur, v_cur, ring_step):
        def full(_):
            return flash_attention_with_lse(
                q, k_cur, v_cur, causal=False, block_q=block_q,
                block_k=block_k, interpret=interpret)

        def diag(_):
            return flash_attention_with_lse(
                q, k_cur, v_cur, causal=True, block_q=block_q,
                block_k=block_k, interpret=interpret)

        def none(_):
            # derive from the operands so the outputs carry the same
            # varying-mesh-axes type as the kernel branches (shard_map vma)
            return (q * jnp.zeros((), q.dtype),
                    q[..., 0].astype(jnp.float32) * 0.0 + _NEG_INF)

        if not causal:
            return full(None)
        src_idx = (my_idx - ring_step) % n
        return jax.lax.switch(_chunk_case(src_idx, my_idx),
                              [full, diag, none], None)

    def step(carry, ring_step):
        k_cur, v_cur, o_acc, m, l = carry
        o_i, lse_i = chunk_summary(k_cur, v_cur, ring_step)
        o_acc, m, l = merge_attention_chunks(o_acc, m, l, o_i, lse_i)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, o_acc, m, l), None

    # Derive accumulators from q so they carry the same shard_map
    # varying-axes type as the rotating kv chunks (scan carry typing).
    qz = q.astype(jnp.float32) * 0.0
    o0 = qz
    m0 = qz[..., 0] + _NEG_INF
    l0 = qz[..., 0]
    (k_fin, v_fin, o_acc, m, l), _ = jax.lax.scan(
        step, (k, v, o0, m0, l0), jnp.arange(n - 1))
    o_i, lse_i = chunk_summary(k_fin, v_fin, n - 1)
    o_acc, m, l = merge_attention_chunks(o_acc, m, l, o_i, lse_i)
    o = finalize_attention(o_acc, l).astype(orig_dtype)
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.where(l > 0.0, l, 1.0)),
                    _NEG_INF)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, causal, block_q, block_k, interpret):
    o, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, block_q, block_k,
                                interpret)
    return o


def _ring_flash_fwd(q, k, v, axis_name, causal, block_q, block_k, interpret):
    o, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, block_q,
                                  block_k, interpret)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, causal, block_q, block_k, interpret, res, do):
    """Ring backward: kv chunks rotate a FULL cycle together with their
    gradient accumulators, so each (dk, dv) collects every device's
    contribution and arrives back at its owner after n steps. dq accumulates
    locally. Per chunk pair, the fused backward kernels recompute p from the
    global lse residual — already the global softmax probabilities, so
    contributions just sum."""
    q, k, v, o, lse = res
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    # accumulator init derived from the operands (shard_map vma typing)
    zeros_q = q.astype(jnp.float32) * 0.0
    zeros_kv = k.astype(jnp.float32) * 0.0
    # q-side operands (padded q/do, lse/Δ columns) are step-invariant:
    # prepared once here, only the kv chunk varies inside the scan.
    dims = _FlashDims(q.shape, k.shape, block_q, block_k)
    prep = _prepare_flash_bwd_q_side(dims, q, o, lse, do)

    def pair_grads(k_cur, v_cur, ring_step):
        def full(_):
            return _flash_backward_from_prepared(
                dims, prep, k_cur, v_cur, causal=False, interpret=interpret)

        def diag(_):
            return _flash_backward_from_prepared(
                dims, prep, k_cur, v_cur, causal=True, interpret=interpret)

        def none(_):
            # zeros derived from the operands: same vma type as the kernels
            return (q * jnp.zeros((), q.dtype),
                    k_cur * jnp.zeros((), k_cur.dtype),
                    v_cur * jnp.zeros((), v_cur.dtype))

        if not causal:
            return full(None)
        src_idx = (my_idx - ring_step) % n
        return jax.lax.switch(_chunk_case(src_idx, my_idx),
                              [full, diag, none], None)

    def step(carry, ring_step):
        k_cur, v_cur, dk_cur, dv_cur, dq_acc = carry
        dq_p, dk_p, dv_p = pair_grads(k_cur, v_cur, ring_step)
        dq_acc = dq_acc + dq_p.astype(jnp.float32)
        dk_cur = dk_cur + dk_p.astype(jnp.float32)
        dv_cur = dv_cur + dv_p.astype(jnp.float32)
        # Rotate the chunk AND its gradient accumulator onward; after n
        # process+rotate steps both are back home.
        rotated = [jax.lax.ppermute(x, axis_name, perm)
                   for x in (k_cur, v_cur, dk_cur, dv_cur)]
        return tuple(rotated) + (dq_acc,), None

    (k_fin, v_fin, dk, dv, dq), _ = jax.lax.scan(
        step, (k, v, zeros_kv, zeros_kv, zeros_q), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def make_ring_attention(mesh, seq_axis: str = 'seq', causal: bool = True,
                        impl: str = None):
    """Wrap :func:`ring_attention` in a ``shard_map`` over ``mesh``.

    Returns ``fn(q, k, v) -> out`` for global arrays of shape
    ``(batch, heads, L, D)`` with L sharded over ``seq_axis`` and batch over
    'data' when present in the mesh. ``impl`` as in :func:`ring_attention`.
    """
    from jax.sharding import PartitionSpec as P

    batch_axis = 'data' if 'data' in mesh.axis_names else None
    spec = P(batch_axis, None, seq_axis, None)
    impl = resolve_ring_impl(impl, mesh)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec)
    def fn(q, k, v):
        return ring_attention(q, k, v, seq_axis, causal=causal, impl=impl)

    return fn
