"""Attention kernels.

``flash_attention`` dispatches to a Pallas TPU kernel (online-softmax, never
materializes the (L, L) score matrix in HBM) and falls back to a
``lax.scan``-based blockwise jnp implementation on other backends. Both share
the same math, so tests can assert the Pallas path against the fallback.

The blockwise core is also the per-step building block of ring attention
(``petastorm_tpu/parallel/ring.py``): one (q-chunk, kv-chunk) partial update of
the running (o, m, l) accumulators.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# blockwise jnp core
# ---------------------------------------------------------------------------

def _block_update(q, k, v, o, m, l, scale, mask):
    """One online-softmax update: attend q against (k, v) and fold into the
    running (o, m, l) accumulators. Shapes: q (..., Lq, D), k/v (..., Lk, D),
    o (..., Lq, D), m/l (..., Lq)."""
    s = jnp.einsum('...qd,...kd->...qk', q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows: exp(-inf - (-inf)) -> exp(0); zero them via l
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    correction = jnp.exp(m - m_new)
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction[..., None] + jnp.einsum('...qk,...kd->...qd', p, v)
    return o_new, m_new, l_new


def attention_accumulators(q_len: int, head_dim: int, batch_shape=()):
    """Fresh (o, m, l) accumulators for online-softmax accumulation."""
    o = jnp.zeros(batch_shape + (q_len, head_dim), dtype=jnp.float32)
    m = jnp.full(batch_shape + (q_len,), _NEG_INF, dtype=jnp.float32)
    l = jnp.zeros(batch_shape + (q_len,), dtype=jnp.float32)
    return o, m, l


def finalize_attention(o, l):
    """Normalize accumulated output; fully-masked rows yield zeros."""
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return o / safe_l[..., None]


def attention_block_step(q, k, v, o, m, l, *, scale=None,
                         q_positions=None, k_positions=None, causal=True):
    """Public building block used by ring attention: fold one kv chunk into the
    accumulators, masking by absolute token positions when ``causal``."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    mask = None
    if causal:
        if q_positions is None or k_positions is None:
            raise ValueError('causal masking needs q_positions/k_positions')
        mask = q_positions[..., :, None] >= k_positions[..., None, :]
    return _block_update(q, k, v, o, m, l, scale, mask)


def _pad_kv(k32, v32, block_k: int):
    """Pad k/v along the sequence dim to a block multiple; returns
    (k, v, num_blocks)."""
    k_len = k32.shape[-2]
    pad = (-k_len) % block_k
    if pad:
        pad_width = [(0, 0)] * (k32.ndim - 2) + [(0, pad), (0, 0)]
        k32 = jnp.pad(k32, pad_width)
        v32 = jnp.pad(v32, pad_width)
    return k32, v32, (k_len + pad) // block_k


def _to_kv_blocks(x, num_blocks: int, block_k: int):
    """(..., nb*bk, D) -> (nb, ..., bk, D) for scanning."""
    x = jnp.moveaxis(x, -2, 0)
    x = x.reshape((num_blocks, block_k) + x.shape[1:])
    return jnp.moveaxis(x, 1, -2)


def _from_kv_blocks(xb, num_blocks: int, block_k: int):
    """Inverse of :func:`_to_kv_blocks`."""
    xb = jnp.moveaxis(xb, -2, 1)
    xb = xb.reshape((num_blocks * block_k,) + xb.shape[2:])
    return jnp.moveaxis(xb, 0, -2)


def _kv_block_mask(q_pos, blk_idx, block_k: int, kv_len: int, causal: bool,
                   window=None):
    """(Lq, bk) validity mask for one kv block: tail padding + causality +
    optional sliding window (attend only the last ``window`` positions)."""
    k_pos = blk_idx * block_k + jnp.arange(block_k)
    mask = jnp.broadcast_to(k_pos[None, :] < kv_len, (q_pos.shape[0], block_k))
    if causal:
        mask = mask & (q_pos[:, None] >= k_pos[None, :])
    if window is not None:
        mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
    return mask


def _seg_to_kv_blocks(seg, num_blocks: int, block_k: int, pad_value: int):
    """(..., L) segment ids → (nb, ..., bk) blocks (tail padded with a
    sentinel that never equals a real segment)."""
    pad = num_blocks * block_k - seg.shape[-1]
    if pad:
        seg = jnp.pad(seg, [(0, 0)] * (seg.ndim - 1) + [(0, pad)],
                      constant_values=pad_value)
    seg = jnp.moveaxis(seg, -1, 0)
    seg = seg.reshape((num_blocks, block_k) + seg.shape[1:])
    return jnp.moveaxis(seg, 1, -1)


def _segment_mask(seg_q, seg_k_blk):
    """(..., Lq) × (..., bk) → (..., Lq, bk) same-segment mask."""
    return seg_q[..., :, None] == seg_k_blk[..., None, :]


def _normalize_seg(seg, target_ndim: int, length: int, name: str):
    """Validate a segment-id array's sequence length and insert singleton
    head/batch axes until it broadcasts against ``(..., L)`` operands of
    ``target_ndim`` dims — callers pass ``(B, L)``, ``(L,)`` or the full
    per-head shape interchangeably. Ids must be non-negative (negative values
    collide with the internal pad sentinels); checked only for host-side
    inputs (lists/numpy) — validating a concrete on-device array would force
    a device→host sync per layer per eager step, so device arrays and
    tracers rely on the documented contract."""
    host_side = not isinstance(seg, jax.Array)
    if host_side:
        import numpy as _np
        if (_np.asarray(seg) < 0).any():
            raise ValueError('%s must be non-negative (negative ids collide '
                             'with internal padding sentinels)' % name)
    seg = jnp.asarray(seg)
    if seg.shape[-1] != length or seg.ndim > target_ndim:
        raise ValueError(
            '%s must have shape (..., %d) broadcastable over the attention '
            'operands; got %r' % (name, length, seg.shape))
    while seg.ndim < target_ndim:
        seg = seg[..., None, :]
    return seg


def _repeat_kv_seg(kv_seg, k, group: int):
    """When the jnp GQA fallback head-repeats k/v, a PER-HEAD kv segment-id
    array (carrying the kv head axis) must be repeated the same way; head-free
    ``(B, L)`` / ``(L,)`` ids broadcast and pass through unchanged."""
    if kv_seg is None or group == 1:
        return kv_seg
    kv_seg = jnp.asarray(kv_seg)
    if kv_seg.ndim >= k.ndim - 1 and kv_seg.shape[-2] == k.shape[-3]:
        return jnp.repeat(kv_seg, group, axis=-2)
    return kv_seg


def _check_window(window, causal: bool):
    """Sliding-window attention is defined here as Mistral-style: each token
    attends the previous ``window`` positions, which only makes sense under
    causal masking."""
    if window is None:
        return
    if not causal:
        raise ValueError('window requires causal=True (sliding-window '
                         'attention looks back, not around)')
    if window < 1:
        raise ValueError('window must be >= 1, got %r' % (window,))


def _resolve_segs(segment_ids, kv_segment_ids, q_ndim: int, k_ndim: int,
                  q_len: int, kv_len: int):
    """ONE definition of segment-argument semantics for every path (jnp
    blockwise, jnp backward, Pallas forward/backward): kv ids default to the
    q ids; kv-only masking is rejected loudly instead of silently ignored.
    Returns ``(seg_q, kv_seg)`` normalized, or ``(None, None)``."""
    if segment_ids is None:
        if kv_segment_ids is not None:
            raise ValueError('kv_segment_ids requires segment_ids (kv-only '
                             'masking has no q-side ids to compare against)')
        return None, None
    seg_q = _normalize_seg(segment_ids, q_ndim - 1, q_len, 'segment_ids')
    kv_seg = segment_ids if kv_segment_ids is None else kv_segment_ids
    kv_seg = _normalize_seg(kv_seg, k_ndim - 1, kv_len, 'kv_segment_ids')
    return seg_q, kv_seg


def blockwise_attention(q, k, v, *, causal: bool = True, block_k: int = 512,
                        segment_ids=None, kv_segment_ids=None, window=None):
    """Memory-efficient attention: scan over key/value blocks with online
    softmax. Works on any backend; O(L·block_k) live memory per head.

    Shapes: q/k/v ``(..., L, D)``; returns ``(..., L, D)`` in q's dtype.
    ``segment_ids`` ``(..., Lq)`` restricts attention to same-segment pairs
    (packed sequences); ``kv_segment_ids`` defaults to ``segment_ids``.
    ``window`` restricts each token to the last ``window`` positions
    (sliding-window/local attention; requires ``causal=True``).
    """
    _check_window(window, causal)
    orig_dtype = q.dtype
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    scale = 1.0 / math.sqrt(q.shape[-1])
    q_len, k_len = q.shape[-2], k.shape[-2]
    batch_shape = q.shape[:-2]

    k32, v32, num_blocks = _pad_kv(k32, v32, block_k)
    kb = _to_kv_blocks(k32, num_blocks, block_k)
    vb = _to_kv_blocks(v32, num_blocks, block_k)
    seg_q, kv_seg = _resolve_segs(segment_ids, kv_segment_ids, q.ndim,
                                  k.ndim, q_len, k_len)
    if seg_q is not None:
        segb = _seg_to_kv_blocks(kv_seg, num_blocks, block_k, pad_value=-2)
    q_pos = jnp.arange(q_len)
    o, m, l = attention_accumulators(q_len, q.shape[-1], batch_shape)
    # under shard_map the scan carry must vary over the operands' mesh axes
    vma = tuple(_out_vma(q32, k32, v32,
                         *(x for x in (seg_q, kv_seg) if x is not None)))
    if vma:
        o, m, l = (jax.lax.pcast(x, vma, to='varying') for x in (o, m, l))

    def step(carry, inputs):
        o, m, l = carry
        if segment_ids is not None:
            k_blk, v_blk, seg_blk, blk_idx = inputs
            mask = (_kv_block_mask(q_pos, blk_idx, block_k, k_len, causal,
                                   window)
                    & _segment_mask(seg_q, seg_blk))
        else:
            k_blk, v_blk, blk_idx = inputs
            mask = _kv_block_mask(q_pos, blk_idx, block_k, k_len, causal,
                                  window)
        o, m, l = _block_update(q32, k_blk, v_blk, o, m, l, scale, mask)
        return (o, m, l), None

    xs = ((kb, vb, segb, jnp.arange(num_blocks)) if segment_ids is not None
          else (kb, vb, jnp.arange(num_blocks)))
    (o, m, l), _ = jax.lax.scan(step, (o, m, l), xs)
    return finalize_attention(o, l).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------

def _mxu_dot(a, b, dimension_numbers):
    """One MXU product of the kernels: the operands in their common dtype
    (the inputs' own, so bf16 tiles stay bf16), accumulated in float32.

    Operands narrower than float32 multiply exactly at any precision, so they
    take the default one: Mosaic refuses bf16 operands at the float32
    precision a global ``jax_default_matmul_precision`` would ask for.
    float32 operands follow that setting, as any matmul does."""
    dtype = jnp.promote_types(a.dtype, b.dtype)
    precision = None if dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype),
                               dimension_numbers, precision=precision,
                               preferred_element_type=jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, *refs, block_q: int,
                  block_k: int, causal: bool, scale: float, kv_seq_len: int,
                  num_kv_blocks: int, with_lse: bool, segmented: bool = False,
                  window=None):
    """One (batch·head, q-block, kv-block) grid step.

    KV **streams through the grid**: each program sees only a (block_k, D)
    slice of k/v in VMEM — bounded VMEM at any sequence length (the previous
    revision pinned the full kv sequence per program, ~2·L·D·4B, which blew
    VMEM exactly in the long-context regime the kernel exists for). The
    online-softmax accumulators (o, m, l) persist across the sequential
    kv-block grid dimension in VMEM scratch; the final kv step normalizes and
    writes the output block plus its logsumexp (saved for the backward).
    ``segmented`` adds per-token segment ids (packed sequences): pairs in
    different segments are masked out.
    """
    from jax.experimental import pallas as pl

    if segmented:
        segq_ref, segkv_ref, *refs = refs
    else:
        segq_ref = segkv_ref = None
    o_ref, *refs = refs
    if with_lse:
        lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        lse_ref, (acc_ref, m_ref, l_ref) = None, refs
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        # Skip kv blocks strictly above the causal diagonal for this q block;
        # a sliding window additionally skips blocks entirely behind it.
        needed = kv_idx * block_k <= (q_idx + 1) * block_q - 1
        if window is not None:
            needed &= (kv_idx + 1) * block_k - 1 >= q_idx * block_q - window + 1
    else:
        needed = kv_idx >= 0

    @pl.when(needed)
    def _compute():
        s = _mxu_dot(q_ref[...], k_ref[...], (((1,), (1,)), ((), ()))) * scale
        k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        mask = k_pos < kv_seq_len                      # tail-padding mask
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            mask = mask & (q_pos >= k_pos)
            if window is not None:
                mask = mask & (q_pos - k_pos < window)
        if segmented:
            # segq (bq, 1); segkv stored sublane-replicated (8, bk)
            mask = mask & (segq_ref[...] == segkv_ref[0:1, :])
        mask = jnp.broadcast_to(mask, s.shape)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...][:, :1]                     # (bq, 1)
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...]
        acc_ref[...] = acc_ref[...] * corr + _mxu_dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kv_idx == num_kv_blocks - 1)
    def _final():
        l = l_ref[...][:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        if with_lse:
            lse = jnp.where(l == 0.0, jnp.float32(_NEG_INF),
                            m_ref[...][:, :1] + jnp.log(safe_l))
            # (bq, 128) lane-replicated: TPU blocks want last-two dims
            # (8, 128)-divisible, so a 1-D (bq,) output block is not lowerable.
            lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _out_vma(*arrays):
    """Union of the inputs' varying-mesh-axes sets, for pallas_call out_shapes
    under ``shard_map`` (its vma check requires outputs to declare how they
    vary across mesh axes; kernel outputs vary exactly over the axes the
    operands do). Empty outside ``shard_map``."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def _sds(shape, dtype, vma):
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


class _FlashDims:
    """Shared clamp/pad/flatten preamble of the forward and backward Pallas
    calls — ONE definition of the block-clamping and padding policy, so the
    backward always recomputes p against residuals padded under exactly the
    forward's rules.

    ``pad_q_like``/``pad_kv_like`` zero-pad the sequence dim to a block
    multiple and flatten batch dims to ``(flat, L, D)``; ``pad_rows`` does
    the same for per-q-row vectors ``(..., Lq)`` → ``(flat, Lq, 1)``
    (zero pad: backward padded rows have q == do == 0, so p = exp(0 − 0)
    stays finite and every contribution vanishes).

    Grouped-query attention: when kv carries fewer heads than q (shapes
    equal except axis -3, q heads a multiple of kv heads), ``group`` > 1 and
    ``kv_program_index`` maps a q program to the kv row its head shares —
    the kernels read shared kv blocks directly instead of materializing
    ``jnp.repeat``-ed kv in HBM."""

    def __init__(self, q_shape, kv_shape, block_q: int, block_k: int):
        *batch, q_len, head_dim = q_shape
        *kv_batch, kv_len, kv_head_dim = kv_shape
        self.batch = tuple(batch)
        self.kv_batch = tuple(kv_batch)
        if self.batch == self.kv_batch:
            self.group = 1
        else:
            if (kv_head_dim != head_dim
                    or len(self.batch) != len(self.kv_batch)
                    or not self.batch
                    or self.batch[:-1] != self.kv_batch[:-1]
                    or self.kv_batch[-1] <= 0
                    or self.batch[-1] % self.kv_batch[-1] != 0):
                raise ValueError(
                    'q/kv batch dims must match, or differ only in the head '
                    'axis (-3) with q heads a multiple of kv heads (GQA); '
                    'got q %r vs kv %r' % (q_shape, kv_shape))
            self.group = self.batch[-1] // self.kv_batch[-1]
        self.n_q_heads = self.batch[-1] if self.batch else 1
        self.n_kv_heads = self.kv_batch[-1] if self.kv_batch else 1
        self.q_len, self.kv_len, self.head_dim = q_len, kv_len, head_dim
        self.bq = min(block_q, q_len)
        self.bk = min(block_k, kv_len)
        self.pad_q = (-q_len) % self.bq
        self.pad_k = (-kv_len) % self.bk
        self.pq_len, self.pk_len = q_len + self.pad_q, kv_len + self.pad_k
        self.flat = int(math.prod(batch)) if batch else 1
        self.kv_flat = int(math.prod(kv_batch)) if kv_batch else 1
        self.num_q_blocks = self.pq_len // self.bq
        self.num_kv_blocks = self.pk_len // self.bk
        self.scale = 1.0 / math.sqrt(head_dim)

    def kv_program_index(self):
        """flat q-program index → flat kv row index (identity unless GQA)."""
        if self.group == 1:
            return lambda b: b
        h, hkv, g = self.n_q_heads, self.n_kv_heads, self.group
        return lambda b: (b // h) * hkv + (b % h) // g

    def _pad_flatten(self, x, pad, plen, flat):
        if pad:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])
        return x.reshape(flat, plen, self.head_dim)

    def pad_q_like(self, x):
        return self._pad_flatten(x, self.pad_q, self.pq_len, self.flat)

    def pad_kv_like(self, x):
        return self._pad_flatten(x, self.pad_k, self.pk_len, self.kv_flat)

    def pad_rows(self, x):
        if self.pad_q:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, self.pad_q)])
        return x.astype(jnp.float32).reshape(self.flat, self.pq_len, 1)

    def unpad_q_like(self, x):
        return x[:, :self.q_len, :].reshape(
            self.batch + (self.q_len, self.head_dim))

    def unpad_kv_like(self, x):
        return x[:, :self.kv_len, :].reshape(
            self.kv_batch + (self.kv_len, self.head_dim))

    def pad_seg_q(self, seg):
        """Broadcast + pad q-side segment ids to ``(flat, pq_len, 1)`` int32
        (pad sentinel -1: padded q rows match nothing real)."""
        seg = jnp.broadcast_to(seg, self.batch + (self.q_len,))
        if self.pad_q:
            seg = jnp.pad(seg, [(0, 0)] * (seg.ndim - 1) + [(0, self.pad_q)],
                          constant_values=-1)
        return seg.astype(jnp.int32).reshape(self.flat, self.pq_len, 1)

    def pad_seg_kv(self, seg):
        """Broadcast + pad kv-side segment ids to ``(kv_flat, 8, pk_len)``
        int32 — sublane-replicated so the kernel's ``(8, bk)`` block is
        lowerable; pad sentinel -2 never equals a q-side id."""
        seg = jnp.broadcast_to(seg, self.kv_batch + (self.kv_len,))
        if self.pad_k:
            seg = jnp.pad(seg, [(0, 0)] * (seg.ndim - 1) + [(0, self.pad_k)],
                          constant_values=-2)
        seg = seg.astype(jnp.int32).reshape(self.kv_flat, 1, self.pk_len)
        return jnp.broadcast_to(seg, (self.kv_flat, 8, self.pk_len))

    def check_segment_blocks(self, interpret: bool):
        """The kv segment block rides with ``block_k`` lanes; Mosaic wants
        the lane dim a multiple of 128 (or the full array dim). Interpret
        mode has no such constraint."""
        if not interpret and self.bk % 128 != 0 and self.bk != self.pk_len:
            raise ValueError(
                'segment_ids on the TPU Pallas path need block_k %% 128 == 0 '
                '(got block_k=%d); use the default block sizes or interpret '
                'mode' % self.bk)

    def sum_head_groups(self, x):
        """Per-q-head kv gradients ``(flat, L, D)`` → per-kv-head
        ``(kv_flat, L, D)`` by summing each head group (identity when not
        GQA). Inputs should be float32 — the group sum happens before any
        cast back to the storage dtype."""
        if self.group == 1:
            return x
        b = self.flat // self.n_q_heads
        return x.reshape(b, self.n_kv_heads, self.group,
                         *x.shape[1:]).sum(axis=2).reshape(
                             self.kv_flat, *x.shape[1:])


def _pallas_flash(q, k, v, causal: bool, block_q: int, block_k: int,
                  interpret: bool = False, with_lse: bool = True,
                  segment_ids=None, kv_segment_ids=None, window=None):
    """Returns ``(o, lse)`` with o in q's dtype and lse float32 ``(..., Lq)``
    — lse is None when ``with_lse=False`` (the no-grad forward skips the
    lane-replicated lse write entirely). Non-block-divisible lengths are
    padded and the pad is masked/sliced. ``segment_ids`` masks cross-segment
    pairs (packed sequences)."""
    from jax.experimental import pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    dims = _FlashDims(q.shape, k.shape, block_q, block_k)
    batch, q_len, head_dim = dims.batch, dims.q_len, dims.head_dim
    kv_len, bq, bk, flat = dims.kv_len, dims.bq, dims.bk, dims.flat
    pq_len, num_kv_blocks = dims.pq_len, dims.num_kv_blocks
    scale = dims.scale
    kvmap = dims.kv_program_index()
    qf = dims.pad_q_like(q)
    kf = dims.pad_kv_like(k)
    vf = dims.pad_kv_like(v)
    seg_q, kv_seg = _resolve_segs(segment_ids, kv_segment_ids, q.ndim,
                                  k.ndim, q_len, kv_len)
    segmented = seg_q is not None
    in_specs = [
        pl.BlockSpec((None, bq, head_dim), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((None, bk, head_dim), lambda b, i, j: (kvmap(b), j, 0)),
        pl.BlockSpec((None, bk, head_dim), lambda b, i, j: (kvmap(b), j, 0)),
    ]
    inputs = [qf, kf, vf]
    if segmented:
        dims.check_segment_blocks(interpret)
        inputs += [dims.pad_seg_q(seg_q), dims.pad_seg_kv(kv_seg)]
        in_specs += [
            pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 8, bk), lambda b, i, j: (kvmap(b), 0, j)),
        ]

    kernel = functools.partial(
        _flash_kernel, block_q=bq, block_k=bk, causal=causal, scale=scale,
        kv_seq_len=kv_len, num_kv_blocks=num_kv_blocks, with_lse=with_lse,
        segmented=segmented, window=window)
    vma = _out_vma(q, k, v)
    out_specs = [pl.BlockSpec((None, bq, head_dim), lambda b, i, j: (b, i, 0))]
    out_shape = [_sds((flat, pq_len, head_dim), q.dtype, vma)]
    if with_lse:
        out_specs.append(pl.BlockSpec((None, bq, 128), lambda b, i, j: (b, i, 0)))
        out_shape.append(_sds((flat, pq_len, 128), jnp.float32, vma))
    result = pl.pallas_call(
        kernel,
        grid=(flat, pq_len // bq, num_kv_blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, head_dim), jnp.float32),   # o accumulator
            pltpu.VMEM((bq, 128), jnp.float32),        # running max (lanes equal)
            pltpu.VMEM((bq, 128), jnp.float32),        # running sum (lanes equal)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(*inputs)
    o = dims.unpad_q_like(result[0])
    if not with_lse:
        return o, None
    lse = result[1][:, :q_len, 0].reshape(batch + (q_len,))
    return o, lse


def _flash_backward(q, k, v, o, lse, do, *, causal: bool, block_k: int,
                    scale: Optional[float] = None, segment_ids=None,
                    kv_segment_ids=None, window=None):
    """Memory-efficient flash backward (any backend): scan over kv blocks,
    recomputing p from (q, k, lse); O(Lq·block_k) live memory.

    dq accumulates across blocks; dk/dv are block-local scan outputs.
    """
    orig_dtypes = (q.dtype, k.dtype, v.dtype)
    q32, k32, v32, o32, do32 = (x.astype(jnp.float32)
                                for x in (q, k, v, o, do))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    q_len, kv_len = q.shape[-2], k.shape[-2]
    bk = min(block_k, kv_len)
    k32, v32, num_blocks = _pad_kv(k32, v32, bk)
    kb = _to_kv_blocks(k32, num_blocks, bk)
    vb = _to_kv_blocks(v32, num_blocks, bk)
    seg_q, kv_seg = _resolve_segs(segment_ids, kv_segment_ids, q.ndim,
                                  k.ndim, q_len, kv_len)
    if seg_q is not None:
        segb = _seg_to_kv_blocks(kv_seg, num_blocks, bk, pad_value=-2)
    q_pos = jnp.arange(q_len)
    # D_i = rowsum(do_i * o_i) — the only residual beyond lse
    d_term = jnp.sum(do32 * o32, axis=-1)            # (..., Lq)

    def step(dq, inputs):
        if segment_ids is not None:
            k_blk, v_blk, seg_blk, blk_idx = inputs
            mask = (_kv_block_mask(q_pos, blk_idx, bk, kv_len, causal,
                                   window)
                    & _segment_mask(seg_q, seg_blk))
        else:
            k_blk, v_blk, blk_idx = inputs
            mask = _kv_block_mask(q_pos, blk_idx, bk, kv_len, causal, window)
        s = jnp.einsum('...qd,...kd->...qk', q32, k_blk) * scale
        p = jnp.exp(s - lse[..., None])
        p = jnp.where(jnp.broadcast_to(mask, p.shape), p, 0.0)
        dv_blk = jnp.einsum('...qk,...qd->...kd', p, do32)
        dp = jnp.einsum('...qd,...kd->...qk', do32, v_blk)
        ds = p * (dp - d_term[..., None]) * scale
        dq = dq + jnp.einsum('...qk,...kd->...qd', ds, k_blk)
        dk_blk = jnp.einsum('...qk,...qd->...kd', ds, q32)
        return dq, (dk_blk, dv_blk)

    dq0 = jnp.zeros(q32.shape, jnp.float32)
    xs = ((kb, vb, segb, jnp.arange(num_blocks)) if segment_ids is not None
          else (kb, vb, jnp.arange(num_blocks)))
    dq, (dkb, dvb) = jax.lax.scan(step, dq0, xs)
    dk = _from_kv_blocks(dkb, num_blocks, bk)[..., :kv_len, :]
    dv = _from_kv_blocks(dvb, num_blocks, bk)[..., :kv_len, :]
    return (dq.astype(orig_dtypes[0]), dk.astype(orig_dtypes[1]),
            dv.astype(orig_dtypes[2]))


def _bwd_recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *,
                        q_idx, kv_idx, block_q: int, block_k: int,
                        causal: bool, scale: float, kv_seq_len: int,
                        segq_ref=None, segkv_ref=None, window=None):
    """Shared recomputation block of both backward kernels: rebuild the
    probabilities p = exp(s − lse) for one (q-block, kv-block) tile (masking
    kv tail padding, causality, and — when segment refs are given — packed
    cross-segment pairs; lse == _NEG_INF marks a fully-masked row
    — forward convention — and exp would overflow there, so it is gated out
    explicitly), then ds = p·(do·vᵀ − Δ)·scale. Returns the q, k and do
    tiles as loaded (the MXU operands) plus float32 (p, ds)."""
    q = q_ref[...]
    k = k_ref[...]
    do = do_ref[...]
    lse = lse_ref[...]                              # (bq, 1) float32
    delta = delta_ref[...]                          # (bq, 1) float32
    s = _mxu_dot(q, k, (((1,), (1,)), ((), ()))) * scale
    k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    mask = k_pos < kv_seq_len
    if causal:
        q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        mask = mask & (q_pos >= k_pos)
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
    if segq_ref is not None:
        mask = mask & (segq_ref[...] == segkv_ref[0:1, :])
    mask = jnp.broadcast_to(mask, s.shape)
    live = mask & jnp.broadcast_to(lse > _NEG_INF / 2, s.shape)
    p = jnp.where(live, jnp.exp(s - lse), 0.0)      # (bq, bk)
    dp = _mxu_dot(do, v_ref[...], (((1,), (1,)), ((), ())))
    ds = p * (dp - delta) * scale                   # (bq, bk)
    return q, k, do, p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *refs, block_q: int, block_k: int,
                         causal: bool, scale: float, kv_seq_len: int,
                         num_kv_blocks: int, segmented: bool = False,
                         window=None):
    """dq pass: one (batch·head, q-block, kv-block) grid step; kv streams
    through the grid (like the forward), dq accumulates in VMEM scratch across
    the sequential kv dimension and is written on the final kv step.

    p is recomputed from (q, k, lse); ds = p·(do·vᵀ − Δ)·scale with
    Δ = rowsum(do·o) precomputed outside the kernel."""
    from jax.experimental import pallas as pl

    if segmented:
        segq_ref, segkv_ref, dq_ref, dq_acc = refs
    else:
        segq_ref = segkv_ref = None
        dq_ref, dq_acc = refs
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if causal:
        needed = kv_idx * block_k <= (q_idx + 1) * block_q - 1
        if window is not None:
            needed &= (kv_idx + 1) * block_k - 1 >= q_idx * block_q - window + 1
    else:
        needed = kv_idx >= 0

    @pl.when(needed)
    def _compute():
        _, k, _, _, ds = _bwd_recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_idx=q_idx,
            kv_idx=kv_idx, block_q=block_q, block_k=block_k, causal=causal,
            scale=scale, kv_seq_len=kv_seq_len, segq_ref=segq_ref,
            segkv_ref=segkv_ref, window=window)
        dq_acc[...] += _mxu_dot(ds.astype(k.dtype), k,
                                (((1,), (0,)), ((), ())))

    @pl.when(kv_idx == num_kv_blocks - 1)
    def _final():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           *refs, block_q: int,
                           block_k: int, causal: bool, scale: float,
                           kv_seq_len: int, num_q_blocks: int,
                           segmented: bool = False, window=None):
    """dk/dv pass: one (batch·head, kv-block, q-block) grid step; q (and do,
    lse, Δ) stream through the grid, dk/dv accumulate in VMEM scratch across
    the sequential q dimension. Padded q rows carry do == 0, so they
    contribute nothing and need no extra mask."""
    from jax.experimental import pallas as pl

    if segmented:
        segq_ref, segkv_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        segq_ref = segkv_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    kv_idx = pl.program_id(1)
    q_idx = pl.program_id(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if causal:
        needed = (q_idx + 1) * block_q - 1 >= kv_idx * block_k
        if window is not None:
            needed &= (kv_idx + 1) * block_k - 1 >= q_idx * block_q - window + 1
    else:
        needed = q_idx >= 0

    @pl.when(needed)
    def _compute():
        q, _, do, p, ds = _bwd_recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_idx=q_idx,
            kv_idx=kv_idx, block_q=block_q, block_k=block_k, causal=causal,
            scale=scale, kv_seq_len=kv_seq_len, segq_ref=segq_ref,
            segkv_ref=segkv_ref, window=window)
        dv_acc[...] += _mxu_dot(p.astype(do.dtype), do,
                                (((0,), (0,)), ((), ())))   # (bk, D)
        dk_acc[...] += _mxu_dot(ds.astype(q.dtype), q,
                                (((0,), (0,)), ((), ())))   # (bk, D)

    @pl.when(q_idx == num_q_blocks - 1)
    def _final():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _prepare_flash_bwd_q_side(dims: '_FlashDims', q, o, lse, do):
    """The q-side backward operands (padded q/do and the per-row lse/Δ
    columns) — step-invariant in ring attention, so callers scanning over kv
    chunks hoist this out of the loop instead of re-padding and re-reducing
    Δ = rowsum(do·o) per chunk."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return (dims.pad_q_like(q), dims.pad_q_like(do), dims.pad_rows(lse),
            dims.pad_rows(delta))


def _pallas_flash_backward(q, k, v, o, lse, do, *, causal: bool, block_q: int,
                           block_k: int, interpret: bool = False,
                           segment_ids=None, kv_segment_ids=None,
                           window=None):
    """Fused flash backward: two Pallas kernels (dq; dk/dv), both streaming
    the non-owned operand through the grid — bounded VMEM at any length, like
    the forward. Returns (dq, dk, dv) in the input dtypes.

    lse/Δ ride as ``(flat, L, 1)`` arrays with ``(bq, 1)`` blocks — the lane
    dim of the block equals the full array dim, which Mosaic lowers without
    the 128-lane replication the forward's lse *output* needs."""
    dims = _FlashDims(q.shape, k.shape, block_q, block_k)
    prep = _prepare_flash_bwd_q_side(dims, q, o, lse, do)
    seg_q, kv_seg = _resolve_segs(segment_ids, kv_segment_ids, q.ndim,
                                  k.ndim, dims.q_len, dims.kv_len)
    segs = None
    if seg_q is not None:
        dims.check_segment_blocks(interpret)
        segs = (dims.pad_seg_q(seg_q), dims.pad_seg_kv(kv_seg))
    return _flash_backward_from_prepared(dims, prep, k, v, causal=causal,
                                         interpret=interpret, segs=segs,
                                         window=window)


def _flash_backward_from_prepared(dims: '_FlashDims', prep, k, v, *,
                                  causal: bool, interpret: bool = False,
                                  segs=None, window=None):
    """Backward kernels given pre-padded q-side operands (see
    :func:`_prepare_flash_bwd_q_side`); only the kv chunk varies per call.
    ``segs``: optional pre-padded ``(seg_q, seg_kv)`` from ``pad_seg_q`` /
    ``pad_seg_kv`` for packed-sequence masking.

    GQA: the dk/dv kernel runs one program per Q head (reading the shared kv
    row via the head map) and emits per-q-head float32 partials that are
    group-summed outside — a transient ``group``× float32 buffer, traded for
    never materializing repeated kv."""
    from jax.experimental import pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    qf, dof, lsef, deltaf = prep
    kv_len, head_dim, bq, bk = dims.kv_len, dims.head_dim, dims.bq, dims.bk
    flat, pq_len, pk_len = dims.flat, dims.pq_len, dims.pk_len
    num_q_blocks, num_kv_blocks = dims.num_q_blocks, dims.num_kv_blocks
    scale = dims.scale
    kvmap = dims.kv_program_index()
    kf = dims.pad_kv_like(k)
    vf = dims.pad_kv_like(v)
    vma = _out_vma(qf, k, v, dof)
    segmented = segs is not None

    qspec = pl.BlockSpec((None, bq, head_dim), lambda b, i, j: (b, i, 0))
    kvspec_j = pl.BlockSpec((None, bk, head_dim),
                            lambda b, i, j: (kvmap(b), j, 0))
    rowspec_i = pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, i, 0))
    dq_inputs = [qf, kf, vf, dof, lsef, deltaf]
    dq_specs = [qspec, kvspec_j, kvspec_j, qspec, rowspec_i, rowspec_i]
    if segmented:
        dq_inputs += list(segs)
        dq_specs += [rowspec_i,
                     pl.BlockSpec((None, 8, bk),
                                  lambda b, i, j: (kvmap(b), 0, j))]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=bq, block_k=bk,
                          causal=causal, scale=scale, kv_seq_len=kv_len,
                          num_kv_blocks=num_kv_blocks, segmented=segmented,
                          window=window),
        grid=(flat, num_q_blocks, num_kv_blocks),
        in_specs=dq_specs,
        out_specs=qspec,
        out_shape=_sds((flat, pq_len, head_dim), qf.dtype, vma),
        scratch_shapes=[pltpu.VMEM((bq, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(*dq_inputs)

    qspec_j = pl.BlockSpec((None, bq, head_dim), lambda b, i, j: (b, j, 0))
    kvspec_i = pl.BlockSpec((None, bk, head_dim),
                            lambda b, i, j: (kvmap(b), i, 0))
    outspec_i = pl.BlockSpec((None, bk, head_dim), lambda b, i, j: (b, i, 0))
    rowspec_j = pl.BlockSpec((None, bq, 1), lambda b, i, j: (b, j, 0))
    dkdv_inputs = [qf, kf, vf, dof, lsef, deltaf]
    dkdv_specs = [qspec_j, kvspec_i, kvspec_i, qspec_j, rowspec_j, rowspec_j]
    if segmented:
        dkdv_inputs += list(segs)
        dkdv_specs += [rowspec_j,
                       pl.BlockSpec((None, 8, bk),
                                    lambda b, i, j: (kvmap(b), 0, i))]
    # GQA emits per-Q-head float32 partials (exact cross-head sum before the
    # storage cast); plain MHA writes k/v dtype directly — no extra HBM
    # traffic or cast pass on the common path
    part_dtypes = ((jnp.float32, jnp.float32) if dims.group > 1
                   else (k.dtype, v.dtype))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, block_q=bq, block_k=bk,
                          causal=causal, scale=scale, kv_seq_len=kv_len,
                          num_q_blocks=num_q_blocks, segmented=segmented,
                          window=window),
        grid=(flat, num_kv_blocks, num_q_blocks),
        in_specs=dkdv_specs,
        out_specs=[outspec_i, outspec_i],
        out_shape=[_sds((flat, pk_len, head_dim), part_dtypes[0], vma),
                   _sds((flat, pk_len, head_dim), part_dtypes[1], vma)],
        scratch_shapes=[pltpu.VMEM((bk, head_dim), jnp.float32),
                        pltpu.VMEM((bk, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(*dkdv_inputs)

    if dims.group > 1:
        dk = dims.sum_head_groups(dk).astype(k.dtype)
        dv = dims.sum_head_groups(dv).astype(v.dtype)
    return dims.unpad_q_like(dq), dims.unpad_kv_like(dk), dims.unpad_kv_like(dv)


def flash_attention_with_lse(q, k, v, *, causal: bool = True,
                             block_q: int = 256, block_k: int = 512,
                             interpret: bool = False):
    """Pallas flash forward returning ``(o, lse)`` — the building block of
    ring attention's per-chunk computation: each visiting kv chunk is attended
    by the fused kernel, and the normalized per-chunk outputs are folded
    together with :func:`merge_attention_chunks`. Not differentiable on its
    own (ring attention wraps the whole chunk loop in a custom_vjp)."""
    return _pallas_flash(q, k, v, causal, block_q, block_k, interpret,
                         with_lse=True)


def flash_attention_chunk_grads(q, k, v, o, lse, do, *, causal: bool,
                                block_q: int = 256, block_k: int = 512,
                                interpret: bool = False):
    """Per-chunk-pair gradients via the fused backward kernels: given local
    queries (with their GLOBAL output o and logsumexp lse) against one kv
    chunk, returns (dq, dk, dv) for exactly that pair — p = exp(s − lse)
    already yields global softmax probabilities, so cross-chunk gradients
    need no further normalization."""
    return _pallas_flash_backward(q, k, v, o, lse, do, causal=causal,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret)


def merge_attention_chunks(o_acc, m, l, o_i, lse_i):
    """Fold one finished attention chunk — normalized output ``o_i`` plus its
    per-row logsumexp ``lse_i`` — into running accumulators ``(o_acc, m, l)``.

    Contract: ``o_acc = Σ_j o_j·exp(lse_j − m)`` and ``l = Σ_j exp(lse_j − m)``
    with ``m = max_j lse_j``, so ``o_acc / l`` is the softmax-weighted merge
    (:func:`finalize_attention`) and ``m + log(l)`` the merged logsumexp.
    Fully-masked chunks carry ``lse_i == -inf`` and contribute weight 0; the
    exponents are never positive, so nothing overflows."""
    m_new = jnp.maximum(m, lse_i)
    corr = jnp.exp(m - m_new)
    w = jnp.exp(lse_i - m_new)
    o_acc = o_acc * corr[..., None] + o_i.astype(jnp.float32) * w[..., None]
    return o_acc, m_new, l * corr + w


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, seg_q, seg_kv, causal, block_q, block_k, interpret,
           bwd_backend, window):
    o, _ = _pallas_flash(q, k, v, causal, block_q, block_k, interpret,
                         with_lse=False, segment_ids=seg_q,
                         kv_segment_ids=seg_kv, window=window)
    return o


def _flash_fwd(q, k, v, seg_q, seg_kv, causal, block_q, block_k, interpret,
               bwd_backend, window):
    o, lse = _pallas_flash(q, k, v, causal, block_q, block_k, interpret,
                           segment_ids=seg_q, kv_segment_ids=seg_kv,
                           window=window)
    return o, (q, k, v, o, lse, seg_q, seg_kv)


def _flash_bwd(causal, block_q, block_k, interpret, bwd_backend, window, res,
               do):
    q, k, v, o, lse, seg_q, seg_kv = res
    if bwd_backend == 'pallas':
        grads = _pallas_flash_backward(q, k, v, o, lse, do, causal=causal,
                                       block_q=block_q, block_k=block_k,
                                       interpret=interpret, segment_ids=seg_q,
                                       kv_segment_ids=seg_kv, window=window)
        return grads + (None, None)
    if q.shape[:-2] != k.shape[:-2]:     # GQA through the jnp oracle:
        group = q.shape[-3] // k.shape[-3]
        kr = jnp.repeat(k, group, axis=-3)
        vr = jnp.repeat(v, group, axis=-3)
        seg_kv_r = _repeat_kv_seg(seg_kv, k, group)
        dq, dkr, dvr = _flash_backward(q, kr, vr, o, lse, do, causal=causal,
                                       block_k=block_k, segment_ids=seg_q,
                                       kv_segment_ids=seg_kv_r, window=window)
        shape = k.shape[:-3] + (k.shape[-3], group) + k.shape[-2:]
        dk = dkr.astype(jnp.float32).reshape(shape).sum(axis=-3)
        dv = dvr.astype(jnp.float32).reshape(shape).sum(axis=-3)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None
    dq, dk, dv = _flash_backward(q, k, v, o, lse, do, causal=causal,
                                 block_k=block_k, segment_ids=seg_q,
                                 kv_segment_ids=seg_kv, window=window)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                    block_k: int = 512, backend: Optional[str] = None,
                    bwd: Optional[str] = None, segment_ids=None,
                    kv_segment_ids=None, window: Optional[int] = None):
    """Fused attention over ``(..., L, D)`` inputs; differentiable (custom_vjp
    with fused Pallas backward kernels), any sequence length (padded to block
    multiples internally).

    Grouped-query attention: k/v may carry fewer heads than q (shapes equal
    except axis -3, q heads a multiple of kv heads). The Pallas path reads
    shared kv blocks via the head map — repeated kv is never materialized in
    HBM; the jnp fallback repeats kv explicitly.

    Packed sequences: ``segment_ids`` ``(..., Lq)`` (int; broadcastable over
    batch/head dims) masks attention to same-segment pairs — the contract
    for multi-document packing is that packed attention equals per-document
    attention (``tests/test_flash_segments.py``). ``kv_segment_ids``
    defaults to ``segment_ids``. On the TPU Pallas path ``block_k`` must be
    a multiple of 128 when segments are used (the defaults are).

    ``backend``: 'pallas' forces the TPU kernel, 'jnp' the scan fallback,
    'interpret' the Pallas interpreter (CI on CPU); default picks Pallas on TPU.
    ``bwd``: backward implementation for the Pallas path — 'pallas' (default;
    two fused kernels: dq with kv streaming, dk/dv with q streaming) or 'jnp'
    (``_flash_backward``, the memory-equivalent kv-block scan XLA compiles to
    fused ops — kept as an escape hatch and as the cross-check oracle in
    ``tests/test_flash_attention.py``).
    """
    if backend is None:
        backend = 'pallas' if jax.default_backend() == 'tpu' else 'jnp'
    if bwd not in (None, 'pallas', 'jnp'):
        raise ValueError("bwd must be 'pallas' or 'jnp', got %r" % (bwd,))
    _check_window(window, causal)
    if backend in ('pallas', 'interpret'):
        return _flash(q, k, v, segment_ids, kv_segment_ids, causal, block_q,
                      block_k, backend == 'interpret', bwd or 'pallas',
                      window)
    if bwd is not None:
        raise ValueError("bwd applies only to the Pallas path (backend "
                         "'pallas' or 'interpret'); the %r backend "
                         "differentiates blockwise_attention directly"
                         % backend)
    if q.shape[:-2] != k.shape[:-2]:     # GQA on the jnp path: repeat kv
        _FlashDims(q.shape, k.shape, block_q, block_k)   # validates shapes
        group = q.shape[-3] // k.shape[-3]
        kv_segment_ids = _repeat_kv_seg(kv_segment_ids, k, group)
        k = jnp.repeat(k, group, axis=-3)
        v = jnp.repeat(v, group, axis=-3)
    return blockwise_attention(q, k, v, causal=causal, block_k=block_k,
                               segment_ids=segment_ids,
                               kv_segment_ids=kv_segment_ids, window=window)
