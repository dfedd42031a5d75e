"""Flagship decoder-only transformer LM, designed mesh-first.

Parallelism is expressed entirely through GSPMD shardings over a named mesh
(axes from ``petastorm_tpu.parallel.mesh``): annotate params/activations with
PartitionSpecs, let XLA insert the collectives.

- **dp** ('data'): batch dim of activations. Flash attention runs under
  shard_map over this axis (and 'model' on heads): Pallas kernels cannot be
  partitioned by GSPMD.
- **tp** ('model'): Megatron-style column/row parallel attention + MLP —
  wq/wk/wv and w_gate/w_up are column-parallel (output dim sharded), wo and
  w_down row-parallel (input dim sharded); XLA inserts the psum where the
  row-parallel matmul closes.
- **sp** ('seq'): sequence dim of activations; attention runs as ring
  attention (``petastorm_tpu/parallel/ring.py``) under shard_map so k/v chunks
  rotate over ICI instead of being all-gathered.
- **ep** ('expert'): optional MoE FFN with experts sharded one-per-group over
  the expert axis.

Compute dtype is bfloat16 (MXU-native); params and softmax/statistics stay
float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from petastorm_tpu.ops.attention import blockwise_attention, flash_attention
from petastorm_tpu.parallel.ring import resolve_ring_impl, ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    # kv heads for grouped-query attention; None = n_heads (MHA). The
    # 'flash' path reads shared kv natively (no repeated kv in HBM);
    # 'blockwise'/'ring' repeat kv heads explicitly.
    n_kv_heads: Optional[int] = None
    n_layers: int = 4
    d_ff: int = 2048
    max_seq_len: int = 2048
    n_experts: int = 0            # 0 → dense FFN; >0 → top-k MoE
    # Experts consulted per token: 1 = Switch routing (scale by the raw top
    # prob), >1 = GShard-style (scales normalized over the selected experts).
    moe_top_k: int = 1
    # Per-expert buffer size = ceil(dispatch_units/n_experts *
    # capacity_factor) with dispatch_units = tokens · top_k; units routed
    # past an expert's capacity are dropped (that choice contributes zero —
    # for top-1 the token's residual stream passes through unchanged,
    # Switch-Transformer semantics).
    moe_capacity_factor: float = 1.25
    # Weight of the Switch load-balancing auxiliary loss; 0 disables it.
    # Deviation from the GShard paper for top_k > 1: the dispatch fraction in
    # the aux term counts ALL k choices per token, not just the first choice —
    # this pressures the router to balance the full dispatch load (what the
    # capacity buffers actually see) rather than first-choice load only.
    moe_aux_weight: float = 0.01
    dtype: Any = jnp.bfloat16
    # 'ring' shards attention over the 'seq' mesh axis; 'flash'/'blockwise'
    # compute full attention locally (XLA all-gathers kv if seq is sharded).
    attention: str = 'blockwise'
    # sliding-window size: each token attends only the previous N positions
    # ('flash'/'blockwise' training and the KV-cache decode honor it; not
    # supported with 'ring').
    attention_window: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads if self.n_kv_heads is not None else self.n_heads


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init(rng, config: TransformerConfig) -> Dict:
    """Initialize parameters as a pytree of float32 arrays."""
    def dense(key, fan_in, shape):
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

    keys = jax.random.split(rng, 2 + config.n_layers)
    c = config
    params = {
        'embed': dense(keys[0], 1, (c.vocab_size, c.d_model)) * 0.02,
        'final_norm': jnp.ones((c.d_model,), jnp.float32),
        'unembed': dense(keys[1], c.d_model, (c.d_model, c.vocab_size)),
        'layers': [],
    }
    if c.n_heads % c.kv_heads != 0:
        raise ValueError('n_heads (%d) must be a multiple of n_kv_heads (%d)'
                         % (c.n_heads, c.kv_heads))
    if c.n_experts > 0 and not 1 <= c.moe_top_k <= c.n_experts:
        raise ValueError('moe_top_k (%d) must be in [1, n_experts=%d]'
                         % (c.moe_top_k, c.n_experts))
    kv_dim = c.kv_heads * c.head_dim
    for i in range(c.n_layers):
        lk = jax.random.split(keys[2 + i], 8)
        layer = {
            'ln1': jnp.ones((c.d_model,), jnp.float32),
            'wq': dense(lk[0], c.d_model, (c.d_model, c.d_model)),
            'wk': dense(lk[1], c.d_model, (c.d_model, kv_dim)),
            'wv': dense(lk[2], c.d_model, (c.d_model, kv_dim)),
            'wo': dense(lk[3], c.d_model, (c.d_model, c.d_model)),
            'ln2': jnp.ones((c.d_model,), jnp.float32),
        }
        if c.n_experts > 0:
            layer.update({
                'gate': dense(lk[7], c.d_model, (c.d_model, c.n_experts)),
                'w_up': dense(lk[4], c.d_model, (c.n_experts, c.d_model, c.d_ff)),
                'w_gate': dense(lk[5], c.d_model, (c.n_experts, c.d_model, c.d_ff)),
                'w_down': dense(lk[6], c.d_ff, (c.n_experts, c.d_ff, c.d_model)),
            })
        else:
            layer.update({
                'w_up': dense(lk[4], c.d_model, (c.d_model, c.d_ff)),
                'w_gate': dense(lk[5], c.d_model, (c.d_model, c.d_ff)),
                'w_down': dense(lk[6], c.d_ff, (c.d_ff, c.d_model)),
            })
        params['layers'].append(layer)
    return params


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def param_specs(config: TransformerConfig, mesh) -> Dict:
    """PartitionSpec pytree matching :func:`init`'s structure, using only axes
    present in ``mesh`` (absent axes collapse to replication)."""
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    tp = 'model' if 'model' in names else None
    ep = 'expert' if 'expert' in names else None

    layer = {
        'ln1': P(), 'ln2': P(),
        'wq': P(None, tp), 'wk': P(None, tp), 'wv': P(None, tp),
        'wo': P(tp, None),
    }
    if config.n_experts > 0:
        layer.update({
            'gate': P(),
            'w_up': P(ep, None, tp), 'w_gate': P(ep, None, tp),
            'w_down': P(ep, tp, None),
        })
    else:
        layer.update({
            'w_up': P(None, tp), 'w_gate': P(None, tp), 'w_down': P(tp, None),
        })
    return {
        'embed': P(None, tp),
        'final_norm': P(),
        'unembed': P(None, tp),
        'layers': [dict(layer) for _ in range(config.n_layers)],
    }


def batch_spec(mesh):
    """Spec for a (batch, seq) token array over whatever of data/seq exists."""
    from jax.sharding import PartitionSpec as P
    names = set(mesh.axis_names)
    return P('data' if 'data' in names else None,
             'seq' if 'seq' in names else None)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms_norm(x, scale):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)


def _rope(x, positions):
    """Rotary position embedding. x: (B, H, L, D), positions: (L,) or (B, L)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(10000.0) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., L, half)
    if angles.ndim == 2:            # (L, half) -> broadcast over B, H
        angles = angles[None, None]
    else:                           # (B, L, half) -> broadcast over H
        angles = angles[:, None]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _ring_attention_sharded(q, k, v, mesh):
    """Ring attention under shard_map: q/k/v are global (B, H, L, dh) arrays
    with L sharded over 'seq' (and B over 'data', H over 'model' when those
    axes exist); each device folds rotating kv chunks over ICI."""
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    spec = P('data' if 'data' in names else None,
             'model' if 'model' in names else None,
             'seq', None)
    # Resolve from the mesh devices (not the session default backend): TPU
    # meshes get per-chunk Pallas kernels, CPU meshes the jnp path.
    impl = resolve_ring_impl(None, mesh)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec)
    def fn(q, k, v):
        return ring_attention(q, k, v, 'seq', causal=True, impl=impl)

    return fn(q, k, v)


def _flash_attention_sharded(q, k, v, mesh, segment_ids=None, window=None):
    """Flash attention under shard_map: Mosaic kernels cannot be partitioned
    by GSPMD, so each device runs the kernel on its own batch shard (and its
    own heads when the mesh has 'model'). q/k/v are global (B, H, L, dh)
    arrays; a sharded 'seq' axis is gathered, as the unsharded call would.
    The kernel backend follows the mesh's devices, as ring attention's does."""
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    batch = 'data' if 'data' in names else None
    heads = 'model' if 'model' in names else None
    spec = P(batch, heads, None, None)
    backend = resolve_ring_impl(None, mesh)       # 'pallas' on TPU meshes
    in_specs = (spec, spec, spec)
    args = (q, k, v)
    if segment_ids is not None:
        in_specs += (P(batch, None),)
        args += (segment_ids,)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=spec)
    def fn(q, k, v, segment_ids=None):
        return flash_attention(q, k, v, causal=True, segment_ids=segment_ids,
                               window=window, backend=backend)

    return fn(*args)


def _attention(x, layer, config: TransformerConfig, positions, mesh=None,
               segment_ids=None):
    c = config
    b, l, _ = x.shape
    h, hkv, dh = c.n_heads, c.kv_heads, c.head_dim

    def heads(w, n):
        y = (x @ w.astype(x.dtype)).reshape(b, l, n, dh)
        return jnp.transpose(y, (0, 2, 1, 3))        # (B, n, L, dh)

    q = heads(layer['wq'], h)
    k = heads(layer['wk'], hkv)
    v = heads(layer['wv'], hkv)
    q, k = _rope(q, positions), _rope(k, positions)
    if hkv != h and c.attention == 'blockwise':
        # flash reads shared kv natively, and ring handles GQA itself
        # (kernel head map on TPU — smaller rotating ppermute payloads —
        # or an internal repeat on the jnp path); only blockwise needs the
        # explicit head repeat here
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)

    if c.attention == 'ring':
        if segment_ids is not None:
            raise ValueError('packed segment_ids are not supported with '
                             "attention='ring' (use 'flash'/'blockwise', or "
                             'shard unpacked sequences)')
        if c.attention_window is not None:
            raise ValueError('attention_window is not supported with '
                             "attention='ring'")
        if mesh is None or 'seq' not in mesh.axis_names:
            raise ValueError("attention='ring' needs a mesh with a 'seq' axis")
        o = _ring_attention_sharded(q, k, v, mesh)
    elif c.attention == 'flash' and mesh is not None:
        o = _flash_attention_sharded(q, k, v, mesh, segment_ids,
                                     c.attention_window)
    elif c.attention == 'flash':
        o = flash_attention(q, k, v, causal=True, segment_ids=segment_ids,
                            window=c.attention_window)
    else:
        o = blockwise_attention(q, k, v, causal=True,
                                segment_ids=segment_ids,
                                window=c.attention_window)
    o = jnp.transpose(o, (0, 2, 1, 3)).reshape(b, l, h * dh)
    return o @ layer['wo'].astype(x.dtype)


def _dense_ffn(x, layer):
    gate = jax.nn.silu(x @ layer['w_gate'].astype(x.dtype))
    up = x @ layer['w_up'].astype(x.dtype)
    return (gate * up) @ layer['w_down'].astype(x.dtype)


def _moe_ffn_dense(x, layer, config: TransformerConfig):
    """Dense one-hot top-k dispatch: every token multiplied by every expert
    with zeros. O(E · tokens · d_ff) FLOPs — kept ONLY as the test oracle for
    :func:`_moe_ffn` (with enough capacity the two must agree exactly)."""
    b, l, d = x.shape
    k = config.moe_top_k
    logits = x.astype(jnp.float32) @ layer['gate']          # (B, L, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_idx, top_probs = _moe_router(probs, k)              # (B, L, k)
    # combine weight per expert = Σ over the choices that picked it
    combine = jnp.einsum('blk,blke->ble', top_probs.astype(jnp.float32),
                         jax.nn.one_hot(top_idx, config.n_experts,
                                        dtype=jnp.float32)).astype(x.dtype)
    onehot = (combine != 0).astype(x.dtype)                 # (B, L, E)

    # dispatch: (E, B, L, d) rows routed to their expert, zeros elsewhere
    xe = jnp.einsum('bld,ble->ebld', x, onehot)
    gate = jax.nn.silu(jnp.einsum('ebld,edf->eblf', xe,
                                  layer['w_gate'].astype(x.dtype)))
    up = jnp.einsum('ebld,edf->eblf', xe, layer['w_up'].astype(x.dtype))
    down = jnp.einsum('eblf,efd->ebld', gate * up,
                      layer['w_down'].astype(x.dtype))
    return jnp.einsum('ebld,ble->bld', down, combine)


def _moe_router(probs, k: int):
    """(N, E) router probs → per-token expert choices (N, k) and combine
    scales (N, k): the raw top prob for k=1 (Switch), normalized over the
    selected experts for k>1 (GShard top-2 convention)."""
    top_probs, top_idx = jax.lax.top_k(probs, k)
    if k > 1:
        top_probs = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)
    return top_idx, top_probs


def _moe_ffn(x, layer, config: TransformerConfig, mesh=None,
             capacity: Optional[int] = None):
    """Top-k MoE with sort-based sparse dispatch (k=1: Switch; k>1: GShard).

    Every (token, choice) pair is one dispatch unit: units are stably sorted
    by their routed expert, scattered into a static (E, capacity, d) buffer,
    run through a batched per-expert matmul, and gathered back as a
    scale-weighted sum over the token's k choices — per-unit FLOPs are
    O(capacity_factor · d · d_ff), independent of the number of experts (the
    VERDICT-flagged dense one-hot dispatch was O(E · d · d_ff) per token).
    Static shapes throughout, so the whole thing jits; over-capacity units
    read the zero overflow row (that choice contributes nothing)."""
    b, l, d = x.shape
    e = config.n_experts
    k = config.moe_top_k
    n = b * l
    n_units = n * k
    xf = x.reshape(n, d)
    logits = xf.astype(jnp.float32) @ layer['gate']          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_idx, top_probs = _moe_router(probs, k)               # (N, k) each
    unit_expert = top_idx.reshape(n_units)                   # unit u ↔ token u//k
    scale = top_probs.astype(x.dtype)                        # (N, k)

    if capacity is None:
        capacity = max(1, int(math.ceil(n_units / e
                                        * config.moe_capacity_factor)))
    # stable sort keeps same-expert units in stream order → deterministic
    # drop policy (earliest tokens win a contended expert)
    order = jnp.argsort(unit_expert, stable=True)
    sorted_expert = unit_expert[order]
    group_starts = jnp.searchsorted(sorted_expert, jnp.arange(e), side='left')
    pos = jnp.arange(n_units) - group_starts[sorted_expert]  # rank in group
    # over-capacity units target the dedicated overflow row e*capacity
    dest = jnp.where(pos < capacity, sorted_expert * capacity + pos,
                     e * capacity)

    unit_token = order // k                                  # token of each unit
    buf = jnp.zeros((e * capacity + 1, d), x.dtype).at[dest].set(xf[unit_token])
    expert_in = buf[:-1].reshape(e, capacity, d)
    if mesh is not None and 'expert' in mesh.axis_names:
        from jax.sharding import PartitionSpec as P
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, jax.sharding.NamedSharding(mesh, P('expert', None, None)))

    gate = jax.nn.silu(jnp.einsum('ecd,edf->ecf', expert_in,
                                  layer['w_gate'].astype(x.dtype)))
    up = jnp.einsum('ecd,edf->ecf', expert_in, layer['w_up'].astype(x.dtype))
    out = jnp.einsum('ecf,efd->ecd', gate * up,
                     layer['w_down'].astype(x.dtype))

    flat = jnp.concatenate([out.reshape(e * capacity, d),
                            jnp.zeros((1, d), x.dtype)])     # overflow row
    # un-sort to unit order (N, k, d), then scale-weighted sum over choices
    unit_out = jnp.zeros((n_units, d), x.dtype).at[order].set(flat[dest])
    y = jnp.einsum('nkd,nk->nd', unit_out.reshape(n, k, d), scale)

    # Switch load-balancing aux loss: E * sum_e(dispatch_fraction_e * mean
    # router prob_e) — minimized (=1) at a uniform routing distribution;
    # fractions count all k choices. Differentiable through `probs`, so the
    # router learns to balance.
    frac = jnp.mean(jax.nn.one_hot(unit_expert, e, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return y.reshape(b, l, d), aux


def _segment_positions(segment_ids):
    """Per-document positions derived from (B, L) segment ids: 0, 1, 2, …
    restarting wherever the segment changes (matches
    ``packing.pack_documents``' positions for contiguous segments)."""
    seg = jnp.asarray(segment_ids)
    idx = jnp.arange(seg.shape[-1])
    boundary = jnp.concatenate(
        [jnp.ones_like(seg[..., :1], bool),
         seg[..., 1:] != seg[..., :-1]], axis=-1)
    starts = jax.lax.cummax(jnp.where(boundary, idx, 0), axis=seg.ndim - 1)
    return idx - starts


def _hidden(params, tokens, config: TransformerConfig, positions, mesh,
            segment_ids):
    """tokens (B, L) → the final-normed hidden states (B, L, D) in the
    compute dtype, and the summed MoE aux loss (see :func:`forward`)."""
    c = config
    if positions is None:
        if segment_ids is not None:
            # restart rotary offsets at every document boundary — silently
            # continuing a neighbor's offsets would train position encodings
            # inconsistent with unpacked inference
            positions = _segment_positions(segment_ids)
        else:
            positions = jnp.arange(tokens.shape[1])
    x = params['embed'].astype(c.dtype)[tokens]              # (B, L, D)
    aux_total = jnp.zeros((), jnp.float32)
    for layer in params['layers']:
        h = _rms_norm(x, layer['ln1'])
        x = x + _attention(h, layer, c, positions, mesh, segment_ids)
        h = _rms_norm(x, layer['ln2'])
        if c.n_experts > 0:
            ffn_out, aux = _moe_ffn(h, layer, c, mesh)
            x = x + ffn_out
            aux_total = aux_total + aux
        else:
            x = x + _dense_ffn(h, layer)
    return _rms_norm(x, params['final_norm']), aux_total


def forward(params, tokens, config: TransformerConfig,
            positions: Optional[jnp.ndarray] = None, mesh=None,
            return_aux: bool = False, segment_ids=None):
    """tokens (B, L) int32 → logits (B, L, vocab) float32.

    With ``return_aux=True`` also returns the summed MoE load-balancing
    auxiliary loss (0.0 for dense models). ``segment_ids`` (B, L) enables
    packed multi-document batches (see ``petastorm_tpu.packing``): attention
    is masked to same-segment pairs — pass the packer's per-document
    ``positions`` too so rotary offsets restart per document."""
    x, aux_total = _hidden(params, tokens, config, positions, mesh,
                           segment_ids)
    logits = (x @ params['unembed'].astype(config.dtype)).astype(jnp.float32)
    return (logits, aux_total) if return_aux else logits


@jax.custom_vjp
def _cross_entropy(logits, targets):
    """Per-position ``-log softmax(logits)[target]`` in float32, from logits
    in the compute dtype: the float32 arithmetic stays inside the
    reductions and the backward, so no float32 copy of the (B, L, vocab)
    logits is ever written."""
    return _cross_entropy_fwd(logits, targets)[0]


def _cross_entropy_fwd(logits, targets):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt.astype(jnp.float32), (logits, lse, targets)


def _cross_entropy_bwd(res, g):
    logits, lse, targets = res
    probs = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    return ((probs - onehot) * g[..., None]).astype(logits.dtype), None


_cross_entropy.defvjp(_cross_entropy_fwd, _cross_entropy_bwd)


def loss_fn(params, tokens, targets, config: TransformerConfig, mesh=None,
            *, positions=None, segment_ids=None, weights=None):
    """Next-token cross entropy (+ weighted MoE load-balance aux for expert
    models); ``targets`` are tokens shifted by the caller (the NGram pipeline
    emits aligned (input, target) windows).

    Packed multi-document batches (``petastorm_tpu.packing``): pass the
    packer's ``positions``/``segment_ids`` plus the ``weights`` from
    ``packed_lm_targets`` — attention is segment-masked, rotary offsets
    restart per document, and padding/document-boundary slots get zero loss
    weight (mean over weighted slots only)."""
    x, aux = _hidden(params, tokens, config, positions, mesh, segment_ids)
    nll = _cross_entropy(x @ params['unembed'].astype(config.dtype), targets)
    if weights is None:
        loss = jnp.mean(nll)
    else:
        loss = (jnp.sum(nll * weights)
                / jnp.maximum(jnp.sum(weights), 1.0))
    if config.n_experts > 0 and config.moe_aux_weight:
        loss = loss + config.moe_aux_weight * aux
    return loss


# ---------------------------------------------------------------------------
# decoding (KV-cache autoregressive generation)
# ---------------------------------------------------------------------------

def init_kv_cache(config: TransformerConfig, batch_size: int, max_len: int):
    """Per-layer key/value caches ``(B, kv_heads, max_len, head_dim)`` in the
    model compute dtype."""
    c = config
    shape = (batch_size, c.kv_heads, max_len, c.head_dim)
    return [{'k': jnp.zeros(shape, c.dtype), 'v': jnp.zeros(shape, c.dtype)}
            for _ in range(c.n_layers)]


def _attend_cache(q, ck, cv, index, window=None):
    """One-token attention against the cache: q ``(B, H, 1, dh)``, cache
    ``(B, Hkv, max, dh)``; positions > ``index`` (and, with ``window``,
    positions ≤ index − window) are masked. GQA-aware (q head groups share a
    cache head)."""
    b, h, _, dh = q.shape
    hkv = ck.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh)
    s = jnp.einsum('bkgd,bkld->bkgl', qg.astype(jnp.float32),
                   ck.astype(jnp.float32)) / math.sqrt(dh)
    pos = jnp.arange(ck.shape[2])[None, None, None, :]
    mask = pos <= index
    if window is not None:
        mask = mask & (index - pos < window)
    s = jnp.where(mask, s, _NEG_INF_LOGIT)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum('bkgl,bkld->bkgd', p, cv.astype(jnp.float32))
    return o.reshape(b, h, 1, dh).astype(q.dtype)


def _decode_layer(x, layer, config: TransformerConfig, cache, index):
    """One transformer layer for ONE token per sequence (x ``(B, 1, D)``),
    reading/extending the kv cache at ``index``. Returns (x, cache)."""
    c = config
    b = x.shape[0]
    h, hkv, dh = c.n_heads, c.kv_heads, c.head_dim
    positions = jnp.reshape(index, (1,))

    hn = _rms_norm(x, layer['ln1'])

    def heads(w, n):
        y = (hn @ w.astype(hn.dtype)).reshape(b, 1, n, dh)
        return jnp.transpose(y, (0, 2, 1, 3))

    q = _rope(heads(layer['wq'], h), positions)
    k_new = _rope(heads(layer['wk'], hkv), positions)
    v_new = heads(layer['wv'], hkv)
    ck = jax.lax.dynamic_update_slice(
        cache['k'], k_new.astype(cache['k'].dtype), (0, 0, index, 0))
    cv = jax.lax.dynamic_update_slice(
        cache['v'], v_new.astype(cache['v'].dtype), (0, 0, index, 0))
    att = _attend_cache(q, ck, cv, index, window=c.attention_window)
    x = x + (jnp.transpose(att, (0, 2, 1, 3)).reshape(b, 1, h * dh)
             @ layer['wo'].astype(x.dtype))

    h2 = _rms_norm(x, layer['ln2'])
    if c.n_experts > 0:
        # capacity = all units of the step: per-step routing sees only B
        # units (vs B·L at training), so the trained capacity_factor could
        # silently drop choices and make decode diverge from teacher forcing
        ffn_out, _ = _moe_ffn(h2, layer, c,
                              capacity=b * c.moe_top_k)  # aux unused at decode
        x = x + ffn_out
    else:
        x = x + _dense_ffn(h2, layer)
    return x, {'k': ck, 'v': cv}


_NEG_INF_LOGIT = -1e30


def _sample_logits(logits, temperature: float, top_k, top_p, rng):
    """One sampling step over ``(B, vocab)`` float32 logits. temperature 0 =
    greedy; otherwise categorical after optional top-k truncation and
    top-p (nucleus) truncation — the smallest set of tokens whose
    probabilities sum to ≥ top_p."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None or top_p is not None:
        # one descending argsort serves both truncations (this runs inside
        # the scanned per-token decode loop). The keep-mask is built over
        # sorted *ranks* and scattered back through the permutation —
        # comparing against the k-th/threshold value would leak every token
        # tied with the cutoff into the candidate set
        order = jnp.argsort(logits, axis=-1)[..., ::-1]
        sorted_desc = jnp.take_along_axis(logits, order, axis=-1)
        keep = jnp.ones(sorted_desc.shape, bool)
        if top_k is not None:
            keep &= jnp.arange(keep.shape[-1]) < top_k
        if top_p is not None:
            probs = jax.nn.softmax(
                jnp.where(keep, sorted_desc, _NEG_INF_LOGIT), axis=-1)
            exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
            keep &= exclusive_cum < top_p       # always keeps the top token
        inverse = jnp.argsort(order, axis=-1)
        logits = jnp.where(jnp.take_along_axis(keep, inverse, axis=-1),
                           logits, _NEG_INF_LOGIT)
    return jax.random.categorical(rng, logits)


def generate(params, tokens, config: TransformerConfig, max_new_tokens: int,
             *, temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None, rng=None):
    """Autoregressive decoding with per-layer KV caches.

    ``tokens`` ``(B, Lp)`` int32 prompts (same length across the batch) →
    ``(B, max_new_tokens)`` sampled continuations. ``temperature`` 0 =
    greedy argmax, > 0 = categorical sampling (seeded by ``rng``) with
    optional ``top_k`` / ``top_p`` (nucleus) truncation. The
    prompt is prefilled through the same single-token decode path, so
    prefill and decode are numerically identical; works for dense, MoE, and
    GQA configs (the cache carries ``kv_heads`` heads). The config's
    ``attention`` mode only affects training — decode always attends the
    cache directly. MoE decode routes with capacity = all units of the step
    (B·top_k), so per-step routing can never drop a choice and decode
    matches teacher forcing for every config (training capacity_factor only
    shapes the training-time drop policy)."""
    c = config
    b, prompt_len = tokens.shape
    total = prompt_len + max_new_tokens
    if c.attention_window is not None and c.attention_window < 1:
        raise ValueError('attention_window must be >= 1, got %r'
                         % (c.attention_window,))
    if top_k is not None and not 1 <= top_k <= c.vocab_size:
        raise ValueError('top_k must be in [1, vocab_size]')
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError('top_p must be in (0, 1]')
    if rng is None:
        rng = jax.random.PRNGKey(0)
    caches = init_kv_cache(c, b, total)
    buf = jnp.concatenate(
        [tokens, jnp.zeros((b, max_new_tokens), tokens.dtype)], axis=1)

    def step(carry, t):
        buf, caches, rng = carry
        x = params['embed'].astype(c.dtype)[buf[:, t]][:, None, :]
        new_caches = []
        for layer, cache in zip(params['layers'], caches):
            x, cache = _decode_layer(x, layer, c, cache, t)
            new_caches.append(cache)
        x = _rms_norm(x, params['final_norm'])
        logits = (x @ params['unembed'].astype(c.dtype))[:, 0].astype(
            jnp.float32)
        rng, sub = jax.random.split(rng)
        nxt = _sample_logits(logits, temperature, top_k, top_p,
                             sub).astype(buf.dtype)
        # keep prompt tokens during prefill; write samples after it
        buf = buf.at[:, t + 1].set(
            jnp.where(t + 1 < prompt_len, buf[:, t + 1], nxt))
        return (buf, new_caches, rng), None

    (buf, _, _), _ = jax.lax.scan(step, (buf, caches, rng),
                                  jnp.arange(total - 1))
    return buf[:, prompt_len:]


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

class _MeshStep:
    """The jitted mesh step, called as ``make_train_step`` documents. With
    ``in_shardings`` jit takes positional arguments only and as many as
    there are shardings, so the unpacked and the packed call each have a
    jit of the one step function; ``lower`` follows ``__call__``."""

    def __init__(self, unpacked, packed):
        self._unpacked, self._packed = unpacked, packed

    def _route(self, name, params, opt_state, tokens, targets=None,
               segment_ids=None, positions=None):
        if segment_ids is None:
            return getattr(self._unpacked, name)(params, opt_state, tokens,
                                                 targets)
        return getattr(self._packed, name)(params, opt_state, tokens, targets,
                                           segment_ids, positions)

    def __call__(self, *args, **kwargs):
        return self._route('__call__', *args, **kwargs)

    def lower(self, *args, **kwargs):
        return self._route('lower', *args, **kwargs)


def make_train_step(config: TransformerConfig, mesh=None, optimizer=None):
    """Build a jitted ``(params, opt_state, tokens, targets) -> (params,
    opt_state, loss)`` step.

    Packed batches (``petastorm_tpu.packing``) call it as ``step(params,
    opt_state, tokens, segment_ids=..., positions=...)``, with no targets:
    the step trains on ``packing.packed_lm_targets``' next-token targets and
    weights (0 at each document's last token and on padding), its loss the
    mean over weighted slots. Without them it is the unpacked step.

    With ``mesh``, params/activations are constrained to :func:`param_specs` /
    :func:`batch_spec` shardings (dp/tp/sp/ep as present in the mesh; the
    packed columns take the batch's); ring and flash attention additionally
    run under shard_map, and the returned optimizer's ``init`` builds its
    state on the mesh.
    """
    import optax

    from petastorm_tpu.packing import packed_lm_targets
    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)

    def step(params, opt_state, tokens, targets=None, segment_ids=None,
             positions=None):
        weights = None
        if segment_ids is not None:
            if targets is not None:
                raise ValueError('a packed step derives its targets from '
                                 'tokens and segment_ids; pass none')
            targets, weights = packed_lm_targets(tokens, segment_ids)
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, targets, config, mesh, positions=positions,
            segment_ids=segment_ids, weights=weights)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if mesh is None:
        return optimizer, jax.jit(step)

    from jax.sharding import NamedSharding

    # opt_state made off the mesh (its step count) differs in type from the
    # one the step returns, so the second step would compile again
    base_init = optimizer.init

    def init_on_mesh(params):
        with jax.set_mesh(mesh):
            return base_init(params)

    optimizer = optimizer._replace(init=init_on_mesh)

    pspecs = param_specs(config, mesh)
    bspec = batch_spec(mesh)
    p_shard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs,
                                     is_leaf=lambda x: isinstance(
                                         x, type(bspec)))
    b_shard = NamedSharding(mesh, bspec)
    out_shardings = (p_shard, None, None)
    unpacked = jax.jit(step, in_shardings=(p_shard, None, b_shard, b_shard),
                       out_shardings=out_shardings)
    packed = jax.jit(step, in_shardings=(p_shard, None) + (b_shard,) * 4,
                     out_shardings=out_shardings)
    return optimizer, _MeshStep(unpacked, packed)


def make_forward(config: TransformerConfig):
    """Jittable inference fn + tiny example args (single-chip compile check)."""
    cfg = config

    @jax.jit
    def fn(params, tokens):
        return forward(params, tokens, cfg)

    return fn
