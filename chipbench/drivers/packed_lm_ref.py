"""Plain reference for the ``packed_lm`` driver: the stored documents read
back with pyarrow, split and packed by a first-fit written here, and the
decoder trained in float32 at ``Precision.HIGHEST`` on the packed rows with
AdamW as ``optax.adamw`` defines it.

The packing rule: each document is cut into pieces of at most ``seq_len``
tokens, in order; the pieces are numbered over the store in stored order
(the store's files sorted by name, their row groups in order); within one
row group the pieces, in that order, each go into the first open row with
room, and the rows are kept in the order they were opened. A row's
segment ids count its pieces from 1 (0 on padding), its positions restart
at 0 for each piece, and its id is the number of its first piece.

The model is ``transformer_lm_ref``'s block (its weights, drawn from the
seed as ``transformer_lm.init`` draws them, and its FLOP count), with
attention masked by an explicit mask to pairs of one segment, rotary angles
from the restarting positions, and next-token targets within a piece: loss
weight 0 at each piece's last token and on padding, the loss the mean over
weighted slots.

Imports nothing of ``petastorm_tpu``; takes nothing the program made.
"""

import glob
import math
import os

import numpy as np

from chipbench import precision
from chipbench.drivers import transformer_lm_ref as lm_ref

init = lm_ref.init
train_flops = lm_ref.train_flops

COLUMNS = ('tokens', 'segment_ids', 'positions')


def first_fit(lengths, seq_len):
    """``(row of each piece, rows opened)``: each piece in order into the
    first row with room."""
    room, row_of = [], []
    for n in lengths:
        for r, free in enumerate(room):
            if free >= n:
                break
        else:
            r = len(room)
            room.append(seq_len)
        room[r] -= n
        row_of.append(r)
    return row_of, len(room)


def pack_row_group(docs, first_number, seq_len):
    """One row group's documents, their pieces numbered from
    ``first_number``: ``(rows {column: (R, seq_len) int32}, the id of each
    row, the number after the group's last piece)``."""
    pieces = [d[lo:lo + seq_len] for d in docs
              for lo in range(0, len(d), seq_len)]
    row_of, count = first_fit([len(p) for p in pieces], seq_len)
    rows = {c: np.zeros((count, seq_len), np.int32) for c in COLUMNS}
    ids = [None] * count
    fill, segments = [0] * count, [0] * count
    for number, (piece, r) in enumerate(zip(pieces, row_of), first_number):
        n, lo = len(piece), fill[r]
        if ids[r] is None:
            ids[r] = number
        segments[r] += 1
        rows['tokens'][r, lo:lo + n] = piece
        rows['segment_ids'][r, lo:lo + n] = segments[r]
        rows['positions'][r, lo:lo + n] = np.arange(n)
        fill[r] += n
    return rows, ids, first_number + len(pieces)


class RowSource:
    """The store's packed rows as the batches should hold them, by id."""

    def __init__(self, cfg, store_path, seed):
        import pyarrow.parquet as pq
        seq_len = cfg['seq_len']
        parts, ids, number = [], [], 0
        for path in sorted(glob.glob(os.path.join(store_path, '*.parquet'))):
            f = pq.ParquetFile(path)
            for g in range(f.num_row_groups):
                column = f.read_row_group(
                    g, columns=['tokens']).column(0).combine_chunks()
                flat = column.flatten().to_numpy().astype(np.int32)
                bounds = column.offsets.to_numpy()
                bounds = bounds - bounds[0]
                docs = [flat[lo:hi] for lo, hi in zip(bounds[:-1], bounds[1:])]
                rows, group_ids, number = pack_row_group(docs, number, seq_len)
                parts.append(rows)
                ids += group_ids
        self._rows = {c: np.concatenate([p[c] for p in parts])
                      for c in COLUMNS}
        self._where = {i: k for k, i in enumerate(ids)}
        #: padding slots over all slots of the store's packing
        self.padding_share = float(np.mean(self._rows['segment_ids'] == 0))
        #: same-segment (query, key) pairs over causal pairs of full rows:
        #: the share of a 2048-token causal window's attention work that
        #: the packed rows need
        seg = self._rows['segment_ids']
        pairs = sum(int(n) * (int(n) + 1) // 2 for row in seg
                    for n in np.bincount(row)[1:])
        self.attention_share = pairs / (len(seg) * seq_len * (seq_len + 1) / 2)

    def __len__(self):
        return len(self._where)

    def rows(self, row_ids):
        """``{column: (N, seq_len) int32}``; an id that names no packed row
        reads as a row of -1."""
        idx = [self._where.get(int(r), -1)
               for r in np.asarray(row_ids).reshape(-1)]
        out = {}
        for c in COLUMNS:
            got = self._rows[c][idx]
            got[np.asarray(idx) < 0] = -1
            out[c] = got
        return out


def loss_sum(params, tokens, segment_ids, positions, cfg, control=None):
    """``(sum of weighted next-token cross entropy, sum of weights)`` over
    (B, seq_len) packed rows."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    h_n = cfg['n_heads']
    dh = cfg['d_model'] // h_n
    b, seq = tokens.shape

    def mm(a, b_):
        return precision.result(jnp.matmul(
            precision.operand(a, control), precision.operand(b_, control),
            precision=hi), control)

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale

    half = dh // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(10000.0) / half))
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)       # (B, 1, L, half)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    mask = causal & (segment_ids[:, :, None] == segment_ids[:, None, :])
    mask = mask[:, None]                               # (B, 1, L, L)

    def rope(t):                                       # (B, H, L, dh)
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)

    def layer(x, p):
        h = rms(x, p['ln1'])
        q, k, v = (mm(h, p[w]).reshape(b, seq, h_n, dh).transpose(0, 2, 1, 3)
                   for w in ('wq', 'wk', 'wv'))
        q, k = rope(q), rope(k)
        s = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(dh)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = mm(a, v).transpose(0, 2, 1, 3).reshape(b, seq, h_n * dh)
        x = x + mm(o, p['wo'])
        h = rms(x, p['ln2'])
        return x + mm(jax.nn.silu(mm(h, p['w_gate'])) * mm(h, p['w_up']),
                      p['w_down'])

    x = params['embed'][tokens]
    for p in params['layers']:
        x = jax.checkpoint(layer)(x, p)
    logits = mm(rms(x, params['final_norm']), params['unembed'])
    logp = jax.nn.log_softmax(logits, axis=-1)
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    next_seg = jnp.concatenate([segment_ids[:, 1:],
                                jnp.zeros_like(segment_ids[:, :1])], axis=1)
    weights = ((segment_ids > 0) & (segment_ids == next_seg)).astype(
        jnp.float32)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return jnp.sum(nll * weights), jnp.sum(weights)


def _adamw(cfg):
    """``(params, mu, nu, grads, count) -> (params, mu, nu)``."""
    import jax
    import jax.numpy as jnp
    lr, wd = cfg['lr'], cfg['weight_decay']
    b1, b2, eps = cfg['adam_b1'], cfg['adam_b2'], cfg['adam_eps']
    tree = jax.tree_util.tree_map

    def update(params, mu, nu, grads, count):
        mu = tree(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = tree(lambda n, g: b2 * n + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        params = tree(lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2)
                                                           + eps) + wd * p),
                      params, mu, nu)
        return params, mu, nu

    return update


def control_step(cfg, control):
    """The reference's AdamW step on a whole batch, called as the program's
    step is, ``((params, opt_state), tokens, segment_ids, positions) ->
    ((params, opt_state), loss)``, on the program's optax state: with the
    cell's control, this is the control put in the program's place
    (``faults.py``)."""
    import jax
    import jax.numpy as jnp
    update = _adamw(cfg)

    def mean_loss(params, *columns):
        total, weight = loss_sum(params, *columns, cfg, control)
        return total / jnp.maximum(weight, 1.0)

    def step(state, *columns):
        params, opt_state = state
        adam = opt_state[0]
        value, grads = jax.value_and_grad(mean_loss)(params, *columns)
        count = adam.count + 1
        params, mu, nu = update(params, adam.mu, adam.nu, grads,
                                count.astype(jnp.float32))
        adam = adam._replace(count=count, mu=mu, nu=nu)
        return (params, (adam,) + tuple(opt_state[1:])), value

    return step


def train3(cfg, params, batches, control=None):
    """Steps the reference through ``batches`` (host batches as
    :meth:`RowSource.rows` gives them) from ``params`` with AdamW, on the
    default device, ``ref_block_rows`` rows at a time: each block's
    weighted sum over the batch's weight. Returns the losses, the first
    step's gradient and the parameters after the last step, on the host."""
    import jax
    import jax.numpy as jnp
    block = cfg['ref_block_rows']
    value_and_grad = jax.jit(jax.value_and_grad(
        lambda p, *columns: loss_sum(p, *columns, cfg, control),
        has_aux=True))
    update = jax.jit(_adamw(cfg))

    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for count, batch in enumerate(batches, start=1):
        weight = max(float(np.sum(
            (batch['segment_ids'][:, :-1] > 0)
            & (batch['segment_ids'][:, :-1] == batch['segment_ids'][:, 1:]))),
            1.0)
        total, grads = 0.0, None
        for start in range(0, len(batch['tokens']), block):
            part = [jnp.asarray(batch[c][start:start + block])
                    for c in COLUMNS]
            (value, _), g = value_and_grad(params, *part)
            total += float(value)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        grads = jax.tree_util.tree_map(lambda g: g / weight, grads)
        losses.append(total / weight)
        if first_grad is None:
            first_grad = jax.device_get(grads)
        params, mu, nu = update(params, mu, nu, grads, float(count))
    return {'losses': losses, 'grad': first_grad,
            'params': jax.device_get(params)}
