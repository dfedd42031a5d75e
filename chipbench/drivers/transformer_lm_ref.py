"""Plain reference for the ``transformer_lm`` driver: the stored token
windows read back with pyarrow and ``np.load``, and the decoder trained in
float32 at ``Precision.HIGHEST`` with AdamW as ``optax.adamw`` defines it
(bias-corrected moments, decoupled weight decay on every parameter).

The block is the one ``transformer_lm`` runs, which departs from GPT-NeoX:
RMSNorm (eps 1e-6) before attention and before the MLP, rotary embedding
over the full head width (base 10000, halves rotated), causal softmax
attention scaled by 1/sqrt(head_dim), a SwiGLU MLP (silu(x Wg) * (x Wu)) Wd,
a sequential residual, a final RMSNorm and an untied output matrix.

Imports nothing of ``petastorm_tpu``; takes nothing the program made. The
weights are drawn from the seed with the same ``jax.random`` calls, in the
same order, as ``transformer_lm.init`` documents.
"""

import glob
import io
import math
import os

import numpy as np

from chipbench import precision


class RowSource:
    """The store's windows as the batches should hold them."""

    def __init__(self, cfg, store_path, seed):
        import pyarrow.parquet as pq
        tables = [pq.read_table(f, columns=['row_id', 'tokens'])
                  for f in sorted(glob.glob(os.path.join(store_path,
                                                         '*.parquet')))]
        ids = np.concatenate([t.column('row_id').to_numpy() for t in tables])
        payloads = [b for t in tables for b in t.column('tokens').to_pylist()]
        self._tokens = np.stack([np.load(io.BytesIO(b)) for b in payloads])
        self._where = {int(r): i for i, r in enumerate(ids)}

    def __len__(self):
        return len(self._where)

    def rows(self, row_ids):
        """``{'tokens': (N, seq_len + 1) int32}``."""
        idx = [self._where[int(r)] for r in np.asarray(row_ids).reshape(-1)]
        return {'tokens': self._tokens[idx].astype(np.int32)}


def init(cfg, seed):
    """float32 weights keyed as ``transformer_lm.init`` keys them."""
    import jax
    import jax.numpy as jnp
    d, v, f, n = cfg['d_model'], cfg['vocab_size'], cfg['d_ff'], cfg['n_layers']

    def dense(key, fan_in, shape):
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)

    def make(key):
        keys = jax.random.split(key, 2 + n)
        p = {'embed': dense(keys[0], 1, (v, d)) * 0.02,
             'final_norm': jnp.ones((d,), jnp.float32),
             'unembed': dense(keys[1], d, (d, v)),
             'layers': []}
        for i in range(n):
            lk = jax.random.split(keys[2 + i], 8)
            p['layers'].append({
                'ln1': jnp.ones((d,), jnp.float32),
                'wq': dense(lk[0], d, (d, d)), 'wk': dense(lk[1], d, (d, d)),
                'wv': dense(lk[2], d, (d, d)), 'wo': dense(lk[3], d, (d, d)),
                'ln2': jnp.ones((d,), jnp.float32),
                'w_up': dense(lk[4], d, (d, f)),
                'w_gate': dense(lk[5], d, (d, f)),
                'w_down': dense(lk[6], f, (f, d))})
        return p

    return jax.jit(make)(jax.random.PRNGKey(seed % (1 << 32)))


def loss(params, tokens, cfg, control=None):
    """Mean next-token cross entropy of (B, seq_len + 1) windows."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    h_n = cfg['n_heads']
    dh = cfg['d_model'] // h_n

    def mm(a, b):
        return precision.result(jnp.matmul(
            precision.operand(a, control), precision.operand(b, control),
            precision=hi), control)

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale

    x_tok, y_tok = tokens[:, :-1], tokens[:, 1:]
    b, seq = x_tok.shape
    half = dh // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(10000.0) / half))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def rope(t):                                        # (B, H, L, dh)
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)

    def layer(x, p):
        h = rms(x, p['ln1'])
        q, k, v = (mm(h, p[w]).reshape(b, seq, h_n, dh).transpose(0, 2, 1, 3)
                   for w in ('wq', 'wk', 'wv'))
        q, k = rope(q), rope(k)
        s = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(dh)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm(a, v).transpose(0, 2, 1, 3).reshape(b, seq, h_n * dh)
        x = x + mm(o, p['wo'])
        h = rms(x, p['ln2'])
        return x + mm(jax.nn.silu(mm(h, p['w_gate'])) * mm(h, p['w_up']),
                      p['w_down'])

    x = params['embed'][x_tok]
    for p in params['layers']:
        x = jax.checkpoint(layer)(x, p)
    logits = mm(rms(x, params['final_norm']), params['unembed'])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y_tok[..., None], -1))


def control_step(cfg, control):
    """The reference's AdamW step on a whole batch, called as the program's
    step is, ``((params, opt_state), tokens) -> ((params, opt_state),
    loss)``, on the program's optax state (its moments and count, the
    arithmetic of :func:`train3`): with the cell's control, this is the
    control put in the program's place (``faults.py``)."""
    import jax
    import jax.numpy as jnp
    lr, wd = cfg['lr'], cfg['weight_decay']
    b1, b2, eps = cfg['adam_b1'], cfg['adam_b2'], cfg['adam_eps']
    tree = jax.tree_util.tree_map

    def step(state, tokens):
        params, opt_state = state
        adam = opt_state[0]
        value, grads = jax.value_and_grad(
            lambda p: loss(p, tokens, cfg, control))(params)
        count = adam.count + 1
        mu = tree(lambda m, g: b1 * m + (1 - b1) * g, adam.mu, grads)
        nu = tree(lambda n, g: b2 * n + (1 - b2) * g * g, adam.nu, grads)
        c1 = 1 - b1 ** count.astype(jnp.float32)
        c2 = 1 - b2 ** count.astype(jnp.float32)
        params = tree(lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2)
                                                           + eps) + wd * p),
                      params, mu, nu)
        adam = adam._replace(count=count, mu=mu, nu=nu)
        return (params, (adam,) + tuple(opt_state[1:])), value

    return step


def train3(cfg, params, batches, control=None):
    """Steps the reference through ``batches`` (host batches as
    :meth:`RowSource.rows` gives them) from ``params`` with AdamW, on the
    default device, ``ref_block_rows`` windows at a time (the batch loss is
    the mean of equal-length windows' losses). Returns the losses, the
    first step's gradient and the parameters after the last step, on the
    host."""
    import jax
    import jax.numpy as jnp
    lr, wd, block = cfg['lr'], cfg['weight_decay'], cfg['ref_block_rows']
    b1, b2, eps = cfg['adam_b1'], cfg['adam_b2'], cfg['adam_eps']
    value_and_grad = jax.jit(jax.value_and_grad(
        lambda p, t: loss(p, t, cfg, control)))

    @jax.jit
    def adamw(params, mu, nu, grads, count):
        mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                    nu, grads)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        params = jax.tree_util.tree_map(
            lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)
                                      + wd * p), params, mu, nu)
        return params, mu, nu

    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for count, batch in enumerate(batches, start=1):
        tokens = batch['tokens']
        n = len(tokens)
        total, grads = 0.0, None
        for start in range(0, n, block):
            part = tokens[start:start + block]
            l, g = value_and_grad(params, jnp.asarray(part))
            w = len(part) / n
            total += float(l) * w
            g = jax.tree_util.tree_map(lambda a: a * w, g)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        losses.append(total)
        if first_grad is None:
            first_grad = jax.device_get(grads)
        params, mu, nu = adamw(params, mu, nu, grads, float(count))
    return {'losses': losses, 'grad': first_grad,
            'params': jax.device_get(params)}


def train_flops(cfg, batch):
    """Model FLOPs of one training step on ``batch`` windows: 6 per matmul
    parameter per token (forward 2, backward 4; the input embedding is a
    gather with no multiply) plus causal attention, whose forward QK^T and
    PV take 2 * d_model FLOPs per (query, visible key) pair, (seq+1)/2 keys
    per query on average, and the backward twice that."""
    d, f, v, n = cfg['d_model'], cfg['d_ff'], cfg['vocab_size'], cfg['n_layers']
    seq = cfg['seq_len']
    matmul_params = n * (4 * d * d + 3 * d * f) + d * v
    attention = n * 3 * 2 * d * (seq + 1)
    return batch * seq * (6 * matmul_params + attention)
