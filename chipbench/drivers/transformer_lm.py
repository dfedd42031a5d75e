"""Driver for language-model pretraining deployments: a store of
pretokenized windows in ``NdarrayCodec`` (the bytes-through device-decode
path) and the repo's ``transformer_lm`` AdamW train step, data-parallel
over the cell's mesh with the parameters replicated.

The harness calls the functions below by name; see ``chipbench/harness.py``.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.drivers.seeded import row_rng


def _schema(cfg):
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    # petastorm_tpu/benchmark/northstar.make_token_schema's NdarrayCodec
    # layout, plus the row id that names a row in every batch
    return Unischema('TokenSchema', [
        UnischemaField('row_id', np.int64, (), ScalarCodec(), False),
        UnischemaField('tokens', np.int32, (cfg['seq_len'] + 1,),
                       NdarrayCodec(), False),
    ])


def write_store(cfg, seed, url):
    """``cfg['rows']`` windows of ``seq_len + 1`` tokens drawn uniformly from
    the vocabulary (northstar.generate_token_dataset's content), each from
    the seed and its row id alone."""
    import pyarrow as pa

    from petastorm_tpu.etl.dataset_metadata import materialize_dataset
    from petastorm_tpu.unischema import encode_row
    schema = _schema(cfg)

    def encoded(row_id):
        tokens = row_rng(seed, row_id, 0).integers(
            0, cfg['vocab_size'], size=(cfg['seq_len'] + 1,), dtype=np.int32)
        return encode_row(schema, {'row_id': np.int64(row_id),
                                   'tokens': tokens})

    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        rows = list(pool.map(encoded, range(cfg['rows'])))
    table = pa.Table.from_pylist(rows, schema=schema.as_arrow_schema())
    with materialize_dataset(url, schema, row_group_size_mb=cfg['row_group_mb'],
                             file_size_mb=1 << 20) as writer:
        writer.write_encoded_table(table)
    return {'rows': len(rows),
            'mean_encoded_bytes_per_row': float(np.mean(
                [len(r['tokens']) for r in rows]))}


def reader_kwargs(cfg, seed):
    return {}


def model_config(cfg):
    import jax.numpy as jnp

    from petastorm_tpu.models.transformer_lm import TransformerConfig
    return TransformerConfig(
        vocab_size=cfg['vocab_size'], d_model=cfg['d_model'],
        n_heads=cfg['n_heads'], n_layers=cfg['n_layers'], d_ff=cfg['d_ff'],
        max_seq_len=cfg['seq_len'], attention=cfg['attention'],
        dtype=jnp.dtype(cfg['compute_dtype']))


def _optimizer(cfg):
    import optax
    return optax.adamw(cfg['lr'], b1=cfg['adam_b1'], b2=cfg['adam_b2'],
                       eps=cfg['adam_eps'], weight_decay=cfg['weight_decay'])


def init_state(cfg, seed, mesh):
    """``(params, opt_state)``, made on the devices in one jitted call from
    the seed and replicated over the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from petastorm_tpu.models import transformer_lm as tlm
    config = model_config(cfg)
    optimizer = _optimizer(cfg)

    def make(key):
        params = tlm.init(key, config)
        return params, optimizer.init(params)

    # every leaf replicated, as the step's out_shardings leave them, so the
    # state the step returns is the state it was compiled for
    return jax.jit(make, out_shardings=NamedSharding(mesh, P()))(
        jax.random.PRNGKey(seed % (1 << 32)))


def make_step(cfg, mesh, batch):
    """``(step, arg_shapes)``: the program's train step on (batch, seq + 1)
    windows under the stable name ``chipbench_train_step``, its state
    donated, and the shape of the token batch it is compiled for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from petastorm_tpu.models import transformer_lm as tlm
    _, inner = tlm.make_train_step(model_config(cfg), mesh,
                                   optimizer=_optimizer(cfg))

    def chipbench_train_step(state, tokens):
        params, opt_state, loss = inner(state[0], state[1], tokens[:, :-1],
                                        tokens[:, 1:])
        return (params, opt_state), loss

    replicated = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P('data'))
    shapes = (jax.ShapeDtypeStruct((batch, cfg['seq_len'] + 1), jnp.int32,
                                   sharding=rows),)
    return jax.jit(chipbench_train_step, in_shardings=(replicated, rows),
                   out_shardings=(replicated, replicated),
                   donate_argnums=0), shapes


def step_args(batch):
    return (batch['tokens'],)


def values(batch):
    return {'tokens': batch['tokens']}


def params_of(state):
    return state[0]


def first_grad(cfg, params0, state1):
    """The gradient AdamW took in step 1, from its state: after one step
    its first moment is ``(1 - b1) * g``."""
    import jax
    return jax.tree_util.tree_map(
        lambda m: np.asarray(m, np.float32) / np.float32(1 - cfg['adam_b1']),
        jax.device_get(state1[1][0].mu))
