"""Driver for packed-document pretraining deployments: a store of documents
of heavy-tailed length in a ragged ``ArrowListCodec`` column, packed per row
group into ``seq_len``-token rows by ``packing.pack_transform`` on the
reader's worker pool, and the repo's ``transformer_lm`` AdamW train step
with segment ids and restarting positions (the document-masked flash path).

The model, its weights and its optimizer are the ``transformer_lm``
driver's; only the store, the reader's transform and the step's arguments
differ. The harness calls the functions below by name; see
``chipbench/harness.py``.
"""

import math

import numpy as np

from chipbench.drivers import transformer_lm as lm
from chipbench.drivers.seeded import row_rng

model_config = lm.model_config
init_state = lm.init_state
params_of = lm.params_of
first_grad = lm.first_grad

#: The batch's columns, in the order the step takes them.
COLUMNS = ('tokens', 'segment_ids', 'positions')


def _schema():
    from petastorm_tpu.codecs import ArrowListCodec, ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField
    return Unischema('DocumentSchema', [
        UnischemaField('row_id', np.int64, (), ScalarCodec(), False),
        UnischemaField('tokens', np.uint16, (None,), ArrowListCodec(), False),
    ])


def document(cfg, seed, index):
    """Document ``index``'s tokens (uint16), from the seed and the index
    alone: a lognormal length clipped to the configuration's range, then
    tokens uniform over the vocabulary."""
    rng = row_rng(seed, index, 0)
    length = int(np.clip(round(cfg['doc_len_median'] * math.exp(
        cfg['doc_len_sigma'] * rng.standard_normal())),
        cfg['doc_len_min'], cfg['doc_len_max']))
    return rng.integers(0, cfg['vocab_size'], size=length, dtype=np.uint16)


def write_store(cfg, seed, url):
    """``cfg['rows']`` documents, each with its first piece number as
    ``row_id``: pieces of at most ``seq_len`` tokens, numbered over the
    store in stored order. ``rows`` in the facts is the number of pieces,
    the range a packed row's id lies in."""
    import pyarrow as pa

    from petastorm_tpu.etl.dataset_metadata import materialize_dataset
    schema = _schema()
    docs = [document(cfg, seed, i) for i in range(cfg['rows'])]
    lengths = np.array([len(d) for d in docs], np.int64)
    pieces = -(-lengths // cfg['seq_len'])
    first = np.concatenate([[0], np.cumsum(pieces)[:-1]])
    offsets = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int32)
    table = pa.Table.from_arrays(
        [pa.array(first, pa.int64()),
         pa.ListArray.from_arrays(pa.array(offsets),
                                  pa.array(np.concatenate(docs)))],
        schema=schema.as_arrow_schema())
    with materialize_dataset(url, schema, row_group_size_mb=cfg['row_group_mb'],
                             file_size_mb=1 << 20) as writer:
        writer.write_encoded_table(table)
    return {'rows': int(pieces.sum()),
            'mean_encoded_bytes_per_row': float(2 * lengths.mean())}


def reader_kwargs(cfg, seed):
    from petastorm_tpu.packing import pack_transform
    return {'transform_spec': pack_transform('tokens', cfg['seq_len'],
                                             id_field='row_id')}


def make_step(cfg, mesh, batch):
    """``(step, arg_shapes)``: the program's train step on (batch, seq_len)
    packed rows with their segment ids and positions, under the stable name
    ``chipbench_train_step``, its state donated."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from petastorm_tpu.models import transformer_lm as tlm
    _, inner = tlm.make_train_step(model_config(cfg), mesh,
                                   optimizer=lm._optimizer(cfg))

    def chipbench_train_step(state, tokens, segment_ids, positions):
        params, opt_state, loss = inner(state[0], state[1], tokens,
                                        segment_ids=segment_ids,
                                        positions=positions)
        return (params, opt_state), loss

    replicated = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P('data'))
    shapes = tuple(jax.ShapeDtypeStruct((batch, cfg['seq_len']), jnp.int32,
                                        sharding=rows) for _ in COLUMNS)
    return jax.jit(chipbench_train_step,
                   in_shardings=(replicated,) + (rows,) * len(COLUMNS),
                   out_shardings=(replicated, replicated),
                   donate_argnums=0), shapes


def step_args(batch):
    return tuple(batch[name] for name in COLUMNS)


def values(batch):
    return {name: batch[name] for name in COLUMNS}
