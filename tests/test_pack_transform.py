"""Packing as a reader transform: ``packing.pack_transform`` against a plain
first-fit written here, a columnar reader delivering every piece once per
epoch, the ragged ``ArrowListCodec`` decode it reads, and
``transformer_lm.make_train_step`` on packed batches."""

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from petastorm_tpu.packing import (PackedBatch, pack_documents,
                                   pack_transform)


def _plain_pack(docs, ids, seq_len):
    """The rule, plainly: split, number, first fit, rows in opening order."""
    pieces = []
    for doc, first in zip(docs, ids):
        for k, lo in enumerate(range(0, len(doc), seq_len)):
            pieces.append((first + k, list(doc[lo:lo + seq_len])))
    rows = []                      # [room, [(number, tokens), ...]]
    for number, tokens in pieces:
        for row in rows:
            if row[0] >= len(tokens):
                break
        else:
            row = [seq_len, []]
            rows.append(row)
        row[0] -= len(tokens)
        row[1].append((number, tokens))
    out = {'tokens': [], 'segment_ids': [], 'positions': [], 'row_id': []}
    for _, placed in rows:
        tok, seg, pos = [], [], []
        for s, (_, tokens) in enumerate(placed, start=1):
            tok += tokens
            seg += [s] * len(tokens)
            pos += list(range(len(tokens)))
        pad = seq_len - len(tok)
        out['tokens'].append(tok + [0] * pad)
        out['segment_ids'].append(seg + [0] * pad)
        out['positions'].append(pos + [0] * pad)
        out['row_id'].append(placed[0][0])
    return {k: np.asarray(v) for k, v in out.items()}


def _documents(seed, n, seq_len):
    rng = np.random.default_rng(seed)
    lengths = rng.choice([0, 1, seq_len - 1, seq_len, seq_len + 1,
                          3 * seq_len + 5], size=n // 3).tolist()
    lengths += rng.integers(1, 2 * seq_len, size=n - len(lengths)).tolist()
    rng.shuffle(lengths)
    docs = [rng.integers(0, 60000, size=m).astype(np.uint16) for m in lengths]
    pieces = [-(-m // seq_len) for m in lengths]
    ids = np.concatenate([[0], np.cumsum(pieces)[:-1]]).astype(np.int64)
    return docs, ids


@pytest.mark.parametrize('seed, seq_len', [(0, 16), (1, 16), (2, 7), (3, 64)])
def test_packer_matches_plain_first_fit(seed, seq_len):
    docs, ids = _documents(seed, 40, seq_len)
    column = np.empty(len(docs), dtype=object)
    column[:] = docs
    counts = {}

    def record_count(name, n):
        counts[name] = counts.get(name, 0) + n

    spec = pack_transform('tokens', seq_len, id_field='row_id')
    got = spec.func({'tokens': column, 'row_id': ids}, record_count)
    want = _plain_pack(docs, ids, seq_len)
    assert set(got) == {'tokens', 'segment_ids', 'positions', 'row_id'}
    for name, value in want.items():
        assert got[name].dtype == (np.int64 if name == 'row_id'
                                   else np.int32), name
        np.testing.assert_array_equal(got[name], value, err_msg=name)
    tokens = sum(len(d) for d in docs)
    assert counts == {
        'pack_rows': len(want['tokens']), 'pack_tokens': tokens,
        'pack_pad_tokens': len(want['tokens']) * seq_len - tokens,
        'pack_docs_split': sum(len(d) > seq_len for d in docs)}


def test_packer_of_no_documents_makes_no_rows():
    spec = pack_transform('tokens', 8, id_field='row_id')
    column = np.empty(2, dtype=object)
    column[:] = [np.zeros(0, np.uint16)] * 2
    got = spec.func({'tokens': column, 'row_id': np.arange(2)})
    assert got['tokens'].shape == (0, 8) and got['row_id'].shape == (0,)


def test_pack_documents_returns_numpy_and_first_documents():
    out = pack_documents([[1, 2, 3], [4, 5], [6, 7, 8, 9], [10]], seq_len=6,
                         num_rows=3)
    assert isinstance(out, PackedBatch)
    for array in out:
        assert isinstance(array, np.ndarray)
    # [1,2,3|4,5|10], [6,7,8,9|pad], then the row num_rows added
    np.testing.assert_array_equal(out.first_doc, [0, 2, -1])
    np.testing.assert_array_equal(out.segment_ids[0], [1, 1, 1, 2, 2, 3])


def test_pack_transform_declares_its_columns():
    spec = pack_transform('tokens', 32, id_field='row_id')
    assert spec.selected_fields == ['tokens', 'segment_ids', 'positions',
                                    'row_id']
    shapes = {f.name: (np.dtype(f.numpy_dtype), f.shape)
              for f in spec.edit_fields}
    assert shapes['tokens'] == (np.dtype(np.int32), (32,))
    assert shapes['row_id'] == (np.dtype(np.int64), ())
    # a picklable, stably named function (process pools, cache keys)
    assert 'RowGroupPacker' in repr(spec.func)


def test_ragged_list_column_decodes_to_views_of_the_field_dtype():
    from petastorm_tpu.codecs import ArrowListCodec
    from petastorm_tpu.readers.columnar_worker import _column_to_numpy
    from petastorm_tpu.unischema import UnischemaField
    field = UnischemaField('tokens', np.uint16, (None,), ArrowListCodec(),
                           False)
    rows = [[1, 2, 3], [], [65535], [4, 5]]
    whole = pa.array(rows, pa.list_(pa.uint16()))
    # two chunks, the second a slice: offsets that do not start at 0
    column = pa.chunked_array([whole.slice(0, 1), whole.slice(1, 3)])
    got = _column_to_numpy(column, field)
    assert got.dtype == object and len(got) == 4
    for cell, want in zip(got, rows):
        assert cell.dtype == np.uint16
        np.testing.assert_array_equal(cell, want)


@pytest.fixture(scope='module')
def document_store(tmp_path_factory):
    """32 documents of 1-90 tokens over several row groups, ids counting
    pieces of 16 tokens in stored order."""
    from petastorm_tpu.codecs import ArrowListCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import materialize_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField
    schema = Unischema('Docs', [
        UnischemaField('row_id', np.int64, (), ScalarCodec(), False),
        UnischemaField('tokens', np.uint16, (None,), ArrowListCodec(), False)])
    docs, ids = _documents(11, 32, 16)
    docs = [d if len(d) else np.ones(1, np.uint16) for d in docs]
    ids = np.concatenate([[0], np.cumsum([-(-len(d) // 16)
                                          for d in docs])[:-1]])
    url = 'file://' + str(tmp_path_factory.mktemp('docs'))
    with materialize_dataset(url, schema, row_group_size_mb=0.0004) as w:
        w.write_rows({'row_id': np.int64(i), 'tokens': d}
                     for i, d in zip(ids, docs))
    pieces = {}
    for first, doc in zip(ids, docs):
        for k, lo in enumerate(range(0, len(doc), 16)):
            pieces[int(first) + k] = tuple(doc[lo:lo + 16])
    return url, pieces


def _pieces_of(batch):
    """``[(tokens of each piece)]`` and ``{row id: first piece}`` of a
    packed batch."""
    found, firsts = [], {}
    for tok, seg, row_id in zip(batch['tokens'], batch['segment_ids'],
                                batch['row_id']):
        for s in range(1, seg.max() + 1):
            found.append(tuple(tok[seg == s]))
        firsts[int(row_id)] = tuple(tok[seg == 1])
    return found, firsts


@pytest.mark.parametrize('pool', ['dummy', 'thread', 'process'])
def test_reader_delivers_every_piece_once_per_epoch(document_store, pool):
    from petastorm_tpu import make_columnar_reader
    url, pieces = document_store
    found, firsts, stats = [], {}, None
    with make_columnar_reader(
            url, num_epochs=2, reader_pool_type=pool, workers_count=2,
            shuffle_row_groups=True, seed=3,
            transform_spec=pack_transform('tokens', 16,
                                          id_field='row_id')) as reader:
        for batch in reader:
            got, first = _pieces_of(batch._asdict())
            found += got
            firsts.update(first)
            assert batch.positions.max() < 16
        stats = reader.stats.snapshot() if reader.stats is not None else None
    assert sorted(found) == sorted(list(pieces.values()) * 2)
    for row_id, tokens in firsts.items():
        assert pieces[row_id] == tokens
    if stats is not None:
        assert stats['pack_tokens'] == 2 * sum(len(p) for p in
                                               pieces.values())
        assert stats['pack_rows'] * 16 == (stats['pack_tokens']
                                           + stats['pack_pad_tokens'])
        assert stats['worker_transform_s'] > 0
        assert stats['worker_decode_s'] >= stats['worker_transform_s']


# -- the train step on packed batches ------------------------------------------

def _tiny():
    from petastorm_tpu.models import transformer_lm as tlm
    return tlm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                 n_layers=1, d_ff=64, max_seq_len=16,
                                 dtype=jnp.float32)


def _packed_batch():
    rng = np.random.default_rng(5)
    return pack_documents([rng.integers(0, 64, n) for n in (5, 9, 3, 12, 4)],
                          16, num_rows=4)


def _manual_step(cfg, optimizer, params, **loss_kwargs):
    import optax

    from petastorm_tpu.models import transformer_lm as tlm
    loss, grads = jax.value_and_grad(tlm.loss_fn)(params, config=cfg,
                                                  **loss_kwargs)
    updates, _ = optimizer.update(grads, optimizer.init(params), params)
    return optax.apply_updates(params, updates), loss


@pytest.mark.parametrize('devices', [0, 2], ids=['no_mesh', 'data2'])
def test_train_step_packed_matches_loss_fn(devices):
    from jax.sharding import Mesh

    from petastorm_tpu.models import transformer_lm as tlm
    from petastorm_tpu.packing import packed_lm_targets
    cfg = _tiny()
    mesh = (Mesh(np.asarray(jax.devices()[:devices]), ('data',))
            if devices else None)
    params = tlm.init(jax.random.PRNGKey(0), cfg)
    optimizer, step = tlm.make_train_step(cfg, mesh)
    batch = _packed_batch()
    got, _, loss = step(params, optimizer.init(params), batch.tokens,
                        segment_ids=batch.segment_ids,
                        positions=batch.positions)
    targets, weights = packed_lm_targets(batch.tokens, batch.segment_ids)
    want, want_loss = _manual_step(
        cfg, optimizer, params, tokens=batch.tokens, targets=targets,
        positions=batch.positions, segment_ids=batch.segment_ids,
        weights=weights)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize('devices', [0, 2], ids=['no_mesh', 'data2'])
def test_train_step_unpacked_is_the_plain_step(devices):
    from jax.sharding import Mesh

    from petastorm_tpu.models import transformer_lm as tlm
    cfg = _tiny()
    mesh = (Mesh(np.asarray(jax.devices()[:devices]), ('data',))
            if devices else None)
    params = tlm.init(jax.random.PRNGKey(1), cfg)
    optimizer, step = tlm.make_train_step(cfg, mesh)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 64, (4, 16)),
                         jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    got, _, loss = step(params, optimizer.init(params), tokens, targets)
    want, want_loss = _manual_step(cfg, optimizer, params, tokens=tokens,
                                   targets=targets)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    if mesh is not None:      # the unpacked call keeps its one compiled form
        assert step.lower(params, optimizer.init(params), tokens,
                          targets).compile() is not None


def test_packed_step_refuses_targets():
    from petastorm_tpu.models import transformer_lm as tlm
    cfg = _tiny()
    params = tlm.init(jax.random.PRNGKey(0), cfg)
    optimizer, step = tlm.make_train_step(cfg)
    batch = _packed_batch()
    with pytest.raises(ValueError, match='targets'):
        step(params, optimizer.init(params), batch.tokens, batch.tokens,
             segment_ids=batch.segment_ids, positions=batch.positions)
