"""Host-side document packing: variable-length token sequences → fixed-shape
``(tokens, segment_ids, positions)`` rows for packed-attention training.

This bridges the data layer (documents of any length in a ragged column;
XLA wants static shapes) and the attention kernels' ``segment_ids`` support
(``ops/attention.py``): several documents share one sequence row,
cross-document attention is masked, and positions restart per document so
rotary embeddings see each document at offset 0.

Two entry points, one first-fit (:func:`pack_documents`):

- :func:`pack_documents` packs documents that each fit a row;
- :func:`pack_transform` is the reader-side form: a ``TransformSpec`` for
  ``make_columnar_reader`` that splits, numbers and packs the documents of
  each row group on the worker pool (``docs/packing.md``).

The reference has no packing (its TF/torch consumers tolerate ragged
batches); this is TPU-native capability: pad-to-bucket wastes
``(bucket − len)`` of every row, packing wastes only the part-filled rows'
tails.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import numpy as np


class PackedBatch(NamedTuple):
    """``tokens`` (B, L); ``segment_ids`` (B, L) int32 — 0 marks padding,
    documents count from 1 per row; ``positions`` (B, L) int32 — restart at 0
    on every document boundary; ``first_doc`` (B,) int64 — the index in
    ``docs`` of each row's first document, -1 for a row ``num_rows`` added."""
    tokens: np.ndarray
    segment_ids: np.ndarray
    positions: np.ndarray
    first_doc: np.ndarray


def pack_documents(docs: Sequence[Sequence[int]], seq_len: int, *,
                   pad_token: int = 0, dtype=np.int32,
                   num_rows: Optional[int] = None) -> PackedBatch:
    """Greedy first-fit packing (documents in order, each placed into the
    first row with room; rows in the order they were opened — deterministic,
    so resumable pipelines re-produce identical batches). Numpy arrays out:
    placing them on a device is the loader's job.

    Every document must fit a row: ``len(doc) <= seq_len`` (split longer
    documents upstream, as :func:`pack_transform` does).

    ``num_rows`` pins the batch dimension for jitted consumers: the output
    is padded with all-padding rows up to ``num_rows`` (and packing raises
    if the documents need more). Without it the row count is data-dependent
    — fine eagerly, but every distinct count retraces a jitted train step,
    so streaming pipelines should always pass it.
    """
    lengths = np.fromiter((len(d) for d in docs), np.int64, count=len(docs))
    if (lengths == 0).any():
        raise ValueError('cannot pack an empty document')
    if (lengths > seq_len).any():
        raise ValueError('document of length %d exceeds seq_len=%d; '
                         'split it upstream' % (lengths.max(), seq_len))
    # first fit: space[r] is row r's room; a document opens at most one row
    space = np.empty(len(docs), np.int64)
    row_of = np.empty(len(docs), np.int64)
    start = np.empty(len(docs), np.int64)
    rows = 0
    for i, n in enumerate(lengths):
        r = int(np.argmax(space[:rows] >= n)) if rows else 0
        if not rows or space[r] < n:
            r = rows
            space[r] = seq_len
            rows += 1
        row_of[i], start[i] = r, seq_len - space[r]
        space[r] -= n

    if num_rows is not None and rows > num_rows:
        raise ValueError(
            'documents need %d rows but num_rows=%d; feed fewer '
            'documents per batch' % (rows, num_rows))
    b = rows if num_rows is None else num_rows
    tokens = np.full((b, seq_len), pad_token, dtype=dtype)
    segment_ids = np.zeros((b, seq_len), dtype=np.int32)
    positions = np.zeros((b, seq_len), dtype=np.int32)
    first_doc = np.full(b, -1, np.int64)
    segments = np.zeros(b, np.int32)
    ramp = np.arange(seq_len, dtype=np.int32)
    for i, doc in enumerate(docs):
        r, lo, n = row_of[i], start[i], lengths[i]
        segments[r] += 1
        if segments[r] == 1:
            first_doc[r] = i
        tokens[r, lo:lo + n] = np.asarray(doc, dtype=dtype)
        segment_ids[r, lo:lo + n] = segments[r]
        positions[r, lo:lo + n] = ramp[:n]
    return PackedBatch(tokens, segment_ids, positions, first_doc)


class RowGroupPacker:
    """The function of :func:`pack_transform`'s ``TransformSpec``: one row
    group's documents in, its packed rows out. A plain object (not a
    closure) so process pools can pickle it; it keeps no state between
    row groups."""

    def __init__(self, field: str, seq_len: int, id_field: str):
        self.field = field
        self.seq_len = seq_len
        self.id_field = id_field

    def __repr__(self):
        # stable across processes: part of the reader cache's key
        return 'RowGroupPacker({!r}, {}, {!r})'.format(
            self.field, self.seq_len, self.id_field)

    def __call__(self, columns, record_count=None):
        pieces, numbers, split = [], [], 0
        for doc, first in zip(columns[self.field], columns[self.id_field]):
            split += len(doc) > self.seq_len
            for k, lo in enumerate(range(0, len(doc), self.seq_len)):
                pieces.append(doc[lo:lo + self.seq_len])
                numbers.append(int(first) + k)
        if pieces:
            batch = pack_documents(pieces, self.seq_len)
        else:
            empty = np.zeros((0, self.seq_len), np.int32)
            batch = PackedBatch(empty, empty, empty, np.zeros(0, np.int64))
        out = {self.field: batch.tokens, 'segment_ids': batch.segment_ids,
               'positions': batch.positions,
               self.id_field: np.asarray(numbers, np.int64)[batch.first_doc]}
        if record_count is not None:
            tokens = int(sum(len(p) for p in pieces))
            record_count('pack_rows', len(batch.tokens))
            record_count('pack_tokens', tokens)
            record_count('pack_pad_tokens',
                         batch.tokens.size - tokens)
            record_count('pack_docs_split', split)
        return out


def pack_transform(field: str, seq_len: int, *, id_field: str):
    """A ``TransformSpec`` for ``make_columnar_reader`` that packs each row
    group's documents (a 1-D ragged integer column ``field``) into
    ``seq_len``-token rows:

    - **split**: each document is cut into pieces of at most ``seq_len``
      tokens, in order (an empty document has none);
    - **number**: ``id_field`` holds each document's first piece number,
      and piece ``k`` of a document is numbered ``id + k``; a store whose
      ids count pieces in stored order numbers every piece over the store;
    - **place**: within the row group, pieces in stored order go each into
      the first open row with room (:func:`pack_documents`); rows come out
      in the order they were opened, the last ones part-filled.

    Columns out: ``field`` (L,) int32 (0 on padding), ``segment_ids`` (L,)
    int32 (from 1 per row, 0 on padding), ``positions`` (L,) int32 (from 0
    per piece) and ``id_field``, the number of the row's first piece
    (int64). Every other column is dropped. Nothing carries over
    between row groups, so delivery stays deterministic, parallel over
    workers and resumable by row group. The worker adds the counters
    ``pack_rows``, ``pack_tokens`` (piece tokens), ``pack_pad_tokens`` and
    ``pack_docs_split`` to the reader's ``ReaderStats``.
    """
    from petastorm_tpu.transform import TransformSpec
    fields = [(field, np.int32, (seq_len,), False),
              ('segment_ids', np.int32, (seq_len,), False),
              ('positions', np.int32, (seq_len,), False),
              (id_field, np.int64, (), False)]
    return TransformSpec(RowGroupPacker(field, seq_len, id_field),
                         edit_fields=fields,
                         selected_fields=[f[0] for f in fields],
                         reports_counts=True)


def packed_lm_targets(tokens, segment_ids):
    """Next-token targets and loss weights for a packed batch: weight 1 where
    the current AND next slot belong to the same (nonzero) document — the
    last token of each document and all padding get weight 0, so no document
    is trained to predict its neighbor's first token."""
    import jax.numpy as jnp
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    next_seg = jnp.concatenate(
        [segment_ids[:, 1:], jnp.zeros_like(segment_ids[:, :1])], axis=1)
    weights = ((segment_ids > 0)
               & (segment_ids == next_seg)).astype(jnp.float32)
    return targets, weights
