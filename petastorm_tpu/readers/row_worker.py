"""Row-granular reader worker: reads one row group, decodes rows with codecs,
applies predicates/transforms/ngram, publishes lists of row dicts.

Reference parity: ``petastorm/py_dict_reader_worker.py`` — worker (:99-274),
predicate pushdown inside the worker (:188-252), row-level cache keyed by
dataset path + piece (:155-163), ngram assembly (:165-166), shuffle_row_drop
partitioning incl. ngram continuation rows (:260-273), results-queue reader
(:63-96).
"""

from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

from petastorm_tpu.lineage import NEVER_QUARANTINE, unwrap_envelope
from petastorm_tpu.ngram import NGramWindowChunk
from petastorm_tpu.readers.piece_worker import ParquetPieceWorker
from petastorm_tpu.unischema import decode_row
from petastorm_tpu.utils import cast_partition_value


def _cast_partition_value(field, value: str):
    return cast_partition_value(field.numpy_dtype if field is not None else None, value)


class RowGroupResultsReader:
    """Consumer-side: buffers published row lists and pops one row at a time as
    schema namedtuples (reference ``PyDictReaderWorkerResultsQueueReader``)."""

    def __init__(self, schema, ngram, lineage=None):
        self._schema = schema
        self._ngram = ngram
        self._buffer: List = []
        if ngram is not None:
            self._offsets, self._base_offset, self._fields_at = \
                ngram.timestep_layout(schema.fields)
        # Multiple consumer threads may drain one reader concurrently
        # (reference ``test_multithreaded_reads``): without the lock, two
        # threads can both see an empty buffer, both fetch a chunk, and one
        # assignment silently overwrites the other's unconsumed rows.
        self._lock = threading.Lock()
        #: The reader's :class:`~petastorm_tpu.lineage.LineageTracker`;
        #: provenance envelopes unwrap (and register) here, and
        #: ``last_seq``/``last_row_offset`` name the source of the most
        #: recently popped row (single-consumer contract: with concurrent
        #: consumer threads the attribution is per-thread approximate).
        self._lineage = lineage if getattr(lineage, 'enabled', False) else None
        self._buffer_seq = None
        self.last_seq = None
        self.last_row_offset = None

    @property
    def batched_output(self) -> bool:
        return False

    def _chunk_window_dict(self, chunk, i):
        """Slice window ``i`` out of a columnar chunk as the same
        ``{offset: {field: value}}`` layout the per-row worker path ships."""
        start = chunk.starts[i]
        cols = chunk.columns
        return {off: {name: cols[name][start + off - self._base_offset]
                      for name in self._fields_at[off] if name in cols}
                for off in self._offsets}

    def read_next(self, pool):
        with self._lock:
            while not self._buffer:
                # raises EmptyResultError at end of stream; propagates to Reader
                item, seq = unwrap_envelope(pool.get_results(), self._lineage)
                self._buffer_seq = seq
                if isinstance(item, NGramWindowChunk):
                    self._buffer = [self._chunk_window_dict(item, i)
                                    for i in range(len(item))]
                else:
                    self._buffer = list(item)
            item = self._buffer.pop()
            # pop() takes the payload's tail: after it, len(buffer) IS the
            # popped row's offset within the published payload
            self.last_seq = self._buffer_seq
            self.last_row_offset = len(self._buffer)
        if self._ngram:
            # workers ship windows as plain dicts (namedtuple classes of
            # schema views cannot cross the process-pool pickle boundary);
            # assemble the per-timestep namedtuples here on the consumer
            return self._ngram.make_namedtuples(item, self._schema)
        return self._schema.make_namedtuple(**item)

    def discard_buffered(self):
        """Drop windows buffered from a partially-consumed published item —
        ``Reader.drain()`` must leave nothing stale for the next pass."""
        with self._lock:
            self._buffer = []

    def read_next_chunk(self, pool):
        """One published item, raw — the JAX loader's chunked NGram path pulls
        whole :class:`NGramWindowChunk`s and collates them vectorized. Only
        valid on a reader whose workers publish chunks
        (``Reader.ngram_chunked``) and must not be mixed with per-window
        ``read_next`` calls on a buffered item."""
        chunk, seq = unwrap_envelope(pool.get_results(), self._lineage)
        if seq is not None:
            self.last_seq = seq
            self.last_row_offset = None
        return chunk


class RowGroupWorker(ParquetPieceWorker):
    """Processes ventilated ``(piece_index, worker_predicate,
    shuffle_row_drop_partition)`` items."""

    def __init__(self, worker_id, publish_func, args):
        super().__init__(worker_id, publish_func, args)
        self._ngram = args['ngram']

    def process(self, piece_index: int, worker_predicate=None,
                shuffle_row_drop_partition=(0, 1), epoch=0):
        piece = self._split_pieces[piece_index]
        self._begin_item(piece, piece_index, epoch, shuffle_row_drop_partition)
        if (self._ngram is not None and worker_predicate is None
                and self._transform_spec is None):
            # Columnar window path: decode the group column-wise (vectorized
            # codecs, zero per-row Python), scan valid window starts with the
            # shared vectorized scan, and publish ONE chunk instead of
            # per-window dicts — the round-4 per-row assembler stole enough
            # worker GIL time to run 3.4x slower than its indexed twin on the
            # identical workload. Predicate/transform items keep
            # the row path: both contracts are per-row here.
            try:
                chunk = self._form_window_chunk(piece,
                                                shuffle_row_drop_partition)
            except Exception as e:  # noqa: BLE001 - policy decides
                if not self._quarantine_item('decode', e):
                    raise
                return
            if chunk is not None:
                self._publish_item(chunk, ('windows', len(chunk)), len(chunk))
            else:
                self._finish_item_empty()
            return
        try:
            if worker_predicate is not None:
                rows = self._load_rows_with_predicate(piece, worker_predicate)
            else:
                cache_key = self._cache_key('rowgroup', piece)
                rows = self._cached_load(cache_key,
                                         lambda: self._load_rows(piece))
        except Exception as e:  # noqa: BLE001 - policy decides
            if not self._quarantine_item('decode', e):
                raise
            return
        offsets = self._last_offsets
        rows, offsets = self._drop_partition(rows, piece,
                                             *shuffle_row_drop_partition,
                                             offsets=offsets)
        if self._transform_spec is not None:
            rows, offsets = self._transform_rows(rows, offsets)
        if self._ngram is not None:
            rows = self._ngram.form_ngram_dicts(rows, self._transformed_schema)
            if rows:
                # windows, not rows: window k spans several source rows
                self._publish_item(rows, ('windows', len(rows)), len(rows))
            else:
                self._finish_item_empty()
            return
        if rows:
            self._publish_item(rows,
                               self._compact_selection(offsets, len(rows)),
                               len(rows))
        else:
            self._finish_item_empty()

    # -- columnar window path --------------------------------------------------

    def _load_columns(self, piece, names, preserve_scalar_nulls=False,
                      tolerant=False):
        """Read + columnar-decode ``names`` (partition columns synthesized) —
        shared by the window-chunk path and the columnar row load.

        ``tolerant``: collect cell-level codec failures and drop the failing
        rows (quarantine; sets ``self._last_offsets`` to the kept source
        offsets). The window-chunk path keeps it off — dropping rows from a
        window universe would silently shift every window after the hole, so
        NGram corruption quarantines at item granularity instead.

        ``preserve_scalar_nulls``: the ROW path's contract is decode_row's —
        a null cell is ``None``, never a NaN-holed float that an astype to
        the declared int dtype would turn into garbage. Null-bearing scalar
        columns re-decode per cell with the field's own decode semantics
        into object arrays. Scoped HERE (not in the shared
        ``_column_to_numpy``): the columnar/indexed batch paths need a
        STABLE numeric dtype per field across row groups (their assembly
        pre-allocates from the first piece), and they keep the documented
        NaN-holing arrow/pandas parity."""
        from petastorm_tpu.readers.columnar_worker import make_partition_columns
        table = self._read_columns(piece, self._stored_columns(names, piece))
        sink = self._decode_error_sink() if tolerant else None
        columns = self._decode_table(table, names, error_sink=sink)
        if preserve_scalar_nulls:
            for name in names:
                if name not in table.column_names or name not in columns:
                    continue
                column = table.column(name)
                if not column.null_count or columns[name].dtype == object:
                    continue   # object columns already carry None cells
                field = self._full_schema.fields[name]
                decode = self._decode_overrides.get(name)
                if decode is None and field.codec is not None:
                    decode = (lambda v, _f=field: _f.codec.decode(_f, v))
                elif decode is None and isinstance(field.numpy_dtype, np.dtype) \
                        and field.numpy_dtype.kind in 'biufc':
                    decode = field.numpy_dtype.type
                out = np.empty(len(column), dtype=object)
                out[:] = [None if v is None
                          else (decode(v) if decode is not None else v)
                          for v in column.to_pylist()]
                columns[name] = out
        n = table.num_rows
        offsets = self._range_offsets(n) if self._tracks_offsets else None
        if sink is not None and sink.errors:
            columns, kept = self._apply_quarantine_drops(columns, sink, n)
            offsets = kept
            n = len(kept)
        columns.update(make_partition_columns(self._full_schema, piece,
                                              n, set(names)))
        self._last_offsets = offsets
        return columns

    def _load_window_columns(self, piece):
        """Decode every field the NGram references, column-wise."""
        return self._load_columns(
            piece, [n for n in self._ngram.get_all_field_names()
                    if n in self._full_schema.fields])

    def _form_window_chunk(self, piece, shuffle_row_drop_partition):
        cache_key = self._cache_key('ngram_cols', piece)
        columns = self._cached_load(
            cache_key, lambda: self._load_window_columns(piece))
        partition, num_partitions = shuffle_row_drop_partition
        if num_partitions > 1:
            # same semantics as _drop_partition: a file-order slice, extended
            # by length-1 continuation rows so boundary-spanning windows
            # survive (sorting happens after the slice, like the row path)
            n = len(next(iter(columns.values()))) if columns else 0
            bounds = np.linspace(0, n, num_partitions + 1, dtype=int)
            start = int(bounds[partition])
            stop = min(int(bounds[partition + 1]) + self._ngram.length - 1, n)
            if stop <= start:
                return None
            columns = {k: v[start:stop] for k, v in columns.items()}
        return self._ngram.form_windows_columnar(columns)

    # -- loading ---------------------------------------------------------------

    def _planned_columns(self, piece):
        """Mirror the primary read of each no-predicate branch of
        :meth:`process` so the readahead prefetches the exact same column
        list (key equality is what turns a prefetch into a hit)."""
        if self._ngram is not None and self._transform_spec is None:
            # columnar window-chunk path (_load_window_columns)
            names = [n for n in self._ngram.get_all_field_names()
                     if n in self._full_schema.fields]
        elif self._ngram is not None:
            # ngram fallback row path (_load_rows with ngram)
            names = [n for n in self._ngram.get_all_field_names()
                     if n in self._schema.fields or n in self._full_schema.fields]
        else:
            names = list(self._schema.fields.keys())
        return self._stored_columns(names, piece)

    def _planned_cache_key(self, piece, params):
        # mirror process(): the plain-ngram branch caches decoded window
        # columns; every other no-predicate item caches decoded row dicts
        if self._ngram is not None and self._transform_spec is None:
            return self._cache_key('ngram_cols', piece)
        return self._cache_key('rowgroup', piece)

    def _read_columns(self, piece, columns: List[str]):
        return self._read_row_group(piece, columns)

    def _decode_with_partitions(self, raw_rows: List[dict], piece, schema) -> List[dict]:
        self.beat('decode')   # entry beat: a wedged codec shows as `decode`
        start = time.perf_counter()
        decoded = []
        partition_items = piece.partition_dict.items()
        for raw in raw_rows:
            for key, value in partition_items:
                field = schema.fields.get(key)
                if field is not None:
                    raw[key] = _cast_partition_value(field, value)
            decoded.append(decode_row(raw, schema, self._decode_overrides))
        elapsed = time.perf_counter() - start
        self.record_latency('decode', elapsed)
        self.record_span('decode_rows', 'decode', start, elapsed)
        return decoded

    def _load_rows(self, piece) -> List[dict]:
        if self._ngram is not None:
            # ngram fallback items (predicate/transform) still row-load the
            # full window universe; the plain ngram path ships chunks instead
            field_names = [n for n in self._ngram.get_all_field_names()
                           if n in self._schema.fields or n in self._full_schema.fields]
            table = self._read_columns(piece,
                                       self._stored_columns(field_names, piece))
            rows = self._decode_with_partitions(table.to_pylist(), piece,
                                                self._full_schema)
            self._last_offsets = (self._range_offsets(len(rows))
                                  if self._tracks_offsets else None)
            return rows
        # Row path decodes COLUMN-wise (shared _decode_table: hoisted cell
        # decoders, zero-copy cell views, vectorized scalar/list conversion)
        # and then splits into row dicts — ~2x less non-codec overhead per
        # row than to_pylist + per-row decode_row on decode-bound stores.
        names = list(self._schema.fields.keys())
        columns = self._load_columns(piece, names, preserve_scalar_nulls=True,
                                     tolerant=self._tolerant_decode)
        keys = [n for n in names if n in columns]
        cols = [columns[k] for k in keys]
        return [dict(zip(keys, values)) for values in zip(*cols)]

    def _load_rows_with_predicate(self, piece, predicate) -> List[dict]:
        """Read predicate columns first; early-exit when nothing matches
        (reference ``py_dict_reader_worker.py:188-252``)."""
        predicate_fields = predicate.get_fields()
        unknown = set(predicate_fields) - set(self._full_schema.fields.keys())
        if unknown:
            raise ValueError('Predicate uses unknown fields: {}'.format(sorted(unknown)))
        predicate_table = self._read_columns(
            piece, self._stored_columns(predicate_fields, piece))
        predicate_rows = self._decode_with_partitions(
            predicate_table.to_pylist(), piece, self._full_schema)
        match_indices = [i for i, row in enumerate(predicate_rows)
                         if predicate.do_include({f: row[f] for f in predicate_fields})]
        self._last_offsets = (np.asarray(match_indices, dtype=np.int64)
                              if self._tracks_offsets else None)
        if not match_indices:
            return []
        other_fields = [n for n in self._schema.fields.keys() if n not in predicate_fields]
        if other_fields:
            other_table = self._read_columns(
                piece, self._stored_columns(other_fields, piece)).take(match_indices)
            other_rows = self._decode_with_partitions(
                other_table.to_pylist(), piece, self._full_schema)
        else:
            other_rows = [{} for _ in match_indices]
        result = []
        for matched_at, extra in zip(match_indices, other_rows):
            row = {f: predicate_rows[matched_at][f] for f in predicate_fields
                   if f in self._schema.fields}
            row.update(extra)
            result.append(row)
        return result

    # -- post-processing -------------------------------------------------------

    def _drop_partition(self, rows: List[dict], piece, partition: int,
                        num_partitions: int, offsets=None):
        """Deterministically keep 1/num_partitions of the row group; with ngram,
        extend by length-1 continuation rows so windows spanning the boundary
        survive (reference ``py_dict_reader_worker.py:260-273``). Returns
        ``(rows, offsets)`` with the provenance offsets sliced in lockstep."""
        if num_partitions <= 1:
            return rows, offsets
        bounds = np.linspace(0, len(rows), num_partitions + 1, dtype=int)
        start, stop = bounds[partition], bounds[partition + 1]
        if self._ngram is not None:
            stop = min(stop + self._ngram.length - 1, len(rows))
        offsets = self._slice_offsets(offsets, start, stop)
        return rows[start:stop], offsets

    def _transform_rows(self, rows: List[dict], offsets):
        """Apply the TransformSpec per row; under quarantine/skip policies a
        row whose transform raises is dropped (and recorded with its exact
        source offset) instead of killing the worker."""
        if not self._tolerant_decode:
            return [self._apply_transform(r) for r in rows], offsets
        out, kept = [], []
        range_base = offsets[1] if isinstance(offsets, tuple) else None
        for i, row in enumerate(rows):
            try:
                out.append(self._apply_transform(row))
                kept.append(i)
            except NEVER_QUARANTINE:
                raise   # infrastructure failure, not a bad sample: stay loud
            except Exception as e:  # noqa: BLE001 - policy decides
                if offsets is None:
                    off = None
                elif range_base is not None:
                    off = range_base + i
                else:
                    off = int(offsets[i])
                self._quarantine_event(
                    'transform', e, rows=1,
                    row_offsets=None if off is None else [off])
        if offsets is not None and len(kept) != len(rows):
            if isinstance(offsets, tuple):
                offsets = np.arange(offsets[1], offsets[2], dtype=np.int64)
            offsets = (offsets[np.asarray(kept, dtype=np.int64)]
                       if kept else offsets[:0])
        return out, offsets

    def _apply_transform(self, row: dict) -> dict:
        spec = self._transform_spec
        if spec.func is not None:
            row = spec.func(row)
        return {name: row[name] for name in self._transformed_schema.fields if name in row}
