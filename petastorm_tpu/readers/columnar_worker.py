"""Columnar decoded reader worker: the vectorized path for petastorm_tpu
(codec) datasets.

The reference forces codec datasets through a per-row path
(``petastorm/py_dict_reader_worker.py``: ``to_pylist`` -> per-row dict ->
``decode_row`` -> namedtuple), which caps Python-side throughput at tens of
thousands of rows/sec. TPU batches are columnar, so this worker decodes a row
group **column-wise**: scalar columns convert via ``Table.to_numpy`` (no
Python per row), codec columns decode cell-by-cell straight into one
preallocated ``(n, *shape)`` array, and the consumer receives a dict of
column arrays with zero per-row Python work. No reference analogue — this
path exists because the JAX adapter wants exactly this layout.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np
import pyarrow as pa

from petastorm_tpu.codecs import batched_decode_enabled, split_binary_chunk
from petastorm_tpu.lineage import NEVER_QUARANTINE, unwrap_envelope
from petastorm_tpu.readers.piece_worker import ParquetPieceWorker
from petastorm_tpu.utils import cast_partition_value


class ColumnarResultsReader:
    """Consumer-side: published dict of column arrays -> batch namedtuple
    (``batched_output=True``)."""

    def __init__(self, schema, ngram=None, lineage=None):
        assert ngram is None, 'NGram is not supported by the columnar reader'
        self._schema = schema
        self._lineage = lineage if getattr(lineage, 'enabled', False) else None
        self.last_seq = None
        self.last_row_offset = None

    @property
    def batched_output(self) -> bool:
        return True

    def read_next(self, pool):
        columns, seq = unwrap_envelope(pool.get_results(), self._lineage)
        if seq is not None:
            self.last_seq = seq
        return self._schema.make_batch_namedtuple(**columns)


def _binary_cell_views(column: pa.ChunkedArray) -> list:
    """Zero-copy ``uint8`` ndarray views of every cell of a (large_)binary
    column; ``None`` for null cells.

    Slicing arrow's offsets+data buffers directly replaces ``to_pylist()``,
    which materializes a python ``bytes`` copy per cell — measurable per-cell
    overhead in decode-bound pipelines. The views keep the arrow buffer alive
    via their ``base`` reference."""
    cells = []
    for chunk in column.chunks:
        n = len(chunk)
        if not n:
            continue
        offsets, data = split_binary_chunk(chunk)
        if chunk.null_count:
            valid = chunk.is_valid().to_numpy(zero_copy_only=False)
            cells.extend(
                data[offsets[i]:offsets[i + 1]] if valid[i] else None
                for i in range(n))
        else:
            cells.extend(data[lo:hi]
                         for lo, hi in zip(offsets[:-1], offsets[1:]))
    return cells


def _decode_column_batched(column: pa.ChunkedArray, field,
                           n: int) -> Optional[np.ndarray]:
    """One-call-per-chunk vectorized decode via the codec's
    ``make_column_decoder``, or ``None`` to punt to the per-cell loop.

    Per the batched contract (``docs/decode.md``) this path only runs for
    fixed-shape fields on null-free columns; any chunk the codec cannot
    vectorize (or that raises — corrupt cells included) punts the WHOLE
    column, so error/quarantine semantics stay exactly the per-cell
    loop's."""
    make = getattr(field.codec, 'make_column_decoder', None)
    if make is None:
        return None
    decode_chunk = make(field)
    if decode_chunk is None:
        return None
    parts = []
    for chunk in column.chunks:
        if not len(chunk):
            continue
        try:
            part = decode_chunk(chunk)
        except NEVER_QUARANTINE:
            raise   # infrastructure failure, not a bad sample: stay loud
        except Exception:  # noqa: BLE001 - per-cell retry owns the error
            return None
        if part is None:
            return None
        parts.append(part)
    if not parts:
        return None
    if len(parts) > 1:
        first = parts[0]
        if any(p.dtype != first.dtype or p.shape[1:] != first.shape[1:]
               for p in parts[1:]):
            # cross-chunk geometry drift: the per-cell dense loop would
            # fail its assignment — let it own that failure
            return None
        out = np.concatenate(parts)
    else:
        out = parts[0]
    return out if len(out) == n else None


def _decode_binary_column(column: pa.ChunkedArray, field,
                          decode_override=None,
                          on_cell_error=None, batched=True,
                          path_counts=None) -> np.ndarray:
    """Decode a codec-encoded binary column into (n, *shape) (fixed shapes)
    or an object array (wildcard shapes, null cells, non-ndarray payloads).

    The row-group-vectorized path runs first (``batched``, default on):
    fixed-shape, null-free, non-overridden columns decode through the
    codec's ``make_column_decoder`` — one numpy/pyarrow call per column
    chunk instead of N Python calls. Columns the codec cannot vectorize
    (and any chunk that raises) fall back to the per-cell loop below,
    which owns the exact error/quarantine semantics; ``path_counts``
    (``{'batched': int, 'percell': int}``) records which path decoded how
    many cells, feeding the ``rows_decoded_batched``/``rows_decoded_percell``
    counters.

    On the per-cell path, cells reach the decoder as zero-copy buffer views
    and the callable comes from ``codec.make_cell_decoder`` (per-column
    setup hoisted out of the loop) — the two halves of keeping this loop
    pure decode.

    ``on_cell_error`` (bad-sample quarantine, see
    :mod:`petastorm_tpu.lineage`): instead of a corrupt cell killing the
    worker, the column is re-decoded tolerantly — every failing cell is
    reported as ``on_cell_error(row_offset, exc)`` and decodes to ``None``
    in an object array; the caller drops those rows and re-densifies. The
    dense fast path runs first, so clean columns pay nothing."""
    n = len(column)
    fixed = field.shape is not None and all(s is not None for s in field.shape)
    if not n:
        if fixed:
            return np.empty((0,) + tuple(field.shape), dtype=field.numpy_dtype)
        return np.empty(0, dtype=object)
    if (batched and decode_override is None and fixed
            and column.null_count == 0 and field.codec is not None):
        out = _decode_column_batched(column, field, n)
        if out is not None:
            if path_counts is not None:
                path_counts['batched'] += n
            return out
    if path_counts is not None:
        path_counts['percell'] += n
    decode = decode_override or field.codec.make_cell_decoder(field)
    cells = _binary_cell_views(column)
    if on_cell_error is not None:
        try:
            return _decode_cells(cells, decode, n, fixed, column.null_count)
        except NEVER_QUARANTINE:
            raise   # infrastructure failure, not a bad sample: stay loud
        except Exception:
            out = np.empty(n, dtype=object)
            failed = False
            for i, cell in enumerate(cells):
                if cell is None:
                    out[i] = None
                    continue
                try:
                    out[i] = decode(cell)
                except NEVER_QUARANTINE:
                    raise
                except Exception as e:  # noqa: BLE001 - reported, row dropped
                    failed = True
                    on_cell_error(i, e)
                    out[i] = None
            if not failed:
                # every cell decoded cleanly on retry: the dense-path failure
                # was NOT a per-cell decode error (e.g. a codec returning a
                # wrong-shaped array breaking dense assignment) — silently
                # publishing an object column would hide it; re-raise so the
                # item-level policy sees the real exception
                raise
            return out
    return _decode_cells(cells, decode, n, fixed, column.null_count)


def _decode_cells(cells, decode, n: int, fixed: bool,
                  null_count: int) -> np.ndarray:
    """The dense/object decode loops shared by the fast and tolerant paths."""
    if fixed and null_count == 0:
        first = decode(cells[0])
        if isinstance(first, np.ndarray):
            out = np.empty((n,) + first.shape, dtype=first.dtype)
        else:
            # non-ndarray payload (e.g. a bytes ScalarCodec): object column,
            # with the already-decoded first element reused
            out = np.empty(n, dtype=object)
        out[0] = first
        for i in range(1, n):
            out[i] = decode(cells[i])
        return out
    # nulls present or wildcard shape: dense packing impossible
    out = np.empty(n, dtype=object)
    for i, cell in enumerate(cells):
        out[i] = None if cell is None else decode(cell)
    return out


def _list_column_to_numpy(column: pa.ChunkedArray, field) -> np.ndarray:
    """List column -> numpy. Fixed-shape numeric lists take the zero-Python
    path: flatten the arrow values buffer in C++ and reshape. Null-free 1-D
    wildcard numeric lists flatten the same way and come out as an object
    array of per-row views of the field's dtype into that one buffer."""
    shape = tuple(field.shape) if field.shape else ()
    fixed = shape and all(s is not None for s in shape)
    if (shape == (None,) and column.null_count == 0
            and field.numpy_dtype is not None
            and np.dtype(field.numpy_dtype).kind in 'biuf'):
        return _ragged_list_views(column, np.dtype(field.numpy_dtype))
    if fixed and column.null_count == 0:
        arr = column.combine_chunks()
        flat = arr.flatten().to_numpy(zero_copy_only=False)
        if field.numpy_dtype is not None:
            target = np.dtype(field.numpy_dtype)
            if flat.dtype != target and flat.dtype.kind in 'biuf':
                flat = flat.astype(target)
        expected = len(arr) * int(np.prod(shape))
        if flat.size == expected:
            return flat.reshape((len(arr),) + shape)
        # ragged data under a fixed-shape schema: fall through to python path
    rows = column.to_pylist()
    if fixed:
        return np.asarray(rows, dtype=field.numpy_dtype).reshape(
            (len(rows),) + shape)
    out = np.empty(len(rows), dtype=object)
    for i, r in enumerate(rows):
        out[i] = np.asarray(r)
    return out


def _ragged_list_views(column: pa.ChunkedArray, dtype) -> np.ndarray:
    """Object array of each row's values as a view into one flat ``dtype``
    array (a 1-D wildcard list column without nulls)."""
    arr = column.combine_chunks()
    flat = arr.flatten().to_numpy(zero_copy_only=False)
    if flat.dtype != dtype:
        flat = flat.astype(dtype)
    bounds = arr.offsets.to_numpy()
    bounds = bounds - bounds[0]
    out = np.empty(len(arr), dtype=object)
    for i in range(len(arr)):
        out[i] = flat[bounds[i]:bounds[i + 1]]
    return out


def _column_to_numpy(column: pa.ChunkedArray, field,
                     decode_override=None, on_cell_error=None,
                     batched=None, path_counts=None) -> np.ndarray:
    """Decoded numpy column for any unischema field. ``on_cell_error``
    enables tolerant codec decode (see :func:`_decode_binary_column`);
    vectorized scalar/list conversions cannot isolate cells and fail
    whole-column under every policy. ``batched``/``path_counts`` gate and
    observe the row-group-vectorized codec path; the default (``None``)
    consults the ``PETASTORM_TPU_BATCHED_DECODE`` switch per call, so
    every caller honors the kill switch — workers pass their
    construction-time read explicitly to keep the env lookup off the
    per-column hot path."""
    if batched is None:
        batched = batched_decode_enabled()
    if field.codec is not None and (
            pa.types.is_binary(column.type) or pa.types.is_large_binary(column.type)):
        return _decode_binary_column(column, field, decode_override,
                                     on_cell_error=on_cell_error,
                                     batched=batched,
                                     path_counts=path_counts)
    if pa.types.is_list(column.type) or pa.types.is_large_list(column.type):
        return _list_column_to_numpy(column, field)
    if pa.types.is_string(column.type) or pa.types.is_large_string(column.type):
        # one C++ conversion instead of a to_pylist -> np.asarray round
        # trip; both produce an object array of str with None at nulls
        return column.to_numpy(zero_copy_only=False)
    arr = column.to_numpy(zero_copy_only=False)
    if field.numpy_dtype is not None and not field.shape:
        try:
            target = np.dtype(field.numpy_dtype)
        except TypeError:
            return arr
        # null-bearing numeric columns stay NaN-holed floats (pandas/arrow
        # parity — the documented batched-path semantics); an astype to a
        # declared int dtype would mint garbage where the nulls were. The
        # row reader re-decodes such columns per cell with None preserved
        # (row_worker._load_columns).
        if (arr.dtype != target and arr.dtype.kind not in ('O', 'U', 'S')
                and not (column.null_count and target.kind in 'biu')):
            arr = arr.astype(target)
    return arr


def validate_predicate_fields(predicate, schema) -> list:
    """Predicate field names, validated against ``schema`` (the FULL stored
    schema — predicates may use fields outside the reader's output view)."""
    fields = list(predicate.get_fields())
    unknown = set(fields) - set(schema.fields.keys())
    if unknown:
        raise ValueError('Predicate uses unknown fields: {}'.format(
            sorted(unknown)))
    return fields


def make_partition_columns(schema, piece, n: int, names) -> Dict[str, np.ndarray]:
    """Synthesize hive-partition-derived columns (constant per piece) for the
    requested ``names``, typed per ``schema`` when the field is declared."""
    out = {}
    for key, value in piece.partition_dict.items():
        if key in names:
            field = schema.fields.get(key)
            typed = cast_partition_value(
                field.numpy_dtype if field is not None else None, value)
            if isinstance(typed, str):
                col = np.empty(n, dtype=object)
                col[:] = typed
            else:
                col = np.full(n, typed)
            out[key] = col
    return out


def _code_digest(code) -> str:
    """Digest of a code object covering bytecode AND constants (constants
    live in ``co_consts``, not ``co_code`` — editing ``x*2`` to ``x*3``
    changes only the former), recursing into nested code objects whose repr
    would otherwise embed unstable memory addresses."""
    parts = [code.co_code.hex()]
    for const in code.co_consts:
        if hasattr(const, 'co_code'):
            parts.append(_code_digest(const))
        else:
            parts.append(repr(const))
    return '|'.join(parts)


def _stable_value_digest(value) -> str:
    """Value identity that does not truncate: ndarrays hash their full bytes
    (``repr`` elides middle elements of large arrays, which would collide
    distinct normalization tables), recursing through list/tuple/dict
    containers so a captured ``[lut_array]`` is covered too; everything else
    uses repr."""
    if isinstance(value, np.ndarray):
        import hashlib
        h = hashlib.md5(np.ascontiguousarray(value).tobytes())
        return 'ndarray:{}:{}:{}'.format(value.dtype, value.shape,
                                         h.hexdigest())
    if isinstance(value, (list, tuple)):
        return '{}[{}]'.format(type(value).__name__,
                               ','.join(_stable_value_digest(v)
                                        for v in value))
    if isinstance(value, dict):
        return 'dict{{{}}}'.format(','.join(
            '{}:{}'.format(repr(k), _stable_value_digest(v))
            for k, v in sorted(value.items(), key=lambda kv: repr(kv[0]))))
    return repr(value)


def transform_fingerprint(spec) -> str:
    """Best-effort identity of a TransformSpec for cache keying: the func's
    qualified name + code (bytecode, constants, positional AND keyword-only
    defaults, closure values) + declared schema edits. Catches logic,
    constant, default-arg, and field-list edits; mutated closure OBJECTS
    whose repr doesn't change remain invisible (caveat — pass a fresh
    ``cache_location`` when parameterizing a transform through mutable
    closure state)."""
    import hashlib
    func = spec.func
    parts = []
    if func is not None:
        code = getattr(func, '__code__', None)
        kwdefaults = getattr(func, '__kwdefaults__', None) or {}
        parts.extend([getattr(func, '__module__', ''),
                      getattr(func, '__qualname__', repr(func)),
                      _code_digest(code) if code is not None else '',
                      '|'.join(_stable_value_digest(v) for v in
                               (getattr(func, '__defaults__', None) or ())),
                      '|'.join('{}={}'.format(k, _stable_value_digest(v))
                               for k, v in sorted(kwdefaults.items()))])
        closure = getattr(func, '__closure__', None) or ()
        parts.extend(_stable_value_digest(getattr(cell, 'cell_contents', None))
                     for cell in closure)
    parts.append(repr([(f.name, str(f.numpy_dtype), f.shape)
                       for f in (spec.edit_fields or [])]))
    parts.append(repr(sorted(spec.removed_fields or [])))
    parts.append(repr(sorted(spec.selected_fields or [])))
    return hashlib.md5('|'.join(parts).encode()).hexdigest()[:16]


def predicate_row_mask(predicate, fields, cols, n: int) -> np.ndarray:
    """Boolean include-mask from ``predicate`` over decoded columns.

    Predicates exposing a ``column_mask`` hook (e.g. the common
    single-field :class:`~petastorm_tpu.predicates.in_set` membership)
    evaluate in one vectorized numpy call; the hook returns ``None`` for
    column dtypes where numpy equality could diverge from Python's (object
    columns, NaN members), and generic predicates without the hook keep
    the per-row dict path."""
    column_mask = getattr(predicate, 'column_mask', None)
    if column_mask is not None:
        mask = column_mask(cols)
        if mask is not None:
            return np.asarray(mask, dtype=bool)
    return np.fromiter(
        (bool(predicate.do_include({f: cols[f][i] for f in fields}))
         for i in range(n)), dtype=bool, count=n)


class ColumnarWorker(ParquetPieceWorker):
    """Processes ventilated items into published dicts of decoded numpy
    column arrays."""

    #: The columnar publish path ships dicts of per-column arrays, so a
    #: device-planned column can travel as its raw ``(n, stride)`` uint8
    #: grid (docs/decode.md "Device-side decode"). Row/arrow-batch workers
    #: leave this unset and the reader's planner declines for them.
    supports_device_decode = True

    def __init__(self, worker_id, publish_func, args):
        super().__init__(worker_id, publish_func, args)
        # the spec is fixed for the worker's lifetime: fingerprint once, not
        # per row group per epoch
        self._transform_key = (
            transform_fingerprint(self._transform_spec)
            if self._transform_spec is not None else None)

    def process(self, piece_index: int, worker_predicate=None,
                shuffle_row_drop_partition=(0, 1), epoch=0):
        piece = self._split_pieces[piece_index]
        partition, num_partitions = shuffle_row_drop_partition
        self._begin_item(piece, piece_index, epoch, shuffle_row_drop_partition)
        try:
            if (worker_predicate is None and num_partitions == 1
                    and self._transform_spec is not None):
                # Cache POST-transform (the reference's batch-path semantics:
                # ``arrow_reader_worker.py:195-227`` applies the TransformSpec
                # inside the load the cache wraps): epochs 2+ skip BOTH codec
                # decode and the transform, and a shrinking transform (e.g.
                # image resize) shrinks the cache payload with it. The key
                # carries a best-effort transform fingerprint (code bytes +
                # schema edits) so editing the transform invalidates entries.
                cache_key = self._cache_key(
                    'columnar_tx:' + self._transform_key, piece)
                columns = self._cached_load(
                    cache_key, lambda: self._apply_transform(self._load(piece)))
                if columns and len(next(iter(columns.values()))):
                    n = len(next(iter(columns.values())))
                    # a transform may change the row count arbitrarily, so
                    # delivered rows cannot be mapped back to source offsets
                    self._publish_item(columns, ('opaque', n), n)
                else:
                    self._finish_item_empty()
                return
            if worker_predicate is not None:
                columns = self._load_with_predicate(piece, worker_predicate)
            else:
                cache_key = self._cache_key('columnar', piece)
                columns = self._cached_load(cache_key,
                                            lambda: self._load(piece))
        except Exception as e:  # noqa: BLE001 - policy decides
            if not self._quarantine_item('decode', e):
                raise
            return
        offsets = self._last_offsets
        if columns is None:
            self._finish_item_empty()
            return
        n = len(next(iter(columns.values()))) if columns else 0
        if not n:
            self._finish_item_empty()
            return
        if num_partitions > 1:
            bounds = np.linspace(0, n, num_partitions + 1, dtype=int)
            lo, hi = bounds[partition], bounds[partition + 1]
            columns = {k: v[lo:hi] for k, v in columns.items()}
            offsets = self._slice_offsets(offsets, lo, hi)
            if hi <= lo:
                self._finish_item_empty()
                return
            n = int(hi - lo)
        if self._transform_spec is not None:
            try:
                columns = self._apply_transform(columns)
            except Exception as e:  # noqa: BLE001 - policy decides
                if not self._quarantine_item('transform', e, rows=n):
                    raise
                return
            if not columns or not len(next(iter(columns.values()))):
                self._finish_item_empty()
                return
            post_n = len(next(iter(columns.values())))
            if post_n != n:
                offsets = None   # count-changing transform: opaque mapping
            n = post_n
        self._publish_item(columns, self._compact_selection(offsets, n), n)

    # -- loading ---------------------------------------------------------------

    # _decode_table comes from ParquetPieceWorker (shared with the row
    # worker's columnar window path)

    def _partition_columns(self, piece, n: int, names) -> Dict[str, np.ndarray]:
        return make_partition_columns(self._full_schema, piece, n, names)

    def _planned_columns(self, piece):
        # every no-predicate branch of process() funnels through _load()
        return self._stored_columns(list(self._schema.fields.keys()), piece)

    def _planned_cache_key(self, piece, params):
        # mirror process(): whole-group transform items cache post-transform
        partition = params.get('shuffle_row_drop_partition', (0, 1))
        if self._transform_spec is not None and partition[1] == 1:
            return self._cache_key('columnar_tx:' + self._transform_key,
                                   piece)
        return self._cache_key('columnar', piece)

    def _load(self, piece) -> Dict[str, np.ndarray]:
        names = list(self._schema.fields.keys())
        table = self._read_row_group(piece, self._stored_columns(names, piece))
        sink = self._decode_error_sink()
        columns = self._decode_table(table, names, error_sink=sink)
        n = table.num_rows
        offsets = self._range_offsets(n) if self._tracks_offsets else None
        if sink is not None and sink.errors:
            columns, kept = self._apply_quarantine_drops(columns, sink, n)
            offsets = kept
            n = len(kept)
        columns.update(self._partition_columns(piece, n, set(names)))
        self._last_offsets = offsets
        return columns

    def _load_with_predicate(self, piece, predicate) -> Optional[Dict[str, np.ndarray]]:
        """Decode predicate columns first; decode the remaining columns only at
        matching indices (cheaper than the row path, which decodes entire
        predicate rows eagerly)."""
        predicate_fields = validate_predicate_fields(predicate, self._full_schema)
        pred_table = self._read_row_group(
            piece, self._stored_columns(predicate_fields, piece))
        pred_cols = self._decode_table(pred_table, predicate_fields)
        pred_cols.update(self._partition_columns(
            piece, pred_table.num_rows, set(predicate_fields)))
        n = pred_table.num_rows
        mask = predicate_row_mask(predicate, predicate_fields, pred_cols, n)
        if not mask.any():
            return None
        idx = np.nonzero(mask)[0]
        out = {f: pred_cols[f][idx] for f in predicate_fields
               if f in self._schema.fields}
        other = [f for f in self._schema.fields if f not in set(predicate_fields)]
        other_stored = self._stored_columns(other, piece)
        if other_stored:
            rest = self._read_row_group(piece, other_stored)
            rest = rest.take(pa.array(idx))
            out.update(self._decode_table(rest, other_stored))
        out.update(self._partition_columns(piece, len(idx), set(other)))
        self._last_offsets = (idx.astype(np.int64)
                              if self._tracks_offsets else None)
        return out

    # -- transform -------------------------------------------------------------

    def _apply_transform(self, columns: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """TransformSpec over a dict of column arrays (the columnar-path
        contract; the row path hands ``func`` one row dict at a time, the arrow
        batch path a pandas frame). Timed as its own stage,
        ``worker_transform_s`` (a part of ``worker_decode_s``); a spec that
        ``reports_counts`` adds to this worker's counters."""
        from petastorm_tpu.transform import apply_columnar_transform
        start = time.perf_counter()
        out = apply_columnar_transform(self._transform_spec,
                                       self._transformed_schema, columns,
                                       self.record_count)
        elapsed = time.perf_counter() - start
        self.record_time('worker_transform_s', elapsed)
        self.record_latency('decode', elapsed)
        self.record_span('transform', 'decode', start, elapsed)
        return out
