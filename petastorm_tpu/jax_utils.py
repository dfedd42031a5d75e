"""JAX/TPU adapter: turn a Reader into an iterator of device-ready batches.

This replaces the reference's framework adapters (``petastorm/tf_utils.py``,
``petastorm/pytorch.py``) with a TPU-first design:

- Host side: rows/batches from the reader are sanitized to numpy, optionally
  shuffled in a (batched) shuffling buffer, and assembled into fixed-size
  column batches — all zero-copy where pyarrow/numpy allow.
- Device side: ``make_jax_loader(..., mesh=...)`` builds global
  ``jax.Array``s with ``jax.make_array_from_process_local_data`` over a
  GSPMD mesh (each TPU host feeds only its own shard — the multi-host story
  the reference delegated to Horovod env vars,
  ``spark_dataset_converter.py:122-159``), and ``prefetch_to_device``
  double-buffers host→HBM transfers so infeed overlaps compute (replacing
  the reference's ``tf.py_func``/queue infeed, ``tf_utils.py:202-252``).

Dtype policy (reference analogue ``tf_utils.py:27-44`` / ``pytorch.py:41-71``):
JAX handles the full unsigned/bool range natively, so no uint16/uint32
promotion is needed. Decimals are cast to float64; datetime64 to int64
nanoseconds; strings/objects stay host-only and are excluded from device
transfer unless the caller handles them.
"""

import collections
import logging
import os
import threading
import time
from decimal import Decimal

import numpy as np

from petastorm_tpu.goodput import GoodputMonitor, goodput_enabled
from petastorm_tpu.lineage import (LINEAGE_COLUMN, PACK_SHIFT, PROVENANCE_KEY,
                                   BatchProvenance, pack_rows)
from petastorm_tpu.readers.shuffling_buffer import (
    BatchedNoopShufflingBuffer, BatchedRandomShufflingBuffer,
    NoopShufflingBuffer, RandomShufflingBuffer)

logger = logging.getLogger(__name__)

_DEVICE_INCOMPATIBLE_KINDS = ('U', 'S', 'O')  # unicode, bytes, python objects


def _sanitize_value(value):
    """Make a single field value numpy-native and JAX-friendly."""
    if isinstance(value, Decimal):
        return np.float64(value)
    value = np.asarray(value)
    if value.dtype.kind == 'M':  # datetime64 -> int64 ns since epoch
        return value.astype('datetime64[ns]').astype(np.int64)
    if value.dtype.kind == 'O' and value.size and isinstance(value.flat[0], Decimal):
        return value.astype(np.float64)
    return value


def sanitize_jax_types(row_dict):
    """In-place dtype sanitization of a row/batch dict for JAX consumption."""
    for name, value in row_dict.items():
        row_dict[name] = _sanitize_value(value)
    return row_dict


def _is_device_compatible(arr):
    return getattr(arr, 'dtype', np.dtype(object)).kind not in _DEVICE_INCOMPATIBLE_KINDS


def _contiguous_rows_view(vals):
    """Zero-copy batch assembly: when ``vals`` are consecutive row views of
    one dense ``(n, *shape)`` decoded column (the unshuffled row-stream
    case — worker columns split into row dicts, consumed in order), the
    batch is a contiguous range of that column and one slice replaces the
    per-row ``np.stack`` memcpy. Returns ``None`` whenever that cannot be
    proven — shuffled rows, process-pool reconstructed rows, scalar or
    object cells — and the caller keeps the copying path. The slice shares
    the column's memory (and its writability): treat collated batches as
    read-only, as ``docs/decode.md`` documents."""
    if not len(vals):
        return None
    first = vals[0]
    base = first.base
    if base is None or not isinstance(base, np.ndarray) or first.ndim == 0:
        return None
    if (base.ndim != first.ndim + 1 or base.shape[1:] != first.shape
            or base.dtype != first.dtype
            or base.dtype.kind in _DEVICE_INCOMPATIBLE_KINDS):
        return None
    row_bytes = base.strides[0]
    if row_bytes <= 0 or first.strides != base.strides[1:]:
        return None
    base_ptr = base.__array_interface__['data'][0]
    ptr = first.__array_interface__['data'][0]
    start, rem = divmod(ptr - base_ptr, row_bytes)
    if rem or start < 0 or start + len(vals) > base.shape[0]:
        return None
    for v in vals[1:]:
        ptr += row_bytes
        if (v.base is not base or v.shape != first.shape
                or v.dtype != first.dtype or v.strides != first.strides
                or v.__array_interface__['data'][0] != ptr):
            return None
    return base[start:start + len(vals)]


#: Environment default for the device-prefetch window
#: (:func:`prefetch_to_device` / :func:`prefetch_batches` ``size`` and the
#: loaders' ``prefetch_depth`` knob). Unset means :data:`DEFAULT_PREFETCH_DEPTH`.
PREFETCH_DEPTH_ENV_VAR = 'PETASTORM_TPU_PREFETCH_DEPTH'

#: Double-buffering: stage batch N+1 while batch N computes. Depths beyond
#: 2-4 only pay off when step times are highly variable (docs/readahead.md).
DEFAULT_PREFETCH_DEPTH = 2


def resolve_prefetch_depth(depth):
    """Validated prefetch depth: the explicit knob wins, then
    :data:`PREFETCH_DEPTH_ENV_VAR`, then :data:`DEFAULT_PREFETCH_DEPTH`."""
    if depth is None:
        raw = os.environ.get(PREFETCH_DEPTH_ENV_VAR, '').strip()
        if not raw:
            return DEFAULT_PREFETCH_DEPTH
        depth = raw
    if isinstance(depth, float):
        # int() would silently truncate 2.5 -> 2; a fractional depth is a
        # caller bug worth surfacing
        raise ValueError('prefetch depth must be an integer >= 1, got {!r}'
                         .format(depth))
    try:
        depth = int(depth)
    except (TypeError, ValueError):
        raise ValueError('prefetch depth must be an integer >= 1, got {!r}'
                         .format(depth))
    if depth < 1:
        raise ValueError('prefetch depth must be >= 1, got {}'.format(depth))
    return depth


def validate_pad_spec(pad_spec):
    """Normalize/validate a ragged-padding spec at loader construction.

    ``pad_spec`` maps field name -> ``{'buckets': [n1, n2, ...]}`` or
    ``{'max_len': n}``, plus optional ``'pad_value'`` (default 0),
    ``'length_field'`` (default ``'<name>_len'``), ``'dtype'`` and
    ``'trailing_shape'``. The last two only matter for ZERO-row batches,
    where neither can be inferred from data; declaring them keeps empty
    batches dtype/rank-identical to non-empty ones (without them an empty
    batch falls back to ``pad_value``'s dtype and no trailing dims)."""
    if not pad_spec:
        return None
    normalized = {}
    for name, spec in pad_spec.items():
        spec = dict(spec)
        buckets = spec.pop('buckets', None)
        max_len = spec.pop('max_len', None)
        pad_value = spec.pop('pad_value', 0)
        length_field = spec.pop('length_field', name + '_len')
        dtype = spec.pop('dtype', None)
        trailing_shape = spec.pop('trailing_shape', ())
        if spec:
            raise ValueError('pad_spec for {!r} has unknown keys {}'.format(
                name, sorted(spec)))
        if (buckets is None) == (max_len is None):
            raise ValueError("pad_spec for {!r} needs exactly one of "
                             "'buckets' or 'max_len'".format(name))
        if buckets is None:
            buckets = [max_len]
        buckets = sorted(int(b) for b in buckets)
        if not buckets or buckets[0] <= 0:
            raise ValueError('pad_spec buckets for {!r} must be positive '
                             'ints, got {!r}'.format(name, buckets))
        normalized[name] = {'buckets': buckets, 'pad_value': pad_value,
                            'length_field': length_field,
                            'dtype': None if dtype is None else np.dtype(dtype),
                            'trailing_shape': tuple(trailing_shape)}
    return normalized


def check_pad_spec_fields(pad_spec, field_names, who: str) -> None:
    """Validate a NORMALIZED pad_spec against a schema's field names: every
    padded field must exist (a typo must fail, not silently no-op) and no
    ``length_field`` may collide with a real column
    (:func:`pad_ragged_batch` would silently overwrite its data). Shared by
    the streaming and indexed loaders."""
    if not pad_spec:
        return
    names = set(field_names)
    unknown = set(pad_spec) - names
    if unknown:
        raise ValueError('{}: pad_spec names unknown fields {} (schema has '
                         '{})'.format(who, sorted(unknown), sorted(names)))
    for name, spec in pad_spec.items():
        if spec['length_field'] in names:
            raise ValueError(
                "{}: pad_spec length_field {!r} for {!r} collides with an "
                'existing column; pick another via length_field='.format(
                    who, spec['length_field'], name))


def require_single_bucket_pad_spec(pad_spec, loader_name: str) -> None:
    """Sharded loaders pad each host's LOCAL sub-batch: with multiple
    buckets, hosts can disagree on the padded width of the same global step
    and ``make_array_from_process_local_data`` would assemble inconsistent
    global shapes (multi-host hang). Shared by the streaming and indexed
    sharded loaders."""
    if not pad_spec:
        return
    multi = {n for n, s in pad_spec.items() if len(s['buckets']) > 1}
    if multi:
        raise ValueError(
            "{} needs a single-bucket pad_spec (use 'max_len'); fields "
            'with multiple buckets: {}'.format(loader_name, sorted(multi)))


def pad_ragged_batch(batch, pad_spec):
    """Pad ragged (object-dtype) columns into dense bucketed arrays so
    variable-length fields can live in HBM under jit.

    For each spec'd field, rows are padded along their first dimension to the
    smallest bucket covering the batch's longest row, and the true lengths are
    emitted as an int32 ``length_field`` column (build masks from it on
    device). Bucketing bounds XLA recompilation to ``len(buckets)`` shapes —
    the pad-to-bucket answer to the static-shape-vs-ragged-fields problem
    (SURVEY §7 "hard parts"). Already-dense columns pass through with a
    constant length column for API uniformity."""
    out = dict(batch)
    for name, spec in pad_spec.items():
        col = out.get(name)
        if col is None:
            continue
        if not (isinstance(col, np.ndarray) and col.dtype == object):
            # Dense arrival (all rows equal length — always true at
            # batch_size=1) must STILL pad to a bucket, or every distinct
            # length is a fresh XLA compile and the bucket-width promise is
            # broken.
            col = np.asarray(col)
            if col.ndim < 2:
                raise ValueError('pad_spec field {!r} has scalar rows; '
                                 'padding needs at least one dimension'
                                 .format(name))
            width = col.shape[1]
            bucket = next((b for b in spec['buckets'] if b >= width), None)
            if bucket is None:
                raise ValueError(
                    'pad_spec field {!r}: row length {} exceeds largest '
                    'bucket {}'.format(name, width, spec['buckets'][-1]))
            if bucket != width:
                padded = np.full((len(col), bucket) + col.shape[2:],
                                 spec['pad_value'], dtype=col.dtype)
                padded[:, :width] = col
                col = padded
            out[name] = col
            out[spec['length_field']] = np.full(len(col), width, np.int32)
            continue
        rows = [np.asarray(v) for v in col]
        if not rows:
            # Empty batch: emit an empty dense column at the smallest bucket
            # so shapes stay bucket-stable even for zero-row batches. dtype
            # and trailing dims can't be inferred from zero rows — they come
            # from the spec's 'dtype'/'trailing_shape' declarations when
            # batch-shape stability across the empty case matters.
            bucket = spec['buckets'][0]
            dtype = spec['dtype']
            if dtype is None:
                dtype = np.asarray(spec['pad_value']).dtype
            shape = (0, bucket) + spec['trailing_shape']
            out[name] = np.empty(shape, dtype=dtype)
            out[spec['length_field']] = np.empty((0,), np.int32)
            continue
        if any(r.ndim < 1 for r in rows):
            raise ValueError('pad_spec field {!r} has scalar rows; padding '
                             'needs at least one dimension'.format(name))
        lengths = np.asarray([len(r) for r in rows], np.int32)
        longest = int(lengths.max())
        bucket = next((b for b in spec['buckets'] if b >= longest), None)
        if bucket is None:
            raise ValueError(
                'pad_spec field {!r}: row length {} exceeds largest bucket {}'
                .format(name, longest, spec['buckets'][-1]))
        first = rows[0]
        dense = np.full((len(rows), bucket) + first.shape[1:],
                        spec['pad_value'], dtype=first.dtype)
        for i, r in enumerate(rows):
            dense[i, :len(r)] = r
        out[name] = dense
        out[spec['length_field']] = lengths
    return out


class LoopBoundary(object):
    """The training loop's boundary with its input, timed on the thread
    that waits there: ``infeed_wait`` (the loop blocked for its next batch)
    and ``train_step`` (the loop's time between two batches).

    The one record site of that boundary, feeding every plane the loader
    has: ``ReaderStats`` (``infeed_wait_s`` and ``batches_out`` in one
    update), the tracer's spans, the latency stages and the
    :class:`~petastorm_tpu.goodput.GoodputMonitor`'s step. Iterating a
    loader directly records all of it in :class:`LoaderIterator`. Under
    :func:`prefetch_to_device` / :func:`prefetch_batches` the ring's
    consumer side reads the clock and makes the stats update (with tracing
    on, the spans too) on the loop's thread, and the producer thread folds
    the readings into the latency stages and the goodput step
    (:meth:`fold`) after each batch it queues: the loop pays two clock
    reads and one stats update per batch, and those planes lag it by a
    batch or two until the input ends."""

    __slots__ = ('stats', 'tracer', 'goodput', 'deferred', '_latency',
                 '_fetch_start', '_step_start', '_readings', '_fold_lock')

    def __init__(self, stats=None, tracer=None, goodput=None):
        self.stats = stats
        self.tracer = tracer
        self.goodput = goodput
        #: Set by a prefetcher, whose producer thread calls :meth:`fold`.
        self.deferred = False
        self._latency = getattr(stats, 'latency', None)
        self._fetch_start = 0.0
        self._step_start = None
        # (step start or None, fetch start, delivery or None, provenance)
        self._readings = collections.deque()
        self._fold_lock = threading.Lock()

    def waiting(self):
        """The loop asks for its next batch: its step in flight ends."""
        self._fetch_start = time.perf_counter()

    def delivered(self, batch, occupancy=None):
        """The loop got ``batch``: closes the step that ended when it
        asked, records the batch's wait (with a prefetcher's
        ``occupancy``, the batches left in its ring, in the same stats
        update) and opens the batch's step. Every record comes after both
        clock reads, so the wait holds none of them."""
        now = time.perf_counter()
        step_start, start = self._step_start, self._fetch_start
        self._step_start = now
        if self.stats is not None:
            self.stats.note_batch_out(now - start, occupancy)
        if self.tracer is not None:
            if step_start is not None:
                self.tracer.add_span('train_step', 'consumer', step_start,
                                     start - step_start)
            self.tracer.add_span('infeed_wait', 'consumer', start,
                                 now - start)
        if self._latency is None and self.goodput is None:
            return
        provenance = (batch.get('_provenance')
                      if self.goodput is not None and isinstance(batch, dict)
                      else None)
        self._readings.append((step_start, start, now, provenance))
        if not self.deferred:
            self.fold()

    def ended(self):
        """Closes the step that ended when the loop last asked for a batch
        (the last one, once the input runs out) and folds every reading."""
        step_start = self._step_start
        if step_start is not None:
            self._step_start = None
            end = self._fetch_start
            if self.tracer is not None:
                self.tracer.add_span('train_step', 'consumer', step_start,
                                     end - step_start)
            if self._latency is not None or self.goodput is not None:
                self._readings.append((step_start, end, None, None))
        self.fold()

    def fold(self):
        """Folds the readings taken so far into the latency stages and the
        goodput step, in the order they were taken."""
        readings, latency, goodput = self._readings, self._latency, \
            self.goodput
        with self._fold_lock:
            while readings:
                step_start, start, now, provenance = readings.popleft()
                if step_start is not None:
                    if latency is not None:
                        latency.record('train_step', start - step_start)
                    if goodput is not None:
                        goodput.finish_step(start - step_start,
                                            ended_at=start)
                if now is None:
                    continue
                if latency is not None:
                    latency.record('infeed_wait', now - start)
                if goodput is not None:
                    goodput.note_fetch(now - start, fetched_at=now,
                                       provenance=provenance)


class LoaderIterator(object):
    """What iterating a loader returns: its batches, with the loop's
    :class:`LoopBoundary` recorded on the thread that calls ``next``. A
    prefetcher handed this iterator takes the boundary over
    (:meth:`take_boundary`) before its producer thread starts drawing."""

    __slots__ = ('loader', '_batches', '_boundary')

    def __init__(self, loader):
        self.loader = loader
        self._batches = loader._generate()
        planes = (loader.stats, loader.tracer, loader.goodput)
        self._boundary = (LoopBoundary(*planes)
                          if any(p is not None for p in planes) else None)

    def __iter__(self):
        return self

    def __next__(self):
        boundary = self._boundary
        if boundary is None:
            return next(self._batches)
        boundary.waiting()
        try:
            batch = next(self._batches)
        except StopIteration:
            boundary.ended()
            raise
        boundary.delivered(batch)
        return batch

    def take_boundary(self):
        """Hands the boundary to the caller; this iterator records no more."""
        boundary, self._boundary = self._boundary, None
        return boundary

    def close(self):
        self._batches.close()


class JaxLoaderBase(object):
    """Iteration-state guard + auto-reset, mirroring the reference's
    ``LoaderBase`` (``pytorch.py:104-129``)."""

    def __init__(self, reader):
        self.reader = reader
        self._in_iter = None
        self._error = None
        #: The reader pool's :class:`~petastorm_tpu.tracing.Tracer` (None
        #: when tracing is off). The training loop's :class:`LoopBoundary`
        #: records ``infeed_wait`` (the loop blocked for its next batch) and
        #: ``train_step`` (the loop's gap between batches) spans into it, so
        #: the device-idle gap is visible on the same timeline as the worker
        #: stages.
        self.tracer = getattr(reader, 'tracer', None)
        #: The reader's :class:`~petastorm_tpu.health.HealthMonitor` (None
        #: for readers without one). A prefetcher handed the loader takes it,
        #: so the prefetch thread heartbeats onto the same watchdog as the
        #: rest of the pipeline.
        self.health = getattr(reader, 'health', None)
        #: The reader pool's ``ReaderStats`` (None for readers without one).
        #: The loop boundary sums ``infeed_wait_s`` and counts
        #: ``batches_out`` into it, and with its latency plane on records
        #: ``infeed_wait``/``train_step`` duration histograms even with
        #: tracing off — tail latencies must not require a span ring.
        self.stats = getattr(reader, 'stats', None)
        #: Background lookahead window for :meth:`iter_prefetched`; subclass
        #: constructors overwrite it from their ``prefetch_depth`` knob.
        self.prefetch_depth = resolve_prefetch_depth(None)
        #: Per-step goodput accounting
        #: (:class:`~petastorm_tpu.goodput.GoodputMonitor`, None under
        #: ``PETASTORM_TPU_GOODPUT=0``). The loop boundary feeds it every
        #: step's ``infeed_wait``/train wall; the staging helpers feed it the
        #: H2D dispatch time. Call ``loader.goodput.fence(outputs)`` inside
        #: the step for the exact device/host split (docs/goodput.md).
        self.goodput = (GoodputMonitor(stats=self.stats, tracer=self.tracer)
                        if goodput_enabled() else None)
        register = getattr(reader, 'register_goodput', None)
        if register is not None and self.goodput is not None:
            register(self.goodput)

    def iter_prefetched(self, sharding=None, to_device=True):
        """Iterate with a background lookahead of ``self.prefetch_depth``
        batches: :func:`prefetch_to_device` when ``to_device`` (explicit
        per-batch ``jax.device_put``, overlapping the H2D DMA with compute),
        else :func:`prefetch_batches` (host lookahead; the jitted step's own
        call transfers). The depth is a loader knob — set it at construction
        (``prefetch_depth=``), via ``PETASTORM_TPU_PREFETCH_DEPTH``, or by
        assigning ``loader.prefetch_depth`` before calling this
        (docs/readahead.md documents who owns the knob)."""
        if to_device:
            return prefetch_to_device(self, self.prefetch_depth,
                                      sharding=sharding)
        return prefetch_batches(self, self.prefetch_depth)

    def __iter__(self):
        return LoaderIterator(self)

    def _generate(self):
        if self._error is not None:
            raise RuntimeError('Cannot start a new iteration after a failed one') \
                from self._error
        if self._in_iter is not None and self._in_iter:
            raise RuntimeError('Loader is already being iterated')
        if self._in_iter is not None and not self._cache_hot():
            self.reader.reset()
            logger.warning('Start a new pass of the Reader. To avoid I/O, consider '
                           'in-memory caching (inmemory_cache_all=True).')
        self._in_iter = True
        try:
            for batch in self._iter_impl():
                yield batch
        except Exception as e:
            self._error = e
            raise
        finally:
            self._in_iter = False

    def _iter_impl(self):
        raise NotImplementedError

    def _cache_hot(self):
        """True when replay epochs are served from an in-memory cache and the
        underlying reader need not be reset."""
        return False

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()
        self.join()

    def stop(self):
        self.reader.stop()

    def join(self):
        self.reader.join()


class JaxDataLoader(JaxLoaderBase):
    """Yields dicts of numpy column batches of exactly ``batch_size`` rows
    (last partial batch dropped when ``drop_last``, else yielded short).

    Works with both row-granular readers (``make_reader``) and batched readers
    (``make_batch_reader``); batched input is fed column-wise into vectorized
    buffers, never exploded into python rows (the perf trap the reference's
    plain ``DataLoader`` falls into and ``BatchedDataLoader`` fixes,
    ``pytorch.py:204-216`` vs ``:352-408``). NGram readers batch through
    per-timestep collation: a batch is ``{offset: {field: (B, ...) array}}``
    and windows shuffle as whole units (reference ngram batching lives only
    in the TF adapter, ``tf_utils.py:141-183``).

    :param shuffling_queue_capacity: 0 disables shuffling; otherwise a
        uniform-shuffling buffer of that many rows decorrelates row-group order.
    :param transform_fn: optional callable applied to each finished batch dict.
    :param inmemory_cache_all: cache epoch-1 batches and replay them for
        subsequent epochs without touching the reader (reference
        ``pytorch.py:292-321``).
    """

    def __init__(self, reader, batch_size=1, shuffling_queue_capacity=0,
                 transform_fn=None, drop_last=False, seed=None,
                 inmemory_cache_all=False, pad_spec=None, device_decode=True,
                 prefetch_depth=None):
        super(JaxDataLoader, self).__init__(reader)
        # NGram rows are {offset: namedtuple} windows; they batch through
        # per-timestep collation into {offset: dict-of-column-arrays} —
        # mirroring the TF adapter's ngram path (reference
        # ``tf_utils.py:141-183``; the reference torch loader refuses ngram,
        # ``pytorch.py:150-152``).
        self._ngram = getattr(reader, 'ngram', None)
        if self._ngram is not None and pad_spec:
            raise ValueError('pad_spec is not supported with NGram readers '
                             '(window fields are fixed-shape per timestep)')
        self.batch_size = batch_size
        self.shuffling_queue_capacity = shuffling_queue_capacity
        self.transform_fn = transform_fn
        self.drop_last = drop_last
        self.seed = seed
        self.inmemory_cache_all = inmemory_cache_all
        self.pad_spec = validate_pad_spec(pad_spec)
        if self.pad_spec:
            schema_fields = getattr(getattr(reader, 'schema', None), 'fields', None)
            if schema_fields is not None:
                check_pad_spec_fields(self.pad_spec, schema_fields,
                                      'JaxDataLoader')
        self._cache = [] if inmemory_cache_all else None
        self._cache_complete = False
        #: The reader's :class:`~petastorm_tpu.lineage.LineageTracker`. When
        #: lineage is on, every batch rides a packed int64 source column
        #: through the shuffling buffer and finished batches expose
        #: ``batch['_provenance']`` (a
        #: :class:`~petastorm_tpu.lineage.BatchProvenance`). NGram batches
        #: carry no per-row column (windows span source rows); use
        #: ``reader.explain_batch()`` at item granularity there.
        self._lineage = getattr(reader, 'lineage', None)
        self._lineage_on = (self._ngram is None
                            and getattr(self._lineage, 'enabled', False))
        #: The reader pool's ReaderStats (None for readers without one):
        #: the loader gauges shuffle-buffer occupancy into it, and the
        #: device-staging helpers time ``jax.device_put`` against it.
        self.stats = getattr(reader, 'stats', None)
        #: End-to-end batch latency (ventilate → finished batch): recorded
        #: here — the LAST delivery point — via the packed lineage sources,
        #: so the reader's own per-item e2e recording defers to the loader
        #: (one observation per delivered unit, never double-counted).
        self._e2e_on = (self._lineage_on
                        and getattr(self.stats, 'latency', None) is not None)
        if self._e2e_on:
            defer = getattr(reader, '_defer_e2e_to_loader', None)
            if defer is not None:
                defer()
        #: Depth of the :func:`prefetch_to_device` / :func:`prefetch_batches`
        #: window :meth:`iter_prefetched` uses (docs/readahead.md knob note).
        self.prefetch_depth = resolve_prefetch_depth(prefetch_depth)
        # -- device-side decode (docs/decode.md "Device-side decode") ----------
        #: name -> DeviceColumnPlan claimed from a bytes-through reader; the
        #: loader decodes these raw (n, stride) uint8 columns under jax.jit
        #: at batch delivery (fused with any device-flagged TransformSpec).
        #: ``device_decode=False`` leaves the claim to an outer component
        #: (ShardedJaxLoader decodes post-staging on the global arrays).
        self._device_plans = {}
        self._device_transform_spec = None
        self._device_fused_fn = None
        if device_decode:
            claim = getattr(reader, '_defer_device_decode_to_loader', None)
            if claim is not None and getattr(reader, 'device_decode_plans',
                                             None):
                self._device_plans, self._device_transform_spec = claim()

    def _decode_on_device(self, batch):
        """Run the jitted decode (+ fused device ``TransformSpec``) over a
        bytes-through batch's device-compatible columns; host-only values
        merge back untouched."""
        from petastorm_tpu.ops.decode import (build_fused_infeed,
                                              split_device_columns)
        if self._device_fused_fn is None:
            self._device_fused_fn = build_fused_infeed(
                self._device_plans, self._device_transform_spec)
        device_cols, host_cols = split_device_columns(
            batch, self._device_plans,
            include_unplanned=self._device_transform_spec is not None)
        out = dict(self._device_fused_fn(device_cols))
        out.update(host_cols)
        planned = [n for n in self._device_plans if n in device_cols]
        if planned and self.stats is not None:
            rows = int(device_cols[planned[0]].shape[0])
            self.stats.add('rows_decoded_device', rows * len(planned))
        return out

    def _cache_hot(self):
        return self._cache_complete

    # -- buffer construction -------------------------------------------------
    def _make_batched_buffer(self):
        if self.shuffling_queue_capacity > 0:
            min_after = max(1, self.shuffling_queue_capacity - self.batch_size)
            return BatchedRandomShufflingBuffer(
                self.shuffling_queue_capacity + self.batch_size,
                min_after_retrieve=min_after, batch_size=self.batch_size,
                seed=self.seed)
        return BatchedNoopShufflingBuffer(self.batch_size)

    def _iter_impl(self):
        if self._cache_complete:
            for batch in self._cache:
                yield batch
            return
        if self._cache is not None:
            # A prior abandoned iteration may have left partial batches.
            self._cache = []
        if self.reader.batched_output:
            gen = self._iter_batched()
        elif self._ngram is not None:
            if getattr(self.reader, 'ngram_chunked', False):
                gen = self._iter_ngram_chunked()
            else:
                gen = self._iter_ngram()
        else:
            gen = self._iter_rows()
        for batch in gen:
            # the packed source column must never reach user transforms or
            # the model: pop it here, re-attach as the provenance object
            sources = (batch.pop(LINEAGE_COLUMN, None)
                       if self._lineage_on and isinstance(batch, dict)
                       else None)
            if self._device_plans and isinstance(batch, dict):
                # decode raw planned columns (and run the fused device
                # TransformSpec) as ONE jitted program, before any host
                # pad/transform sees the batch
                batch = self._decode_on_device(batch)
            if self.pad_spec:
                batch = pad_ragged_batch(batch, self.pad_spec)
            if self.transform_fn is not None:
                batch = self.transform_fn(batch)
            if sources is not None and isinstance(batch, dict):
                batch[PROVENANCE_KEY] = BatchProvenance(sources, self._lineage)
                if self._e2e_on and len(sources):
                    # ventilate timestamp of the batch's oldest source item
                    # → now: the end-to-end latency of this delivery,
                    # correlated through the lineage seqs the provenance
                    # column already carries. The smallest seq IS the
                    # earliest-registered item (one min, no unique/sort on
                    # the per-batch path).
                    ts = self._lineage.ventilated_ts(
                        int(np.asarray(sources).min()) >> PACK_SHIFT)
                    if ts is not None:
                        self.stats.record_latency(
                            'e2e_batch', time.perf_counter() - ts)
            if self._cache is not None:
                self._cache.append(batch)
            yield batch
        if self._cache is not None:
            self._cache_complete = True

    def _drive_batched_buffer(self, column_stream, post=None):
        """Shared batched-buffer loop: feed column dicts, drain fixed-size
        batches, honor ``drop_last`` on the tail. ``post`` maps each
        retrieved batch (the chunked NGram path unflattens its keys)."""
        post = post or (lambda b: b)
        buffer = self._make_batched_buffer()
        stats = self.stats
        for columns in column_stream:
            while not buffer.can_add():
                yield post(buffer.retrieve())
            buffer.add_many(columns)
            if stats is not None:
                stats.gauge('shuffle_buffer_depth', buffer.size)
            while buffer.can_retrieve() and buffer.size >= self.batch_size:
                yield post(buffer.retrieve())
        buffer.finish()
        while buffer.can_retrieve():
            batch = buffer.retrieve()
            n = len(next(iter(batch.values())))
            if n == self.batch_size or not self.drop_last:
                yield post(batch)

    def _iter_batched(self):
        lineage_on = self._lineage_on
        reader = self.reader

        def columns():
            for chunk in reader:
                cols = sanitize_jax_types(
                    chunk._asdict() if hasattr(chunk, '_asdict') else dict(chunk))
                if lineage_on:
                    seq = reader.last_seq
                    n = len(next(iter(cols.values()))) if cols else 0
                    if seq is not None and n:
                        # one vectorized int64 column per chunk: the rows'
                        # packed source ids survive shuffling/batching
                        cols[LINEAGE_COLUMN] = pack_rows(seq, n)
                yield cols
        return self._drive_batched_buffer(columns())

    def _iter_rows(self):
        # per-ROW hook: read the results reader's plain attributes directly
        # and pack inline — property indirection per row is measurable on
        # small-row-group stores
        results_reader = getattr(self.reader, '_results_reader', None)
        lineage_on = self._lineage_on and results_reader is not None

        def prepare(row):
            d = sanitize_jax_types(row._asdict()
                                   if hasattr(row, '_asdict') else dict(row))
            if lineage_on:
                seq = results_reader.last_seq
                offset = results_reader.last_row_offset
                if seq is not None and offset is not None:
                    d[LINEAGE_COLUMN] = (seq << PACK_SHIFT) | offset
            return d
        return self._iter_row_stream(prepare, self._collate)

    def _iter_ngram_chunked(self):
        """Vectorized NGram batching: whole columnar window chunks
        (``Reader.iter_ngram_chunks``) collate with one fancy-index per
        (offset, field) per chunk and batch through the BATCHED buffers under
        flattened ``(offset, field)`` keys — zero per-window Python, the
        consumer-side twin of the worker's columnar window path. Windows
        still shuffle as whole units: the batched buffer permutes rows (=
        windows) with one permutation across all columns, so timestep
        alignment survives. Yields the same ``{offset: {field: (B, ...)}}``
        layout as :meth:`_iter_ngram`."""
        offsets, base, fields_at = self._ngram.timestep_layout(
            self.reader.schema.fields)

        def take_rows(col, pos):
            # windows over a gap-free row range index consecutive rows:
            # slice the decoded column zero-copy instead of a fancy-index
            # gather (contiguous-slice batch assembly, docs/decode.md)
            if (len(pos) and int(pos[-1]) - int(pos[0]) == len(pos) - 1
                    and bool(np.all(np.diff(pos) == 1))):
                lo = int(pos[0])
                return col[lo:lo + len(pos)]
            return col[pos]

        def collate_chunks():
            for chunk in self.reader.iter_ngram_chunks():
                flat = {}
                for off in offsets:
                    pos = chunk.starts + (off - base)
                    for name in fields_at[off]:
                        col = chunk.columns.get(name)
                        if col is not None:
                            flat[(off, name)] = _sanitize_value(
                                take_rows(col, pos))
                yield flat

        def unflatten(batch):
            out = {}
            for (off, name), col in batch.items():
                out.setdefault(off, {})[name] = col
            return out

        return self._drive_batched_buffer(collate_chunks(), post=unflatten)

    def _iter_ngram(self):
        """NGram windows ({offset: namedtuple}) → per-timestep collated
        batches: ``{offset: {field: (B, ...) array}}`` — windows shuffle as
        whole units so timestep alignment survives the buffer."""
        def collate(windows):
            out = {}
            for offset in sorted(windows[0].keys()):
                rows = [sanitize_jax_types(dict(w[offset]._asdict()))
                        for w in windows]
                out[offset] = self._collate(rows)
            return out
        return self._iter_row_stream(lambda w: w, collate)

    def _iter_row_stream(self, prepare, collate):
        """Shared row-granular loop: shuffle buffer → fixed-size batches."""
        if self.shuffling_queue_capacity > 0:
            min_after = max(1, self.shuffling_queue_capacity - 1)
            buffer = RandomShufflingBuffer(
                self.shuffling_queue_capacity, min_after_retrieve=min_after,
                seed=self.seed)
        else:
            buffer = NoopShufflingBuffer()
        pending = []

        def drain(final):
            rows = pending
            while buffer.can_retrieve():
                rows.append(buffer.retrieve())
                if len(rows) == self.batch_size:
                    yield collate(rows)
                    rows.clear()
            if final and rows and not self.drop_last:
                yield collate(rows)

        stats = self.stats
        row_count = 0
        for row in self.reader:
            row = prepare(row)
            while not buffer.can_add():
                for b in drain(False):
                    yield b
                if not buffer.can_retrieve():
                    break
            buffer.add_many([row])
            # sample the gauge sparsely: a lock acquire per row would tax the
            # very hot path this telemetry exists to diagnose
            row_count += 1
            if stats is not None and row_count % 64 == 1:
                stats.gauge('shuffle_buffer_depth', buffer.size)
            for b in drain(False):
                yield b
        buffer.finish()
        for b in drain(True):
            yield b

    @staticmethod
    def _collate(rows):
        keys = rows[0].keys()
        out = {}
        for k in keys:
            if k == LINEAGE_COLUMN:
                # packed int sources: one fromiter, no per-row asarray
                out[k] = np.fromiter((r[k] for r in rows), dtype=np.int64,
                                     count=len(rows))
                continue
            vals = [np.asarray(r[k]) for r in rows]
            contiguous = _contiguous_rows_view(vals)
            if contiguous is not None:
                # the batch IS a contiguous range of one decoded column:
                # emit the zero-copy slice instead of re-collating rows
                # (docs/decode.md "contiguous-slice batch assembly")
                out[k] = contiguous
                continue
            shapes = {v.shape for v in vals}
            kinds = {v.dtype.kind for v in vals}
            if len(shapes) == 1 and not (kinds & set(_DEVICE_INCOMPATIBLE_KINDS)):
                out[k] = np.stack(vals)
            else:
                # Ragged (shape=(None,...)) or string/object fields cannot form a
                # dense device batch; keep them as a host-side object column.
                col = np.empty(len(vals), dtype=object)
                for i, v in enumerate(vals):
                    col[i] = v
                out[k] = col
        return out


class ShardedJaxLoader(JaxLoaderBase):
    """Wraps a ``JaxDataLoader`` and lifts each host-local numpy batch into a
    **global** ``jax.Array`` sharded over ``mesh`` along ``batch_axis``.

    Under multi-host TPU each process constructs only its local shard
    (``local_batch_size = global_batch_size // process_count``) and XLA sees one
    logical array — the idiomatic replacement for the reference's static
    rank/size shard arithmetic. ``drop_last`` is forced True (no ragged
    batches), and under ``process_count > 1`` every step is preceded by a
    cross-host readiness allgather so all hosts yield exactly the same number
    of steps even when row-group sharding is unbalanced — a host with a
    surplus batch drops it instead of deadlocking the others' collectives
    (SURVEY §7 "hard parts").

    String/object columns cannot live in HBM; they are returned under
    ``batch['_host']`` untouched.

    Bytes-through readers: this loader claims the device-decode plans and
    decodes POST-staging (jitted over the global sharded arrays) — but only
    when no host stage needs the decoded values first. With a
    ``transform_fn`` (or a ``pad_spec`` naming a planned column) the claim
    is declined and the reader host-decodes, so the transform always
    receives decoded numpy columns; fuse device-side work through a
    ``TransformSpec(device=True)`` on the reader instead.

    NGram readers are supported: each step yields the nested
    ``{offset: {field: global jax.Array}}`` layout, every timestep's columns
    sharded over ``batch_axis`` at WINDOW granularity (``local_batch_size``
    windows per process), with the same lockstep-stop protocol.
    """

    def __init__(self, reader, mesh, local_batch_size, batch_axis='data',
                 shuffling_queue_capacity=0, transform_fn=None, seed=None,
                 inmemory_cache_all=False, pad_spec=None, prefetch_depth=None):
        super(ShardedJaxLoader, self).__init__(reader)
        from jax.sharding import NamedSharding, PartitionSpec
        # NGram batches are nested {offset: {field: array}}; each timestep's
        # columns stage into global arrays per offset (window batches shard
        # over the batch axis exactly like row batches)
        self._ngram = getattr(reader, 'ngram', None)
        self.mesh = mesh
        self.batch_axis = batch_axis
        normalized_pad_spec = validate_pad_spec(pad_spec)
        require_single_bucket_pad_spec(normalized_pad_spec,
                                       'ShardedJaxLoader')
        # device_decode=False: the inner loader must NOT decode the raw
        # bytes-through columns pre-staging — this loader claims them below
        # and decodes post-staging, jitted over the GLOBAL sharded arrays,
        # so decode work shards along the batch axis with the data
        self._loader = JaxDataLoader(
            reader, batch_size=local_batch_size,
            shuffling_queue_capacity=shuffling_queue_capacity,
            transform_fn=transform_fn, drop_last=True, seed=seed,
            inmemory_cache_all=inmemory_cache_all, pad_spec=pad_spec,
            device_decode=False, prefetch_depth=prefetch_depth)
        self._pspec = PartitionSpec(batch_axis)
        self._named_sharding = NamedSharding(mesh, self._pspec)
        self.stats = self._loader.stats
        self.prefetch_depth = self._loader.prefetch_depth
        if self.goodput is not None:
            # This loader drives the inner loader's _iter_impl directly,
            # bypassing its loop boundary — the OUTER monitor is the
            # live one. Share it (the staging sites below feed it) and
            # re-register it over the inner loader's dormant registration.
            self._loader.goodput = self.goodput
            register = getattr(reader, 'register_goodput', None)
            if register is not None:
                register(self.goodput)
        # -- device-side decode (docs/decode.md "Device-side decode") ----------
        # This loader decodes POST-staging (jitted over the global sharded
        # arrays), so the inner loader's pad/transform stages would see the
        # raw (n, stride) uint8 grids. A host transform_fn (or a pad_spec
        # over a planned column) needs decoded host values BEFORE staging —
        # in that case decline the claim and let the reader host-decode,
        # keeping the transform's decoded-numpy contract (a device=True
        # TransformSpec still fuses into the jitted decode).
        self._device_plans = {}
        self._device_fused_fn = None
        claim = getattr(reader, '_defer_device_decode_to_loader', None)
        available_plans = getattr(reader, 'device_decode_plans', None)
        if claim is not None and available_plans:
            padded_planned = sorted(set(normalized_pad_spec or {})
                                    & set(available_plans))
            if transform_fn is not None:
                logger.info(
                    'ShardedJaxLoader: transform_fn needs decoded host '
                    'columns; declining the bytes-through claim (the reader '
                    'host-decodes). Use a TransformSpec(device=True) on the '
                    'reader to keep decode on the accelerator.')
            elif padded_planned:
                logger.info(
                    'ShardedJaxLoader: pad_spec names device-planned '
                    'columns %s which pad before staging; declining the '
                    'bytes-through claim (the reader host-decodes).',
                    padded_planned)
            else:
                plans, device_spec = claim()
                if plans:
                    from petastorm_tpu.ops.decode import build_fused_infeed
                    self._device_plans = plans
                    self._device_fused_fn = build_fused_infeed(plans,
                                                               device_spec)

    def _cache_hot(self):
        return self._loader._cache_hot()

    def _iter_impl(self):
        import jax
        lockstep = jax.process_count() > 1
        it = self._loader._iter_impl()
        while True:
            batch = next(it, None)
            if lockstep:
                # Cross-host agreement before every step: row-group sharding
                # can hand one host a batch more than another (9 row groups
                # over 2 hosts), and a host entering a collective the others
                # never reach deadlocks the cluster. All hosts stop together
                # at the shortest host's stream; a surplus local batch is
                # dropped (the multi-host extension of drop_last).
                if not _all_processes_ready(batch is not None):
                    # Drain the surplus before stopping: abandoning the
                    # stream mid-epoch would leave the Reader unfinished
                    # (reset() would refuse), breaking the NEXT pass on this
                    # host only. With the epoch cache on, the inner generator
                    # must run to completion (the cache replays these
                    # batches); otherwise discard raw pool results without
                    # decoding/collating them (heavily unbalanced shards
                    # would pay full window/batch assembly for data nobody
                    # reads).
                    drain = getattr(self.reader, 'drain', None)
                    if self._loader.inmemory_cache_all or drain is None:
                        for _ in it:
                            pass
                    else:
                        drain()
                    return
            elif batch is None:
                return
            stats = self._loader.stats
            tracer = self.tracer
            goodput = self.goodput
            if self._ngram is not None:
                yield {off: stage_to_global(cols, self._named_sharding,
                                            stats=stats, tracer=tracer,
                                            goodput=goodput)
                       for off, cols in batch.items()}
            else:
                if self._device_plans and stats is not None:
                    planned = [n for n in self._device_plans if n in batch]
                    if planned:
                        stats.add('rows_decoded_device',
                                  int(batch[planned[0]].shape[0])
                                  * len(planned))
                yield stage_to_global(batch, self._named_sharding, stats=stats,
                                      tracer=tracer,
                                      fused_fn=self._device_fused_fn,
                                      goodput=goodput)


def _all_processes_ready(local_ready: bool) -> bool:
    """True iff EVERY process has a next batch. One tiny allgather per step —
    the price of streaming readers not knowing their row count up front."""
    import jax
    import numpy as np
    from jax.experimental import multihost_utils
    flags = multihost_utils.process_allgather(
        np.asarray([1 if local_ready else 0], np.int32))
    return bool(np.asarray(flags).min())


def stage_to_global(batch, named_sharding, stats=None, tracer=None,
                    fused_fn=None, goodput=None):
    """Assemble a host batch dict into global ``jax.Array``s over
    ``named_sharding``; device-incompatible (string/object) columns ride
    under ``batch['_host']`` untouched — the single definition of the
    'what can live in HBM' split. ``stats`` (a ``ReaderStats``) accumulates
    the assembly wall time as ``device_stage_s``; ``tracer`` (a
    :class:`~petastorm_tpu.tracing.Tracer`) records it as a ``device_stage``
    span. ``fused_fn`` (an ``ops.decode.build_fused_infeed`` program) runs
    over the assembled device dict — bytes-through decode plus any device
    ``TransformSpec``, jitted over the GLOBAL sharded arrays so the work
    shards along the batch axis with the data. ``goodput`` (a
    :class:`~petastorm_tpu.goodput.GoodputMonitor`) attributes the same
    wall time to the current step's ``h2d_stage`` leg."""
    import jax
    timed = stats is not None or tracer is not None or goodput is not None
    start = time.perf_counter() if timed else 0.0
    device, host = {}, {}
    for name, value in batch.items():
        if name == PROVENANCE_KEY:
            # under '_host' with the other non-HBM values: every top-level
            # entry except '_host' stays a jax.Array, so a staged batch can
            # still be passed whole into jit
            host[name] = value
        elif _is_device_compatible(value):
            device[name] = jax.make_array_from_process_local_data(
                named_sharding, value)
        else:
            host[name] = value
    if fused_fn is not None and device:
        device = dict(fused_fn(device))
    if host:
        device['_host'] = host
    if timed:
        _record_stage(start, stats, tracer, goodput)
    return device


def _record_stage(start, stats, tracer, goodput):
    """The one record site of a staged batch, for both staging helpers:
    the seconds since ``start`` as ``device_stage_s``, a ``device_stage``
    latency observation and span, and the goodput step's ``h2d_stage``
    leg."""
    elapsed = time.perf_counter() - start
    if stats is not None:
        stats.add_time('device_stage_s', elapsed)
        stats.record_latency('device_stage', elapsed)
    if tracer is not None:
        tracer.add_span('device_stage', 'device', start, elapsed)
    if goodput is not None:
        goodput.note_stage(elapsed)


def infeed_diagnosis(snapshot: dict, heartbeats=None,
                     stall_after_s=None, roofline=None, latency=None,
                     slo=None) -> dict:
    """Classify an infeed pipeline from a ``ReaderStats`` snapshot
    (``reader.diagnostics`` / ``loader.stats.snapshot()``) and recommend the
    knobs that attack its bottleneck.

    The signatures (see ``docs/troubleshooting.md``):

    - **io-bound** — storage stall dominates decode: raise ``io_readahead``
      (overlap reads with decode) before raising ``workers_count``.
    - **decode-bound** — decode dominates and reads are already hidden:
      ``io_readahead`` cannot help; raise ``workers_count`` / move decode
      work (decode_hints, transforms) instead.
    - **consumer-bound** — workers outrun the consumer (large
      ``worker_publish_wait_s``): the training step, not the reader, is the
      ceiling.

    ``heartbeats`` (``reader.health.heartbeats()``) optionally folds the
    live health layer into the verdict: the returned dict gains
    ``pipeline_state`` (healthy/degraded/stalled/starving) and
    ``stalled_entities``, and a stalled entity overrides ``bottleneck`` with
    ``'stalled'`` — the same :func:`petastorm_tpu.health.classify_pipeline`
    call the watchdog and ``/healthz`` make, so the CLI's ``-d`` output and
    the debug endpoint can never disagree. ``stall_after_s`` defaults to
    :data:`petastorm_tpu.health.DEFAULT_STALL_AFTER_S`.

    ``roofline`` (a :meth:`~petastorm_tpu.reader.Reader.profile` result or
    its :func:`~petastorm_tpu.profiler.roofline_summary`) adds a
    ``roofline`` section — measured samples/s as a fraction of the
    calibrated binding-stage ceiling — so the diagnosis says not only
    *which* stage binds but *how far from the host's measured limit* the
    pipeline runs (see ``docs/profiling.md``).

    ``latency`` (a :class:`~petastorm_tpu.latency.PipelineLatency`, e.g.
    ``reader.stats.latency``) adds a ``latency`` section of per-stage
    percentile summaries; the snapshot's derived ``queue_wait_p50_s`` /
    ``queue_wait_p99_s`` / ``e2e_latency_p99_s`` keys are surfaced either
    way. ``slo`` (an :class:`~petastorm_tpu.latency.SLOMonitor` verdict)
    embeds the SLO burn accounting (see ``docs/latency.md``).
    """
    from petastorm_tpu.health import (DEFAULT_STALL_AFTER_S,
                                      bottleneck_signals, classify_pipeline)
    from petastorm_tpu.workers.stats import (batched_decode_fraction,
                                             device_decode_fraction,
                                             readahead_hit_rate,
                                             recommend_io_readahead)
    signals = bottleneck_signals(snapshot)
    io_s, decode_s = signals['io_s'], signals['decode_s']
    out = {
        'bottleneck': signals['bottleneck'],
        'io_s': round(io_s, 4),
        'decode_s': round(decode_s, 4),
        'io_decode_ratio': round(io_s / decode_s, 3) if decode_s else None,
        'io_overlap_fraction': snapshot.get('io_overlap_fraction', 0.0),
        'readahead_hit_rate': readahead_hit_rate(snapshot),
        'recommended_io_readahead': recommend_io_readahead(snapshot),
        'rows_quarantined': snapshot.get('rows_quarantined', 0),
        'rows_decoded_batched': snapshot.get('rows_decoded_batched', 0),
        'rows_decoded_percell': snapshot.get('rows_decoded_percell', 0),
        'batched_decode_fraction': batched_decode_fraction(snapshot),
        # ONE device-side block: decode placement, per-step goodput, and the
        # prefetch ring together answer "is the accelerator actually fed?"
        # without hunting across sections (docs/goodput.md).
        'device': {
            'rows_decoded_device': snapshot.get('rows_decoded_device', 0),
            'bytes_shipped_raw': snapshot.get('bytes_shipped_raw', 0),
            'device_decode_fraction': device_decode_fraction(snapshot),
            'goodput_fraction': snapshot.get('goodput_fraction'),
            'data_stall_fraction': snapshot.get('data_stall_fraction'),
            'prefetch_occupancy': snapshot.get('prefetch_occupancy', 0),
            'prefetch_occupancy_max': snapshot.get('prefetch_occupancy_max',
                                                   0),
        },
        'queue_wait_p50_s': round(snapshot.get('queue_wait_p50_s', 0.0), 6),
        'queue_wait_p99_s': round(snapshot.get('queue_wait_p99_s', 0.0), 6),
        'e2e_latency_p99_s': round(snapshot.get('e2e_latency_p99_s', 0.0), 6),
        'hint': signals['hint'],
    }
    if signals.get('tail_stall'):
        out['tail_stall'] = True
    if latency is not None:
        out['latency'] = latency.summary()
    if slo is not None:
        out['slo'] = slo
    if heartbeats is not None:
        verdict = classify_pipeline(
            heartbeats, snapshot,
            DEFAULT_STALL_AFTER_S if stall_after_s is None else stall_after_s)
        out['pipeline_state'] = verdict['state']
        out['stalled_entities'] = verdict['stalled_entities']
        if verdict['state'] == 'stalled':
            # a wedged entity trumps any aggregate signal: time sums stop
            # moving the moment the stall starts, so the ratios describe the
            # past, not the problem
            out['bottleneck'] = 'stalled'
            out['hint'] = verdict['hint']
    if roofline is not None:
        from petastorm_tpu.profiler import roofline_summary
        out['roofline'] = (roofline_summary(roofline)
                           if roofline.get('kind') ==
                           'petastorm_tpu_roofline_profile' else roofline)
    return out


def make_jax_loader(reader, batch_size=1, mesh=None, batch_axis='data',
                    shuffling_queue_capacity=0, transform_fn=None,
                    drop_last=False, seed=None, inmemory_cache_all=False,
                    pad_spec=None, device_decode=True, prefetch_depth=None):
    """Factory: plain host loader when ``mesh is None``, else a sharded loader.

    With a mesh, ``batch_size`` is the **per-process** batch size; the global
    logical batch is ``batch_size * jax.process_count()``.

    ``device_decode=False`` opts the host loader out of claiming a
    bytes-through reader's raw columns (the reader then host-decodes them,
    keeping its yield contract). ``prefetch_depth`` sets the loaders'
    :meth:`~JaxLoaderBase.iter_prefetched` lookahead window (default: the
    ``PETASTORM_TPU_PREFETCH_DEPTH`` env var, else 2 — docs/readahead.md).
    """
    if mesh is None:
        return JaxDataLoader(reader, batch_size=batch_size,
                             shuffling_queue_capacity=shuffling_queue_capacity,
                             transform_fn=transform_fn, drop_last=drop_last,
                             seed=seed, inmemory_cache_all=inmemory_cache_all,
                             pad_spec=pad_spec, device_decode=device_decode,
                             prefetch_depth=prefetch_depth)
    return ShardedJaxLoader(reader, mesh, batch_size, batch_axis=batch_axis,
                            shuffling_queue_capacity=shuffling_queue_capacity,
                            transform_fn=transform_fn, seed=seed,
                            inmemory_cache_all=inmemory_cache_all,
                            pad_spec=pad_spec, prefetch_depth=prefetch_depth)


def epoch_cache_on_device(loader, sharding=None):
    """Iterate epochs forever, caching epoch 1 **on device**.

    Epoch 1 stages each batch into HBM (``jax.device_put``) and keeps the
    device arrays; epochs 2+ replay them with zero host work and zero
    transfers — infeed disappears entirely for datasets that fit in device
    memory (the device-side upgrade of the reference's host-side
    ``inmemory_cache_all``, ``pytorch.py:292-321``). Host-only columns
    (``_host`` or string/object arrays) are kept on host, untouched.

    :param loader: an iterable yielding batch dicts; re-iterated never (the
        cached epoch is replayed instead).
    :param sharding: optional ``jax.sharding.Sharding`` for the device copies.
    """
    import jax

    def stage(batch):
        def put(x):
            if not _is_device_compatible(x):
                return x
            return jax.device_put(x, sharding) if sharding is not None \
                else jax.device_put(x)
        return jax.tree_util.tree_map(put, batch)

    cache = []
    for batch in loader:
        staged = stage(batch)
        cache.append(staged)
        yield staged
    if not cache:
        return
    while True:
        for batch in cache:
            yield batch


def prefetch_batches(iterator, size=None, health=None, stats=None):
    """Host-side lookahead WITHOUT device staging: a background thread keeps
    up to ``size`` numpy batches ready; the jitted step's own call performs
    the host→device transfer. ``iterator`` may be a loader or the iterator
    ``iter(loader)`` returns: the prefetcher then takes the loader's planes
    (those not given here) and its :class:`LoopBoundary`, recorded on the
    consumer's side of the ring. ``health`` (a
    :class:`~petastorm_tpu.health.HealthMonitor`, e.g. ``reader.health``)
    lets the prefetch thread publish liveness heartbeats.

    When to use which prefetcher: :func:`prefetch_to_device` issues an
    explicit ``jax.device_put`` per batch, overlapping the H2D DMA with
    compute — right for large batches where transfer bandwidth matters. For
    small/latency-bound batches the extra per-batch transfer dispatch (and
    its GIL traffic against the decode workers) costs more than it hides:
    passing numpy straight into ``jit`` folds transfer+execute into one
    dispatch. Measured on a v5e LM bench (64×257 int32 batches, ~1ms steps):
    86-90% infeed overlap via ``prefetch_to_device`` vs ~99% via
    ``prefetch_batches``. ``stats`` (a ``ReaderStats``) gauges the live ring
    depth as ``prefetch_occupancy`` — an empty ring at step boundaries is
    the classic starving signal."""
    source = _Source(iterator, stats=stats, health=health)
    return _pipeline(source, resolve_prefetch_depth(size),
                     lambda batch: batch)


def prefetch_to_device(iterator, size=None, sharding=None, stats=None,
                       tracer=None, health=None, fused_fn=None, goodput=None):
    """Double-buffered host→device prefetch.

    Stages up to ``size`` batches ahead of the consumer on a background thread
    so the ``jax.device_put`` (host→HBM DMA) of batch N+1 overlaps the compute
    of batch N. When batches are already global ``jax.Array``s (from
    ``ShardedJaxLoader``) the transfer has been issued, and recorded, at
    construction time: this just provides pipelining depth and records no
    second ``device_stage``. See :func:`prefetch_batches` for the
    small-batch/latency-bound alternative.

    :param iterator: the batches: a loader, the iterator ``iter(loader)``
        returns, or any iterator of batch pytrees. From a loader the
        prefetcher takes the planes below that are not given, and its
        :class:`LoopBoundary`: the loop's ``infeed_wait``/``train_step``
        are then recorded on the consumer's side of the ring, and the
        producer thread's time inside the loader is a ``stage_next`` span.
    :param sharding: optional ``jax.sharding.Sharding`` applied via
        ``jax.device_put`` to plain numpy batches.
    :param stats: optional ``ReaderStats`` (e.g. ``reader.stats`` /
        ``loader.stats``) accumulating the transfer-dispatch wall time as
        ``device_stage_s``.
    :param tracer: optional ``Tracer`` (e.g. ``reader.tracer``) recording
        each transfer dispatch as a ``device_stage`` span — the prefetch
        thread gets its own track, so the overlap with the consumer's
        ``train_step`` spans is visible directly.
    :param health: optional :class:`~petastorm_tpu.health.HealthMonitor`
        (e.g. ``reader.health`` / ``loader.health``); the prefetch thread
        publishes a ``loader-prefetch`` heartbeat entity so the watchdog can
        tell a wedged device transfer from a starving reader.
    :param fused_fn: optional ``ops.decode.build_fused_infeed`` program run
        over each staged batch's device-compatible columns on the prefetch
        thread — bytes-through decode (+ device ``TransformSpec``) overlaps
        the consumer's compute exactly like the transfer it rides with.
    :param goodput: optional :class:`~petastorm_tpu.goodput.GoodputMonitor`
        (e.g. ``loader.goodput``); each transfer dispatch's wall time is
        attributed to the in-flight step's ``h2d_stage`` leg (thread-safe —
        the put runs on the prefetch thread).
    :param size: lookahead depth; ``None`` resolves the loader knob chain
        (``PETASTORM_TPU_PREFETCH_DEPTH``, else 2 — docs/readahead.md).
    """
    import jax
    size = resolve_prefetch_depth(size)
    source = _Source(iterator, stats=stats, tracer=tracer, health=health,
                     goodput=goodput)
    stats, tracer, goodput = source.stats, source.tracer, source.goodput

    def put(batch):
        # _is_device_compatible reads dtype via getattr: global jax.Arrays must
        # NOT be round-tripped through np.asarray (device->host copy; crashes
        # on non-fully-addressable multi-host arrays).
        if sharding is None and fused_fn is None and all(
                isinstance(x, jax.Array) or not _is_device_compatible(x)
                for x in jax.tree_util.tree_leaves(batch)):
            return batch    # staged, and recorded, where it was assembled
        timed = stats is not None or tracer is not None or goodput is not None
        start = time.perf_counter() if timed else 0.0
        if sharding is None:
            staged = jax.tree_util.tree_map(
                lambda x: jax.device_put(x) if _is_device_compatible(x) else x,
                batch)
        else:
            staged = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding) if _is_device_compatible(x) else x,
                batch)
        if fused_fn is not None and isinstance(staged, dict):
            host = {k: v for k, v in staged.items()
                    if not _is_device_compatible(v)}
            dev = {k: v for k, v in staged.items()
                   if _is_device_compatible(v)}
            if dev:
                staged = dict(fused_fn(dev))
                staged.update(host)
        if timed:
            _record_stage(start, stats, tracer, goodput)
        return staged

    return _pipeline(source, size, put)


class _Source(object):
    """A prefetcher's source and planes. Handed a loader or its
    :class:`LoaderIterator`, it takes the loader's planes where none is
    given, and the loop boundary, which the ring's consumer side times and
    the producer thread folds (:meth:`LoopBoundary.fold`); the producer
    thread's time inside the loader is then a ``stage_next`` span."""

    __slots__ = ('batches', 'boundary', 'stats', 'tracer', 'health',
                 'goodput')

    def __init__(self, iterator, stats=None, tracer=None, health=None,
                 goodput=None):
        if isinstance(iterator, JaxLoaderBase):
            iterator = iter(iterator)
        self.boundary = None
        if isinstance(iterator, LoaderIterator):
            loader = iterator.loader
            stats = loader.stats if stats is None else stats
            tracer = loader.tracer if tracer is None else tracer
            health = loader.health if health is None else health
            goodput = loader.goodput if goodput is None else goodput
            self.boundary = iterator.take_boundary()
            if self.boundary is not None:
                self.boundary.deferred = True
            if tracer is not None:
                iterator = _spanned(iterator, tracer)
        self.batches = iterator
        self.stats, self.tracer = stats, tracer
        self.health, self.goodput = health, goodput


def _spanned(batches, tracer):
    """``batches``, each draw recorded as a ``stage_next`` span on the
    drawing (producer) thread."""
    batches = iter(batches)
    while True:
        start = time.perf_counter()
        try:
            batch = next(batches)
        except StopIteration:
            return
        tracer.add_span('stage_next', 'producer', start,
                        time.perf_counter() - start)
        yield batch


def _pipeline(source, size, put):
    """Shared producer-thread pipeline behind the two prefetchers, drawing
    from ``source`` (a :class:`_Source`). Its ``stats`` gauges the ring's
    live depth as ``prefetch_occupancy`` on every enqueue/dequeue — the
    depth is read under the ring's condition but the gauge is recorded
    OUTSIDE it (the stats lock must never nest inside the ring lock). Its
    ``boundary``, when the source is a loader, is timed here on the
    consumer's side, the thread that waits for the batch, and folded on
    the producer's."""
    queue = collections.deque()
    done = object()
    cv = threading.Condition()
    state = {'error': None, 'finished': False}
    iterator, boundary = source.batches, source.boundary
    beat = source.health.beat if source.health is not None else None
    gauge = source.stats.gauge if source.stats is not None else None
    # the boundary's stats update carries the consumer side's gauge
    folded = (boundary is not None and gauge is not None
              and boundary.stats is source.stats)

    def producer():
        try:
            for batch in iterator:
                if state['finished']:   # consumer closed early: stop reading
                    return
                if beat is not None:
                    beat('loader-prefetch', 'staging')
                staged = put(batch)
                with cv:
                    if beat is not None and len(queue) >= size:
                        # blocked on a full prefetch queue = the consumer is
                        # the slow side; idle-class, never a prefetch stall
                        beat('loader-prefetch', 'backpressured')
                    while len(queue) >= size and not state['finished']:
                        cv.wait()
                    if state['finished']:
                        return
                    queue.append(staged)
                    depth = len(queue)
                    cv.notify_all()
                if gauge is not None:
                    gauge('prefetch_occupancy', depth)
                if boundary is not None:
                    boundary.fold()
                if beat is not None:
                    beat('loader-prefetch', 'idle')
        except Exception as e:  # propagate into the consumer
            state['error'] = e
        finally:
            if beat is not None:
                beat('loader-prefetch', 'done')
            with cv:
                queue.append(done)
                cv.notify_all()

    thread = threading.Thread(target=producer, daemon=True,
                              name='petastorm-tpu-prefetch')
    thread.start()
    try:
        while True:
            if boundary is not None:
                boundary.waiting()
            with cv:
                while not queue:
                    cv.wait()
                item = queue.popleft()
                # the done sentinel is not a buffered batch: the gauge must
                # read 0 once the ring is drained, not count the marker
                depth = len(queue) - (1 if queue and queue[-1] is done else 0)
                cv.notify_all()
            if item is done:
                if boundary is not None:
                    boundary.ended()
                if state['error'] is not None:
                    raise state['error']
                return
            if boundary is not None:
                boundary.delivered(item, depth if folded else None)
            if gauge is not None and not folded:
                gauge('prefetch_occupancy', depth)
            yield item
    finally:
        with cv:
            state['finished'] = True
            queue.clear()
            cv.notify_all()
        if boundary is not None:
            boundary.fold()
