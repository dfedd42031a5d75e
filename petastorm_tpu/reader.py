"""Reader core: ``make_reader`` / ``make_batch_reader`` factories and the
``Reader`` orchestrator.

Reference parity: ``petastorm/reader.py`` — ``make_reader`` (:61-195),
``make_batch_reader`` (:198-327), ``Reader`` (:330-676): constructor pipeline
(:384-462), row-group filtering by predicate/selector/shard (:498-608),
ventilation (:622-637), iterator protocol (:655-665), ``reset`` (:468-492),
context manager (:670-676), diagnostics (:648-650).

TPU-first deviations:
 - ``seed`` gives a reproducible epoch shuffle (ventilator is seeded).
 - ``cur_shard``/``shard_count`` default to the JAX process if
   ``shard_by_jax_process=True`` is passed (multi-host pods read disjoint
   row-group shards; see SURVEY.md §2 "Parallelism accounting").
 - The reader never touches the TPU: it produces numpy/namedtuple rows.
   Device staging lives in :mod:`petastorm_tpu.jax_utils`.
"""

from __future__ import annotations

import logging
import os
import time
from typing import List, Optional

from petastorm_tpu.cache import LocalDiskCache, NullCache
from petastorm_tpu.codecs import build_decode_overrides
from petastorm_tpu.errors import NoDataAvailableError, PetastormMetadataError
from petastorm_tpu.etl.dataset_metadata import (get_schema, infer_or_load_unischema,
                                                load_row_groups)
from petastorm_tpu.filters import (FiltersPredicate, RowGroupStatsEvaluator,
                                   filter_column_names, normalize_filters,
                                   validate_filter_types)
from petastorm_tpu.fs import get_filesystem_and_path_or_paths, normalize_dataset_url_or_urls
from petastorm_tpu.health import (DEFAULT_STALL_AFTER_S, DebugServer,
                                  HealthMonitor, PipelineWatchdog,
                                  build_flight_record, resolve_debug_port,
                                  write_flight_record)
from petastorm_tpu.lineage import (BatchProvenance, CoverageAuditor,
                                   LineageTracker, batch_provenance_of,
                                   lineage_enabled, unwrap_envelope,
                                   validate_decode_error_policy)
from petastorm_tpu.lineage import replay as _lineage_replay
from petastorm_tpu.ngram import NGram
from petastorm_tpu.predicates import in_reduce
from petastorm_tpu.readers.batch_worker import ArrowBatchWorker, BatchResultsReader
from petastorm_tpu.readers.columnar_worker import ColumnarResultsReader, ColumnarWorker
from petastorm_tpu.readers.row_worker import RowGroupResultsReader, RowGroupWorker
from petastorm_tpu.tracing import MetricsEmitter, Tracer, resolve_trace
from petastorm_tpu.transform import transform_schema
from petastorm_tpu.unischema import match_unischema_fields
from petastorm_tpu.utils import cast_partition_value
from petastorm_tpu.workers import EmptyResultError
from petastorm_tpu.workers.dummy_pool import DummyPool
from petastorm_tpu.workers.process_pool import ProcessPool
from petastorm_tpu.workers.serializers import ArrowTableSerializer, ZeroCopySerializer
from petastorm_tpu.workers.thread_pool import ThreadPool
from petastorm_tpu.workers.ventilator import ConcurrentVentilator

logger = logging.getLogger(__name__)

#: Extra row groups ventilated beyond the worker count, keeping workers busy
#: without unbounded decode-ahead (reference ``reader.py:46``).
_VENTILATE_EXTRA_ROWGROUPS = 2


def _validate_io_readahead(io_readahead):
    """Normalize the ``io_readahead`` knob: 0/None disables, a positive int is
    a fixed per-worker prefetch depth, ``'auto'`` sizes it live from the
    worker's measured io:decode ratio."""
    if io_readahead in (None, 0):
        return 0
    if io_readahead == 'auto':
        return 'auto'
    if isinstance(io_readahead, int) and io_readahead > 0:
        return io_readahead
    raise ValueError("io_readahead must be a non-negative int or 'auto', got "
                     '{!r}'.format(io_readahead))


#: Valid ``cache_type`` values for every reader factory (see
#: ``docs/cache.md``): no caching, a per-reader pickle-on-disk cache, or the
#: host-wide tiered shared decoded cache.
CACHE_TYPES = ('null', 'local-disk', 'shared')


def _make_cache(cache_type, cache_location, cache_size_limit, cache_row_size_estimate,
                cache_extra_settings):
    if cache_type in (None, 'null'):
        return NullCache()
    if cache_type == 'local-disk':
        if not cache_location or not cache_size_limit:
            raise ValueError("cache_type='local-disk' needs cache_location and "
                             'cache_size_limit')
        return LocalDiskCache(cache_location, cache_size_limit,
                              cache_row_size_estimate or 0,
                              **(cache_extra_settings or {}))
    if cache_type == 'shared':
        if not cache_location or not cache_size_limit:
            raise ValueError("cache_type='shared' needs cache_location and "
                             'cache_size_limit')
        from petastorm_tpu.sharedcache import (SharedRowGroupCache,
                                               shared_cache_enabled)
        if not shared_cache_enabled():
            # kill switch: no attachment, no files, no shared state at all
            logger.warning(
                "cache_type='shared' disabled via %s=0; reads are uncached",
                'PETASTORM_TPU_SHARED_CACHE')
            return NullCache()
        return SharedRowGroupCache(cache_location, cache_size_limit,
                                   **(cache_extra_settings or {}))
    raise ValueError('cache_type must be one of {}; got {!r}'.format(
        ', '.join(repr(t) for t in CACHE_TYPES), cache_type))


def _make_pool(reader_pool_type, workers_count, results_queue_size, serializer,
               zmq_copy_buffers, profiling_enabled=False, tracer=None,
               recovery=None):
    if reader_pool_type == 'thread':
        return ThreadPool(workers_count, results_queue_size,
                          profiling_enabled=profiling_enabled, tracer=tracer,
                          recovery=recovery)
    if reader_pool_type == 'process':
        return ProcessPool(workers_count, serializer=serializer,
                           zmq_copy_buffers=zmq_copy_buffers, tracer=tracer,
                           recovery=recovery)
    if reader_pool_type == 'dummy':
        return DummyPool(tracer=tracer)
    raise ValueError("reader_pool_type must be one of 'thread', 'process', 'dummy'; "
                     'got {!r}'.format(reader_pool_type))


def _make_tracer(trace):
    """Resolve the ``trace=`` kwarg (and :data:`~petastorm_tpu.tracing.TRACE_ENV_VAR`)
    into ``(Tracer-or-None, export_path-or-None)``."""
    enabled, export_path = resolve_trace(trace)
    return (Tracer() if enabled else None), export_path


def _relax_hinted_shapes(schema, decode_hints, stored_schema):
    """Copy of ``schema`` with the spatial dims of hinted fields made dynamic
    (``None`` wildcards) — scaled decode changes them at read time. A field
    whose shape a TransformSpec redeclared (differs from the stored shape,
    e.g. a resize to a fixed size) keeps its declared shape."""
    from petastorm_tpu.unischema import Unischema, UnischemaField
    fields = []
    for f in schema.fields.values():
        stored = stored_schema.fields.get(f.name)
        # only fields the codec can actually scale get dynamic dims — a
        # hinted field decode_scaled always passes through (png, uint16,
        # RGBA) keeps its exact static shape
        scalable = (stored is not None
                    and getattr(stored.codec, 'can_scale',
                                lambda _f: False)(stored))
        if (f.name in decode_hints and scalable and f.shape
                and len(f.shape) >= 2 and f.shape == stored.shape):
            f = UnischemaField(f.name, f.numpy_dtype,
                               (None, None) + tuple(f.shape[2:]),
                               f.codec, f.nullable)
        fields.append(f)
    return Unischema(schema._name, fields)


def _validate_shard_range(cur_shard, shard_count):
    """Fail at the factory with a message naming both values — a bad shard
    spec must not surface as an empty iterator or a ventilator IndexError
    deep inside the pipeline."""
    if cur_shard is None and shard_count is None:
        return
    if (cur_shard is None) != (shard_count is None):
        raise ValueError('cur_shard and shard_count must be specified together '
                         '(got cur_shard={!r}, shard_count={!r})'.format(
                             cur_shard, shard_count))
    if shard_count < 1:
        raise ValueError('shard_count must be a positive integer, got '
                         'shard_count={!r} (with cur_shard={!r})'.format(
                             shard_count, cur_shard))
    if cur_shard < 0:
        raise ValueError('cur_shard must be non-negative, got cur_shard={!r} '
                         '(with shard_count={!r})'.format(
                             cur_shard, shard_count))
    if cur_shard >= shard_count:
        raise ValueError('cur_shard must be < shard_count, got cur_shard={!r} '
                         'for shard_count={!r}'.format(cur_shard, shard_count))


def _resolve_jax_shard(cur_shard, shard_count, shard_by_jax_process,
                       elastic=None):
    if elastic is not None:
        # lease-driven shard assignment: the elasticity plane derives
        # (cur_shard, shard_count) from the live pod membership (import is
        # local so the default-off plane costs nothing when unused)
        from petastorm_tpu.podelastic import resolve_elastic_shard
        cur_shard, shard_count, _ = resolve_elastic_shard(
            elastic, cur_shard, shard_count, shard_by_jax_process)
        _validate_shard_range(cur_shard, shard_count)
        return cur_shard, shard_count
    if not shard_by_jax_process:
        _validate_shard_range(cur_shard, shard_count)
        return cur_shard, shard_count
    if cur_shard is not None or shard_count is not None:
        raise ValueError('shard_by_jax_process is mutually exclusive with explicit '
                         'cur_shard/shard_count')
    import jax
    cur_shard, shard_count = jax.process_index(), jax.process_count()
    _validate_shard_range(cur_shard, shard_count)
    return cur_shard, shard_count


def make_reader(dataset_url,
                schema_fields=None,
                reader_pool_type='thread', workers_count=10, results_queue_size=50,
                seed=None, shuffle_row_groups=True, shuffle_row_drop_partitions=1,
                predicate=None, rowgroup_selector=None,
                num_epochs=1,
                cur_shard=None, shard_count=None, shard_by_jax_process=False,
                cache_type='null', cache_location=None, cache_size_limit=None,
                cache_row_size_estimate=None, cache_extra_settings=None,
                transform_spec=None, filters=None,
                storage_options=None, zmq_copy_buffers=True,
                profiling_enabled=False, decode_hints=None,
                io_readahead=0, trace=None, metrics_interval=0,
                metrics_out=None, debug_port=None, stall_timeout=0,
                flight_record_dir=None, on_decode_error='raise',
                slo=None, autotune=False, retry=None, hedge=None,
                remote_read=None, worker_recovery=None, elastic=None):
    """Row-granular reader for petastorm_tpu datasets (codec-decoded rows).

    Mirrors the reference factory (``reader.py:61-195``). Raises a helpful error
    directing to :func:`make_batch_reader` when the store lacks petastorm
    metadata (reference behavior at ``reader.py:128-141``).

    With ``reader_pool_type='process'`` payloads cross the worker boundary
    over the zero-copy transport: large (≥64 KB) contiguous arrays arrive as
    **read-only** views over the transport frames (see ``docs/transport.md``).
    Consumers that mutate samples in place must copy first; batching
    (``JaxDataLoader`` collation, shuffling buffers) already copies.

    ``io_readahead=K`` gives each worker a background reader that prefetches
    the parquet reads of its next K ventilated pieces while it decodes the
    current one, overlapping storage latency with decode CPU; ``'auto'``
    sizes K from the live io:decode ratio (see ``docs/readahead.md``).

    ``cache_type`` picks the row-group cache: ``'null'`` (none, the
    default), ``'local-disk'`` (per-reader pickle-on-disk), or ``'shared'``
    — the host-wide tiered cache (shared-memory decoded segments, disk
    spill) that N concurrent readers and their worker processes attach to
    so each row group is read+decoded ONCE per host; a shared-tier miss
    still prefetches via the readahead planner with coalesced remote reads.
    Shared-cache hits return **read-only** zero-copy views. Kill switch:
    ``PETASTORM_TPU_SHARED_CACHE=0``. See ``docs/cache.md``.

    ``trace=True`` (or the ``PETASTORM_TPU_TRACE`` env var) records per-item
    spans for every pipeline stage into ``reader.tracer``, exportable as
    Chrome trace-event JSON for Perfetto; ``metrics_interval=N`` starts a
    background emitter snapshotting the reader's stats every N seconds into
    ``metrics_out`` (JSON-lines, or Prometheus text for ``.prom`` paths).
    See ``docs/tracing.md``.

    ``debug_port=N`` (or ``PETASTORM_TPU_DEBUG_PORT``) serves the live
    health endpoints on ``127.0.0.1:N`` (``/healthz`` ``/metrics``
    ``/diagnostics`` ``/stacks``; ``0`` = ephemeral, read
    ``reader.debug_port``); ``stall_timeout=S`` arms a background watchdog
    that classifies the pipeline from per-entity heartbeats and writes a
    flight-recorder JSON into ``flight_record_dir`` when no entity has made
    progress for S seconds. See ``docs/health.md``.

    Every yielded item carries sample lineage by default (``reader.lineage``
    ledgers, ``reader.explain_batch()``, ``reader.replay()``, the
    ``/coverage`` debug route; kill switch ``PETASTORM_TPU_LINEAGE=0``).
    ``on_decode_error`` picks the bad-sample policy: ``'raise'`` (default)
    propagates decode/transform exceptions, ``'skip'`` drops the failing
    rows counting them, ``'quarantine'`` drops them AND records
    provenance-tagged quarantine records. See ``docs/lineage.md``.

    ``autotune=True`` (or an options dict; job-wide via
    ``PETASTORM_TPU_AUTOTUNE=1``, kill switch ``=0``) starts the
    model-predictive pipeline controller: a background thread that
    live-resizes the worker pool, readahead depth, ventilation window and
    results-queue bound toward the roofline model's best predicted
    configuration, with hysteresis, per-knob cooldowns and
    revert-on-regression. Every action is observable via ``/autotune``,
    flight records and ``/metrics``. See ``docs/autotune.md``.

    Fault tolerance (``docs/robustness.md``): ``retry=`` (default ON)
    retries transient storage errors under the shared
    :class:`~petastorm_tpu.resilience.RetryPolicy` (full-jitter backoff,
    total-wall cap; permanent errors fail in one attempt); ``hedge=``
    (default off; ``True``, a threshold in seconds, or an options dict)
    fires a duplicate row-group read when the first exceeds the live p95 —
    first result wins; ``worker_recovery=`` (default ON) respawns a crashed
    worker and re-ventilates its in-flight items exactly once, with bounded
    respawns and poison-item quarantine. ``PETASTORM_TPU_CHAOS`` arms the
    deterministic fault-injection harness.

    ``remote_read=`` picks the storage read plane
    (``docs/object_store.md``): ``'serial'`` (plain reads), ``'prebuffer'``
    (pyarrow-coalesced column chunks), ``'ranged'`` (explicit footer-planned
    parallel range fetches; retry/hedge then apply per RANGE, not per row
    group). Default auto: ``prebuffer`` for object stores, ``serial`` local.

    ``elastic=`` (a ``{'coord_root': ...}`` dict; default off, kill switch
    ``PETASTORM_TPU_ELASTIC=0``) derives ``(cur_shard, shard_count)`` from
    the live pod membership instead of static arguments — a **snapshot**
    taken at construction; mid-epoch host death/join rebalancing lives in
    the lease-grid plane (``petastorm_tpu.podelastic``,
    ``docs/robustness.md``). Mutually exclusive with explicit
    ``cur_shard``/``shard_count`` and ``shard_by_jax_process``.
    """
    dataset_url = normalize_dataset_url_or_urls(dataset_url)
    fs, path, factory = get_filesystem_and_path_or_paths(dataset_url, storage_options)
    if isinstance(path, list):
        raise ValueError('make_reader supports a single dataset url; a list of file '
                         'urls is only supported by make_batch_reader')
    try:
        get_schema(fs, path)
    except PetastormMetadataError as e:
        raise RuntimeError(
            'Dataset at {} is missing petastorm_tpu metadata ({}). If this is a plain '
            'parquet store, use make_batch_reader instead.'.format(dataset_url, e))

    cache = _make_cache(cache_type, cache_location, cache_size_limit,
                        cache_row_size_estimate, cache_extra_settings)
    tracer, trace_export = _make_tracer(trace)
    from petastorm_tpu.resilience import resolve_recovery
    # ZeroCopySerializer: decoded ndarray payloads cross the process boundary
    # as out-of-band ZMQ frames instead of being memcpy'd into a pickle blob
    pool = _make_pool(reader_pool_type, workers_count, results_queue_size,
                      ZeroCopySerializer(), zmq_copy_buffers, profiling_enabled,
                      tracer=tracer, recovery=resolve_recovery(worker_recovery))
    cur_shard, shard_count = _resolve_jax_shard(cur_shard, shard_count,
                                                 shard_by_jax_process, elastic)
    return Reader(factory, path,
                  worker_class=RowGroupWorker,
                  results_reader_factory=RowGroupResultsReader,
                  schema_fields=schema_fields, seed=seed,
                  shuffle_row_groups=shuffle_row_groups,
                  shuffle_row_drop_partitions=shuffle_row_drop_partitions,
                  predicate=predicate, rowgroup_selector=rowgroup_selector,
                  num_epochs=num_epochs, cur_shard=cur_shard, shard_count=shard_count,
                  cache=cache, transform_spec=transform_spec, filters=filters,
                  pool=pool, is_batched_reader=False, decode_hints=decode_hints,
                  io_readahead=io_readahead, trace_export=trace_export,
                  metrics_interval=metrics_interval, metrics_out=metrics_out,
                  debug_port=debug_port, stall_timeout=stall_timeout,
                  flight_record_dir=flight_record_dir,
                  on_decode_error=on_decode_error, slo=slo,
                  autotune=autotune, retry=retry, hedge=hedge,
                  remote_read=remote_read)


def make_columnar_reader(dataset_url,
                         schema_fields=None,
                         reader_pool_type='thread', workers_count=10,
                         results_queue_size=50,
                         seed=None, shuffle_row_groups=True,
                         shuffle_row_drop_partitions=1,
                         predicate=None, rowgroup_selector=None,
                         num_epochs=1,
                         cur_shard=None, shard_count=None, shard_by_jax_process=False,
                         cache_type='null', cache_location=None, cache_size_limit=None,
                         cache_row_size_estimate=None, cache_extra_settings=None,
                         transform_spec=None, filters=None,
                         storage_options=None, zmq_copy_buffers=True,
                         profiling_enabled=False, decode_hints=None,
                         io_readahead=0, trace=None, metrics_interval=0,
                         metrics_out=None, debug_port=None, stall_timeout=0,
                         flight_record_dir=None, on_decode_error='raise',
                         slo=None, autotune=False, retry=None, hedge=None,
                         remote_read=None, worker_recovery=None,
                         elastic=None):
    """Vectorized codec-decoded reader for petastorm_tpu datasets.

    Yields **batch namedtuples of decoded numpy column arrays** (one per row
    group), with no per-row Python work anywhere on the path — the layout the
    JAX adapter wants. This is the high-throughput way to read codec datasets;
    ``make_reader`` remains the row-granular analogue of the reference API.

    Differences from :func:`make_reader`: ``batched_output=True``; NGram is not
    supported (windows are row-granular); ``TransformSpec.func`` receives a
    dict of column arrays instead of a row dict.

    With ``reader_pool_type='process'`` the published column arrays arrive
    over the zero-copy transport as **read-only** views over the transport
    frames (see ``docs/transport.md``); copy before mutating in place.
    """
    dataset_url = normalize_dataset_url_or_urls(dataset_url)
    fs, path, factory = get_filesystem_and_path_or_paths(dataset_url, storage_options)
    if isinstance(path, list):
        raise ValueError('make_columnar_reader supports a single dataset url; a list '
                         'of file urls is only supported by make_batch_reader')
    if isinstance(schema_fields, NGram):
        raise ValueError('NGram is not supported by make_columnar_reader; use '
                         'make_reader for windowed sequence assembly')
    try:
        get_schema(fs, path)
    except PetastormMetadataError as e:
        raise RuntimeError(
            'Dataset at {} is missing petastorm_tpu metadata ({}). If this is a plain '
            'parquet store, use make_batch_reader instead.'.format(dataset_url, e))

    cache = _make_cache(cache_type, cache_location, cache_size_limit,
                        cache_row_size_estimate, cache_extra_settings)
    tracer, trace_export = _make_tracer(trace)
    from petastorm_tpu.resilience import resolve_recovery
    pool = _make_pool(reader_pool_type, workers_count, results_queue_size,
                      ZeroCopySerializer(), zmq_copy_buffers, profiling_enabled,
                      tracer=tracer, recovery=resolve_recovery(worker_recovery))
    cur_shard, shard_count = _resolve_jax_shard(cur_shard, shard_count,
                                                 shard_by_jax_process, elastic)
    return Reader(factory, path,
                  worker_class=ColumnarWorker,
                  results_reader_factory=ColumnarResultsReader,
                  schema_fields=schema_fields, seed=seed,
                  shuffle_row_groups=shuffle_row_groups,
                  shuffle_row_drop_partitions=shuffle_row_drop_partitions,
                  predicate=predicate, rowgroup_selector=rowgroup_selector,
                  num_epochs=num_epochs, cur_shard=cur_shard, shard_count=shard_count,
                  cache=cache, transform_spec=transform_spec, filters=filters,
                  pool=pool, is_batched_reader=True, decode_hints=decode_hints,
                  io_readahead=io_readahead, trace_export=trace_export,
                  metrics_interval=metrics_interval, metrics_out=metrics_out,
                  debug_port=debug_port, stall_timeout=stall_timeout,
                  flight_record_dir=flight_record_dir,
                  on_decode_error=on_decode_error, slo=slo,
                  autotune=autotune, retry=retry, hedge=hedge,
                  remote_read=remote_read)


def make_batch_reader(dataset_url_or_urls,
                      schema_fields=None,
                      reader_pool_type='thread', workers_count=10, results_queue_size=50,
                      seed=None, shuffle_row_groups=True,
                      predicate=None,
                      num_epochs=1,
                      cur_shard=None, shard_count=None, shard_by_jax_process=False,
                      cache_type='null', cache_location=None, cache_size_limit=None,
                      cache_row_size_estimate=None, cache_extra_settings=None,
                      transform_spec=None, filters=None,
                      storage_options=None, zmq_copy_buffers=True,
                      profiling_enabled=False, io_readahead=0, trace=None,
                      metrics_interval=0, metrics_out=None, debug_port=None,
                      stall_timeout=0, flight_record_dir=None,
                      on_decode_error='raise', slo=None, autotune=False,
                      retry=None, hedge=None, remote_read=None,
                      worker_recovery=None, elastic=None):
    """Vectorized batch reader for arbitrary parquet stores
    (reference ``reader.py:198-327``). Yields namedtuples of column arrays,
    one per row group. ``io_readahead`` prefetches upcoming row-group reads
    per worker; ``trace``/``metrics_interval``/``metrics_out`` enable the
    span tracer and metrics emitter; ``debug_port``/``stall_timeout``/
    ``flight_record_dir`` the live health layer (see :func:`make_reader`)."""
    dataset_url_or_urls = normalize_dataset_url_or_urls(dataset_url_or_urls)
    fs, path, factory = get_filesystem_and_path_or_paths(dataset_url_or_urls,
                                                         storage_options)
    if schema_fields is not None and not (
            isinstance(schema_fields, list)
            and all(isinstance(f, str) for f in schema_fields)):
        raise ValueError('make_batch_reader schema_fields must be a list of regex '
                         'strings (UnischemaField selection and NGram are row-reader '
                         'features)')
    cache = _make_cache(cache_type, cache_location, cache_size_limit,
                        cache_row_size_estimate, cache_extra_settings)
    tracer, trace_export = _make_tracer(trace)
    from petastorm_tpu.resilience import resolve_recovery
    pool = _make_pool(reader_pool_type, workers_count, results_queue_size,
                      ArrowTableSerializer(), zmq_copy_buffers, profiling_enabled,
                      tracer=tracer, recovery=resolve_recovery(worker_recovery))
    cur_shard, shard_count = _resolve_jax_shard(cur_shard, shard_count,
                                                 shard_by_jax_process, elastic)
    return Reader(factory, path,
                  worker_class=ArrowBatchWorker,
                  results_reader_factory=BatchResultsReader,
                  schema_fields=schema_fields, seed=seed,
                  shuffle_row_groups=shuffle_row_groups, shuffle_row_drop_partitions=1,
                  predicate=predicate, rowgroup_selector=None,
                  num_epochs=num_epochs, cur_shard=cur_shard, shard_count=shard_count,
                  cache=cache, transform_spec=transform_spec, filters=filters,
                  pool=pool, is_batched_reader=True, io_readahead=io_readahead,
                  trace_export=trace_export, metrics_interval=metrics_interval,
                  metrics_out=metrics_out, debug_port=debug_port,
                  stall_timeout=stall_timeout,
                  flight_record_dir=flight_record_dir,
                  on_decode_error=on_decode_error, slo=slo,
                  autotune=autotune, retry=retry, hedge=hedge,
                  remote_read=remote_read)


class Reader:
    """Iterates rows (or batches) of a parquet dataset through a worker pool."""

    def __init__(self, filesystem_factory, dataset_path,
                 worker_class, results_reader_factory,
                 schema_fields=None, seed=None, shuffle_row_groups=True,
                 shuffle_row_drop_partitions=1, predicate=None, rowgroup_selector=None,
                 num_epochs=1, cur_shard=None, shard_count=None,
                 cache=None, transform_spec=None, filters=None,
                 pool=None, is_batched_reader=False, decode_hints=None,
                 io_readahead=0, trace_export=None, metrics_interval=0,
                 metrics_out=None, debug_port=None, stall_timeout=0,
                 flight_record_dir=None, on_decode_error='raise',
                 slo=None, autotune=False, retry=None, hedge=None,
                 remote_read=None):
        if (cur_shard is None) != (shard_count is None):
            raise ValueError('cur_shard and shard_count must be specified together')
        if cur_shard is not None and not 0 <= cur_shard < shard_count:
            raise ValueError('cur_shard {} out of range for shard_count {}'.format(
                cur_shard, shard_count))
        if predicate is not None and not isinstance(cache, NullCache):
            raise RuntimeError('Local cache is not supported together with predicates '
                               '(cached row groups would bypass predicate evaluation)')
        if metrics_interval and not metrics_out:
            raise ValueError('metrics_interval needs a metrics_out path to '
                             'emit snapshots into')
        if stall_timeout and stall_timeout < 0:
            raise ValueError('stall_timeout must be >= 0, got '
                             '{!r}'.format(stall_timeout))
        validate_decode_error_policy(on_decode_error)
        # resolve + validate the resilience knobs here (fail fast on a
        # typo'd option); workers re-resolve the stored shapes after
        # unpickling (docs/robustness.md)
        from petastorm_tpu.resilience import resolve_hedge, resolve_retry
        retry_options = resolve_retry(retry)
        hedge_options = resolve_hedge(hedge)
        # remote read plane (docs/object_store.md): validate here so a
        # typo'd mode fails the factory; None = per-protocol auto in the
        # worker ('prebuffer' remote / 'serial' local, the pre-knob shape)
        from petastorm_tpu.objectstore import resolve_remote_read
        remote_read = resolve_remote_read(remote_read)
        if slo:
            # fail fast on a typo'd target name; the monitor itself is
            # built after the pool (it reads the stats snapshot + latency)
            from petastorm_tpu.latency import validate_slo_targets
            slo = validate_slo_targets(slo)
        # resolve autotune BEFORE any pipeline state exists: a typo'd option
        # must fail the factory, and the PETASTORM_TPU_AUTOTUNE=0 kill
        # switch must yield a reader with no controller thread and no files
        from petastorm_tpu.autotune import resolve_autotune
        autotune_options = resolve_autotune(autotune)
        #: The reader's :class:`~petastorm_tpu.autotune.PipelineController`
        #: (``None`` unless autotune resolved on): serves ``/autotune`` and
        #: owns the live worker/readahead/window/queue knobs.
        self._controller = None
        #: The reader's :class:`~petastorm_tpu.latency.SLOMonitor`
        #: (``None`` unless built with ``slo=dict(...)``); serves ``/slo``
        #: and feeds the burn accounting from the watchdog tick.
        self._slo = None
        self._filesystem_factory = filesystem_factory
        self._dataset_path = dataset_path
        self._pool = pool
        self._is_batched_reader = is_batched_reader
        self._num_epochs = num_epochs
        self._trace_export = trace_export
        self._metrics_emitter = None
        self._watchdog = None
        self._debug_server = None
        #: The loader-attached :class:`~petastorm_tpu.goodput.GoodputMonitor`
        #: (``None`` until a JAX loader registers one, and always ``None``
        #: under ``PETASTORM_TPU_GOODPUT=0``); serves ``/goodput`` and the
        #: flight-record goodput section.
        self._goodput = None
        self._flight_record_dir = flight_record_dir
        self.last_row_consumed = False
        # -- roofline profiler state (see docs/profiling.md) ------------------
        #: Most recent :meth:`profile` result (``None`` until the first call).
        self._last_profile = None
        #: ``stage_ceiling_*`` / ``roofline_fraction`` / ``binding_stage``
        #: gauges merged into :meth:`_stats_snapshot` once a profile exists,
        #: so ``/metrics`` and the metrics emitter expose %-of-ceiling.
        self._roofline_gauges = {}
        self._pool_type = {'ProcessPool': 'process', 'ThreadPool': 'thread',
                           'DummyPool': 'dummy'}.get(type(pool).__name__,
                                                     'thread')
        self._cache_type = {'NullCache': 'null',
                            'LocalDiskCache': 'local-disk',
                            'SharedRowGroupCache': 'shared'}.get(
                                type(cache).__name__, 'null')
        #: The pipeline's :class:`~petastorm_tpu.health.HealthMonitor`:
        #: per-entity heartbeats from the ventilator, the pool's workers
        #: (plus their readahead threads), and — when a prefetcher is handed
        #: the loader, or given ``health=`` — the loader's prefetch thread.
        #: ``reader.health.heartbeats()`` is the live record set.
        self.health = HealthMonitor()

        filesystem = filesystem_factory()
        stored_schema, _ = infer_or_load_unischema(filesystem, dataset_path)

        # -- schema view / ngram resolution (reference reader.py:408-441) ------
        self.ngram = schema_fields if isinstance(schema_fields, NGram) else None
        if self.ngram is not None:
            if is_batched_reader:
                raise ValueError('NGram is not supported by make_batch_reader')
            if not self.ngram.timestamp_overlap and shuffle_row_drop_partitions > 1:
                raise NotImplementedError(
                    'shuffle_row_drop_partitions is not supported with '
                    'timestamp_overlap=False (reference reader.py:420-422)')
            self.ngram.resolve_regex_field_names(stored_schema)
            ngram_field_names = self.ngram.get_all_field_names()
            view_fields = [stored_schema.fields[n] for n in ngram_field_names
                           if n in stored_schema.fields]
            view_schema = stored_schema.create_schema_view(view_fields)
        elif schema_fields is not None:
            if isinstance(schema_fields, list) and all(isinstance(f, str)
                                                       for f in schema_fields):
                matched = match_unischema_fields(stored_schema, schema_fields)
                if not matched:
                    raise ValueError('schema_fields {} matched no fields'.format(
                        schema_fields))
                view_schema = stored_schema.create_schema_view(matched)
            else:
                view_schema = stored_schema.create_schema_view(schema_fields)
        else:
            view_schema = stored_schema

        transformed_schema = (transform_schema(view_schema, transform_spec)
                              if transform_spec is not None else view_schema)
        if decode_hints:
            # hinted fields decode at reduced resolution: the consumer-facing
            # schema must advertise dynamic spatial dims, or adapters (TF
            # static shapes, columnar assembly asserts) would promise the
            # full-resolution shape the data no longer has. Workers keep the
            # original schema — decode_scaled needs the stored shape to pick
            # its denominator.
            transformed_schema = _relax_hinted_shapes(transformed_schema,
                                                      decode_hints,
                                                      stored_schema)
        #: The schema of the rows/batches this reader yields.
        self.schema = transformed_schema

        # -- row-group discovery + filtering (reference reader.py:498-608) -----
        footer_cache = {}
        all_pieces = load_row_groups(filesystem, dataset_path,
                                     footer_cache=footer_cache)
        if not all_pieces:
            raise NoDataAvailableError('No row groups found at {}'.format(dataset_path))
        pieces, worker_predicate, filters_predicate = self._filter_row_groups(
            filesystem, all_pieces, stored_schema, predicate, rowgroup_selector,
            filters, cur_shard, shard_count, footer_cache)
        del all_pieces
        if not pieces:
            raise NoDataAvailableError(
                'No row groups left after predicate/selector/shard filtering at '
                '{}'.format(dataset_path))
        self._pieces = pieces

        # -- ventilation (reference reader.py:622-637) -------------------------
        items = []
        for piece_index in range(len(pieces)):
            piece_predicate = worker_predicate
            if filters_predicate is not None:
                specialized = filters_predicate.specialize(pieces[piece_index],
                                                           stored_schema)
                if specialized is not None:
                    if piece_predicate is not None:
                        piece_predicate = in_reduce(
                            [piece_predicate, specialized], all)
                    else:
                        piece_predicate = specialized
            for drop_partition in range(shuffle_row_drop_partitions):
                items.append({'piece_index': piece_index,
                              'worker_predicate': piece_predicate,
                              'shuffle_row_drop_partition': (
                                  drop_partition, shuffle_row_drop_partitions)})
        # The in-flight bound must cover every worker's prefetch window or
        # the ventilator starves the readahead: each worker holds its current
        # item plus up to `lookahead` hinted ones.
        io_readahead = _validate_io_readahead(io_readahead)
        if io_readahead and not getattr(pool, 'supports_prefetch_hints', False):
            # a pool that never hints (dummy) would record every read as a
            # readahead miss — misleading diagnostics plus dead threads
            logger.debug('io_readahead disabled: %s does not hint workers '
                         'about upcoming items', type(pool).__name__)
            io_readahead = 0
        if io_readahead:
            from petastorm_tpu.readers.readahead import AUTO_MAX_DEPTH
            lookahead = (AUTO_MAX_DEPTH if io_readahead == 'auto'
                         else io_readahead)
        else:
            lookahead = 0
        self._io_readahead = io_readahead
        # -- sample lineage (see docs/lineage.md) ------------------------------
        import hashlib
        dataset_digest = hashlib.md5(
            str(dataset_path).encode()).hexdigest()[:12]
        #: The reader's :class:`~petastorm_tpu.lineage.LineageTracker`:
        #: per-item provenance records, per-epoch ventilated/delivered
        #: ledgers, quarantine ring. ``reader.lineage.coverage_report()``
        #: audits delivery; disabled (but present) under
        #: ``PETASTORM_TPU_LINEAGE=0``.
        self.lineage = LineageTracker(
            enabled=lineage_enabled(),
            dataset_digest=dataset_digest,
            shard=cur_shard if cur_shard is not None else -1,
            pieces=[(p.path, p.row_group, p.num_rows) for p in pieces],
            items=[(it['piece_index'],
                    tuple(it['shuffle_row_drop_partition'])) for it in items],
            row_filtered=(worker_predicate is not None
                          or filters_predicate is not None),
            # ventilate timestamps anchor the end-to-end latency histogram;
            # only stamped when the latency plane actually consumes them
            record_vent_ts=getattr(pool.stats, 'latency', None) is not None)
        #: End-to-end latency recording at ITEM delivery (one observation per
        #: registered item). A JaxDataLoader defers this to its own batch
        #: delivery point via :meth:`_defer_e2e_to_loader` so each delivered
        #: unit is observed exactly once.
        self._e2e_live = (self.lineage.enabled
                          and getattr(pool.stats, 'latency', None) is not None)
        self._last_e2e_seq = None
        self._worker_class = worker_class
        self._replay_items = {
            (it['piece_index'], tuple(it['shuffle_row_drop_partition'])): it
            for it in items}

        tracer = getattr(pool, 'tracer', None)
        ventilate_fn = pool.ventilate
        if self.lineage.enabled:
            # the ventilation ledger is the audit's "expected" side: what was
            # dispatched but never delivered is a DROP, not a mystery
            record_ventilated = self.lineage.record_ventilated
            inner_ventilate = ventilate_fn

            def ventilate_fn(*v_args, **v_kwargs):
                record_ventilated(
                    v_kwargs.get('epoch', 0), v_kwargs.get('piece_index'),
                    v_kwargs.get('shuffle_row_drop_partition', (0, 1)))
                inner_ventilate(*v_args, **v_kwargs)
        if tracer is not None:
            traced_ventilate = ventilate_fn

            def ventilate_fn(*v_args, **v_kwargs):
                with tracer.span('ventilate', 'ventilator'):
                    traced_ventilate(*v_args, **v_kwargs)
        self._ventilator = ConcurrentVentilator(
            ventilate_fn, items, iterations=num_epochs,
            randomize_item_order=shuffle_row_groups, random_seed=seed,
            max_ventilation_queue_size=(
                pool.workers_count * (1 + lookahead) + _VENTILATE_EXTRA_ROWGROUPS),
            heartbeat=self.health.beat if self.health.enabled else None,
            epoch_key='epoch')

        # the controller owns the readahead knob when autotune is on: the
        # machinery is constructed (dormant at depth 0) even when the reader
        # starts with readahead off, and 'auto' stops self-tuning locally —
        # two controllers on one knob would oscillate (docs/autotune.md)
        autotune_active = (autotune_options is not None
                           and self._pool_type in ('thread', 'process'))
        if autotune_options is not None and not autotune_active:
            logger.warning('autotune disabled: the %s pool has no live '
                           'actuators', self._pool_type)

        # -- device-decode planning (docs/decode.md "Device-side decode") ------
        from petastorm_tpu.ops.decode import plan_device_decode
        device_decode_plans, device_decode_declined = plan_device_decode(
            view_schema,
            has_predicate=(worker_predicate is not None
                           or filters_predicate is not None),
            has_ngram=self.ngram is not None,
            decode_hints=decode_hints,
            transform_spec=transform_spec,
            transformed_schema=transformed_schema,
            batched_output=self._is_batched_reader,
            tolerant_decode=(on_decode_error != 'raise'),
            worker_supported=getattr(worker_class, 'supports_device_decode',
                                     False))
        #: name -> :class:`~petastorm_tpu.ops.decode.DeviceColumnPlan` for the
        #: columns workers ship raw (bytes-through); empty when the whole
        #: reader declined to the host decode matrix.
        self.device_decode_plans = device_decode_plans
        #: column name (or ``'*'`` for whole-reader reasons) -> why the device
        #: path declined; surfaced by ``infeed_diagnosis`` for triage.
        self.device_decode_declined = device_decode_declined
        # a device-flagged TransformSpec fuses into the loader's jitted
        # decode program instead of running on CPU workers; the worker-side
        # spec is nulled so the transform runs exactly once
        self._device_transform_spec = (
            transform_spec if (device_decode_plans and transform_spec is not None
                               and transform_spec.device) else None)
        self._device_decode_deferred = False
        worker_transform_spec = (None if self._device_transform_spec is not None
                                 else transform_spec)
        worker_args = {
            'trace': tracer is not None,
            'health': self.health.enabled,
            'lineage': self.lineage.enabled,
            'latency': getattr(pool.stats, 'latency', None) is not None,
            'readahead_controlled': autotune_active,
            # resolved dicts, or False for explicitly-off (a missing key
            # means "default" to the worker, which is not the same thing)
            'retry': retry_options if retry_options else False,
            'hedge': hedge_options if hedge_options else False,
            'remote_read': remote_read,
            'on_decode_error': on_decode_error,
            'shard': cur_shard if cur_shard is not None else -1,
            'filesystem_factory': filesystem_factory,
            'dataset_path': dataset_path,
            'schema': view_schema,
            'full_schema': stored_schema,
            'ngram': self.ngram,
            'split_pieces': pieces,
            'local_cache': cache,
            'transform_spec': worker_transform_spec,
            'transformed_schema': transformed_schema,
            'decode_hints': decode_hints,
            'device_decode_plans': device_decode_plans,
            'io_readahead': io_readahead,
        }
        self._worker_args = worker_args
        # fail fast on bad hints (workers rebuild these after unpickling)
        build_decode_overrides(stored_schema, decode_hints)
        pool.lineage = self.lineage
        pool.start(worker_class, worker_args, self._ventilator)
        if metrics_interval:
            # the reader-level snapshot folds in the roofline gauges once a
            # profile exists, so emitted series gain %-of-ceiling context
            self._metrics_emitter = MetricsEmitter(
                self._stats_snapshot, metrics_interval, metrics_out)
            self._metrics_emitter.start()

        # -- live health + SLO layer (see docs/health.md, docs/latency.md) -----
        if slo:
            from petastorm_tpu.latency import SLOMonitor
            self._slo = SLOMonitor(slo, snapshot_fn=self._stats_snapshot,
                                   latency=getattr(pool.stats, 'latency',
                                                   None))
        # -- autotune controller (see docs/autotune.md) ------------------------
        if autotune_active:
            from petastorm_tpu import profiler as _profiler
            from petastorm_tpu.autotune import (HostArbiter,
                                                PipelineController,
                                                ReaderActuators, scratch_dir)
            from petastorm_tpu.readers.readahead import AUTO_INITIAL_DEPTH
            initial_depth = (AUTO_INITIAL_DEPTH if io_readahead == 'auto'
                             else int(io_readahead or 0))
            calibrate_mode = autotune_options['calibrate']
            calibration_schema = view_schema

            def calibration_fn():
                # probes (if any) run on the controller thread, never the
                # hot path; 'cached' never probes at all
                if not _profiler.profiler_enabled():
                    return None
                return _profiler.get_calibration(
                    self._filesystem_factory(), self._dataset_path,
                    self._pieces, calibration_schema, mode=calibrate_mode)

            self._controller = PipelineController(
                ReaderActuators(
                    pool, ventilator=self._ventilator,
                    pool_type=self._pool_type,
                    resize_timeout_s=float(
                        autotune_options['resize_timeout_s']),
                    initial_readahead=initial_depth),
                self._stats_snapshot,
                calibration_fn=calibration_fn,
                latency=getattr(pool.stats, 'latency', None),
                slo_targets=slo or {},
                options=autotune_options,
                arbiter=HostArbiter(
                    scratch_dir(autotune_options),
                    cpu_count=os.cpu_count() or 1,
                    tick_interval_s=autotune_options['tick_interval_s']))
            self._controller.start()
        pool_heartbeats = getattr(pool, 'heartbeats', None)
        if pool_heartbeats is not None:
            self.health.add_source(pool_heartbeats)
        resolved_debug_port = resolve_debug_port(debug_port)
        if stall_timeout or resolved_debug_port is not None:
            # on-demand verdicts (/healthz) use the default threshold when no
            # stall_timeout was configured; the background thread only runs
            # when one was (it exists to fire the flight recorder and to
            # cadence the SLO burn accounting)
            self._watchdog = PipelineWatchdog(
                self.health.heartbeats, pool.stats.snapshot,
                stall_after_s=stall_timeout or DEFAULT_STALL_AFTER_S,
                on_stall=self._on_stall, slo_monitor=self._slo)
            if stall_timeout:
                self._watchdog.start()
        if resolved_debug_port is not None:
            from petastorm_tpu.goodput import goodput_enabled
            from petastorm_tpu.podobs import podobs_enabled
            from petastorm_tpu.profiler import profiler_enabled
            observe_fn = None
            podmetrics_fn = None
            if podobs_enabled():
                # pod observability plane (docs/pod_observability.md): this
                # host's one-JSON snapshot on /observe/snapshot, and — when
                # the env names a pod peer list — the aggregated /podmetrics
                from petastorm_tpu.podobs import (PodObserver,
                                                  make_observe_fn,
                                                  pod_peers_from_env)
                observe_fn = make_observe_fn(
                    snapshot_fn=self._stats_snapshot,
                    health_fn=self._watchdog.evaluate,
                    slo_fn=(self._slo.evaluate if self._slo is not None
                            else None),
                    coverage_fn=(self.lineage.coverage_report
                                 if self.lineage.enabled else None),
                    cache_counters_fn=getattr(cache, 'host_counters', None),
                    span_tail_fn=(tracer.tail if tracer is not None
                                  else None),
                    goodput_fn=(self._goodput_route if goodput_enabled()
                                else None))
                pod_peers = pod_peers_from_env()
                if pod_peers:
                    podmetrics_fn = PodObserver(pod_peers).report
            self._debug_server = DebugServer(
                self._watchdog.evaluate, self._stats_snapshot,
                self.health.heartbeats, port=resolved_debug_port,
                coverage_fn=(self.lineage.coverage_report
                             if self.lineage.enabled else None),
                profile_fn=(self._profile_route if profiler_enabled()
                            else None),
                slo_fn=(self._slo.evaluate if self._slo is not None
                        else None),
                autotune_fn=(self._controller.report
                             if self._controller is not None else None),
                observe_fn=observe_fn,
                podmetrics_fn=podmetrics_fn,
                goodput_fn=(self._goodput_route if goodput_enabled()
                            else None))
            try:
                self._debug_server.start()
            except (OSError, OverflowError) as e:   # taken / out-of-range port
                # A taken port must not kill the pipeline it observes: with
                # PETASTORM_TPU_DEBUG_PORT set job-wide, the SECOND reader in
                # the job would otherwise crash at construction. The watchdog
                # stays armed; only this reader's endpoint is missing.
                logger.warning(
                    'debug endpoint disabled: could not bind 127.0.0.1:%d '
                    '(%s); pass debug_port=0 for an ephemeral port per '
                    'reader', resolved_debug_port, e)
                self._debug_server = None
        self._results_reader = results_reader_factory(transformed_schema,
                                                      self.ngram,
                                                      lineage=self.lineage)
        self._stopped = False
        #: True when every published NGram item is a columnar
        #: :class:`~petastorm_tpu.ngram.NGramWindowChunk` (no per-row
        #: predicate/transform/filters work item exists) — the JAX loader's
        #: vectorized collation path keys off this.
        self.ngram_chunked = (self.ngram is not None
                              and transform_spec is None
                              and worker_predicate is None
                              and filters_predicate is None)

    @property
    def batched_output(self) -> bool:
        return self._is_batched_reader

    # -- filtering -------------------------------------------------------------

    def _filter_row_groups(self, filesystem, pieces, stored_schema, predicate,
                           rowgroup_selector, filters, cur_shard, shard_count,
                           footer_cache=None):
        # Row-group indexes (rowgroup_selector) are built over the full
        # load_row_groups() ordering; carry each piece's original ordinal so
        # selection stays aligned after predicate/filters pruning.
        indexed = list(enumerate(pieces))
        worker_predicate = None
        filters_predicate = None
        partition_keys = (set(pieces[0].partition_dict.keys()) if pieces else set())
        if predicate is not None:
            predicate_fields = set(predicate.get_fields())
            unknown = predicate_fields - set(stored_schema.fields.keys())
            if unknown:
                raise ValueError('Predicate uses unknown fields: {}'.format(sorted(unknown)))
            if predicate_fields and predicate_fields <= partition_keys:
                # Evaluate on partition values only: prune pieces with no reads
                # (reference reader.py:577-608).
                indexed = [(i, p) for i, p in indexed if predicate.do_include(
                    {f: _cast_partition(stored_schema, f, p.partition_dict[f])
                     for f in predicate_fields})]
            else:
                worker_predicate = predicate

        conjunctions = normalize_filters(filters) if filters is not None else None
        if conjunctions:
            filter_cols = set(filter_column_names(conjunctions))
            # hive partition columns may be absent from the stored schema
            unknown = filter_cols - set(stored_schema.fields.keys()) - partition_keys
            if unknown:
                raise ValueError('filters use unknown columns: {}'.format(
                    sorted(unknown)))
            validate_filter_types(conjunctions, stored_schema, partition_keys)
            # Planning: exact on partition values, conservative on row-group
            # min/max statistics (reference delegates both to pyarrow,
            # reader.py:399-401). Pruning never decides inclusion on its own —
            # any non-partition term also pushes the full DNF down to the
            # workers so the result is row-exact. The partition-only pass runs
            # first so footers are only fetched for pieces it cannot prune.
            stats = RowGroupStatsEvaluator(filesystem, stored_schema,
                                           preloaded_footers=footer_cache)
            indexed = [(i, p) for i, p in indexed
                       if stats.piece_maybe_matches(p, conjunctions,
                                                    partition_only=True)]
            if filter_cols - partition_keys:
                stats.prefetch_footers({p.path for _, p in indexed})
                indexed = [(i, p) for i, p in indexed
                           if stats.piece_maybe_matches(p, conjunctions)]
                # row-exact residual; specialized per piece at ventilation
                # time (partition terms are constants for a given piece, and
                # may name columns the stored schema doesn't even declare)
                filters_predicate = FiltersPredicate(conjunctions)

        if rowgroup_selector is not None:
            from petastorm_tpu.etl.rowgroup_indexing import get_row_group_indexes
            indexes = get_row_group_indexes(filesystem, self._dataset_path)
            missing = set(rowgroup_selector.get_index_names()) - set(indexes.keys())
            if missing:
                raise ValueError('Selector references unknown indexes: {}'.format(
                    sorted(missing)))
            selected = rowgroup_selector.select_row_groups(indexes)
            indexed = [(i, p) for i, p in indexed if i in selected]

        pieces = [p for _, p in indexed]
        if cur_shard is not None:
            if len(pieces) < shard_count:
                # Fail loudly like the reference (reader.py:547-549): a
                # silently empty shard surprises users — and in SPMD training
                # it deadlocks the collectives of every other host.
                raise NoDataAvailableError(
                    'Dataset has only {} row groups after pruning but {} '
                    'shards were requested; some shards would receive no '
                    'data'.format(len(pieces), shard_count))
            pieces = [p for i, p in enumerate(pieces) if i % shard_count == cur_shard]
        return pieces, worker_predicate, filters_predicate

    # -- iteration -------------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        try:
            row = self._results_reader.read_next(self._pool)
        except EmptyResultError:
            self.last_row_consumed = True
            raise StopIteration
        if self._e2e_live:
            # one end-to-end observation per delivered ITEM (row readers
            # yield many rows per item: record on the seq edge only)
            seq = self._results_reader.last_seq
            if seq is not None and seq != self._last_e2e_seq:
                self._last_e2e_seq = seq
                ts = self.lineage.ventilated_ts(seq)
                if ts is not None:
                    self._pool.stats.record_latency(
                        'e2e_batch', time.perf_counter() - ts)
        if self.device_decode_plans and not self._device_decode_deferred:
            # no loader claimed the raw columns: keep the "reader yields
            # decoded batches" contract by decoding on the host here (the
            # vectorized reference path, counted as batched host decode)
            row = self._host_decode_raw(row)
        return row

    def _host_decode_raw(self, batch):
        """Host-decode a bytes-through batch's raw planned columns (and run
        a device-flagged transform on the host) — the fallback consumer path
        when :meth:`_defer_device_decode_to_loader` was never called."""
        from petastorm_tpu.ops.decode import decode_raw_host
        updates = {}
        rows = 0
        for name, plan in self.device_decode_plans.items():
            col = getattr(batch, name, None)
            if col is None:
                continue
            updates[name] = decode_raw_host(plan, col)
            # per decoded COLUMN, matching the worker batched path and the
            # device counters — the fractions divide like-for-like
            rows += len(col)
        if updates:
            batch = batch._replace(**updates)
            self._pool.stats.add('rows_decoded_batched', rows)
        if self._device_transform_spec is not None:
            from petastorm_tpu.transform import apply_columnar_transform
            columns = apply_columnar_transform(self._device_transform_spec,
                                               self.schema, batch._asdict())
            batch = batch._replace(**columns)
        return batch

    def _defer_e2e_to_loader(self):
        """Called by ``JaxDataLoader`` when it takes over end-to-end latency
        recording at its own (later) batch-delivery point — the reader's
        per-item recording stops so each delivered unit is observed once."""
        self._e2e_live = False

    def _defer_device_decode_to_loader(self):
        """Called by ``JaxDataLoader`` (and the sharded staging path) when it
        claims the bytes-through columns: raw ``(n, stride)`` uint8 grids pass
        through :meth:`__next__` undecoded and the loader decodes them under
        ``jax.jit`` (fused with any device ``TransformSpec``). Returns
        ``(plans, device_transform_spec)``."""
        self._device_decode_deferred = True
        return self.device_decode_plans, self._device_transform_spec

    def next(self):
        return self.__next__()

    def iter_ngram_chunks(self):
        """Yield raw :class:`~petastorm_tpu.ngram.NGramWindowChunk`s (one per
        row-group work item) instead of per-window namedtuples — the
        zero-per-window-Python feed for vectorized batch collation. Only
        available when :attr:`ngram_chunked`; do not interleave with
        ``next()`` on the same pass."""
        if not self.ngram_chunked:
            # plain method (not a generator) so misuse fails HERE, not at the
            # consumer's first next() in some other component
            raise RuntimeError(
                'iter_ngram_chunks() needs a chunk-mode NGram reader (no '
                'predicate/transform_spec/filters); iterate per-window '
                'instead')

        def chunks():
            while True:
                try:
                    yield self._results_reader.read_next_chunk(self._pool)
                except EmptyResultError:
                    self.last_row_consumed = True
                    return
        return chunks()

    def drain(self):
        """Consume the rest of the stream WITHOUT decoding/collating on the
        consumer side (published items are discarded as-is), leaving the
        reader resettable. Used by the sharded loader's lockstep stop: a host
        whose shard has surplus batches discards them raw instead of paying
        window/batch assembly for data nobody reads."""
        discard = getattr(self._results_reader, 'discard_buffered', None)
        if discard is not None:
            discard()
        tracker = self.lineage if self.lineage.enabled else None
        try:
            while True:
                # register discarded items' provenance so the coverage audit
                # still sees them delivered (dropped-on-purpose != dropped)
                unwrap_envelope(self._pool.get_results(), tracker)
        except EmptyResultError:
            self.last_row_consumed = True

    def reset(self):
        """Restart iteration for another ``num_epochs`` pass; only legal after
        the previous pass fully drained (reference ``reader.py:468-492``)."""
        if not self.last_row_consumed:
            raise RuntimeError(
                'Reader.reset() is only supported after the previous epoch set was '
                'fully consumed (in-flight row groups cannot be recalled)')
        # epoch numbers are globally monotone (the ventilator never rewinds),
        # so the new pass audits against fresh per-epoch ledgers
        self.lineage.start_pass()
        self._ventilator.reset(self._num_epochs)
        self.last_row_consumed = False

    # -- flight recorder -------------------------------------------------------

    def _on_stall(self, verdict):
        if self._slo is not None:
            # edge-triggered upstream: one episode per stall, however long
            self._slo.record_stall_episode()
        try:
            path = self.dump_flight_record(verdict=verdict)
            logger.error('pipeline stalled; flight record written to %s', path)
        except Exception:
            logger.exception('failed to write flight record')

    def dump_flight_record(self, path=None, verdict=None):
        """Write a flight-recorder JSON (heartbeats, stats snapshot, queue
        occupancy, per-thread stacks, span ring tail when tracing is on) and
        return its path. The watchdog calls this automatically on a stall;
        call it directly for an on-demand dump. ``path=None`` names a file
        in ``flight_record_dir`` (or the system temp dir)."""
        if verdict is None:
            if self._watchdog is not None:
                verdict = self._watchdog.evaluate()
            else:
                from petastorm_tpu.health import classify_pipeline
                verdict = classify_pipeline(self.health.heartbeats(),
                                            self._pool.stats.snapshot())
        snapshot = self._pool.stats.snapshot()
        queues = {
            'queue_depth': snapshot.get('queue_depth', 0),
            'queue_depth_max': snapshot.get('queue_depth_max', 0),
            'shuffle_buffer_depth': snapshot.get('shuffle_buffer_depth', 0),
            'readahead_depth': snapshot.get('readahead_depth', 0),
            'prefetch_occupancy': snapshot.get('prefetch_occupancy', 0),
            'prefetch_occupancy_max': snapshot.get('prefetch_occupancy_max',
                                                   0),
        }
        roofline = None
        if self._last_profile is not None:
            from petastorm_tpu.profiler import roofline_summary
            roofline = roofline_summary(self._last_profile)
        latency_plane = getattr(self._pool.stats, 'latency', None)
        slo_verdict = None
        if self._slo is not None:
            try:
                slo_verdict = self._slo.evaluate()
            except Exception:
                logger.exception('SLO evaluation failed for flight record')
        record = build_flight_record(verdict, self.health.heartbeats(),
                                     snapshot, queues, tracer=self.tracer,
                                     lineage=(self.lineage.flight_summary()
                                              if self.lineage.enabled
                                              else None),
                                     roofline=roofline,
                                     latency=(latency_plane.flight_summary()
                                              if latency_plane is not None
                                              else None),
                                     slo=slo_verdict,
                                     autotune=(
                                         self._controller.flight_summary()
                                         if self._controller is not None
                                         else None),
                                     goodput=(
                                         self._goodput.flight_summary()
                                         if self._goodput is not None
                                         else None))
        if path is None:
            import tempfile
            out_dir = self._flight_record_dir or tempfile.gettempdir()
            path = os.path.join(out_dir, 'petastorm_tpu_flight_{}_{}.json'
                                .format(os.getpid(), int(time.time())))
        return write_flight_record(path, record)

    # -- goodput plane (see docs/goodput.md) -----------------------------------

    def register_goodput(self, monitor):
        """Attach a loader's :class:`~petastorm_tpu.goodput.GoodputMonitor`
        so the reader's surfaces (``/goodput``, ``/diagnostics``, flight
        records, the pod observe snapshot) serve its per-step accounting.
        The JAX loaders call this at construction; latest registration
        wins (one live consumer loop per reader)."""
        self._goodput = monitor

    def _goodput_route(self):
        """``GET /goodput`` source: the monitor's summary once a loader
        registered one, else an explicit not-yet-attached marker (the
        plane is on — a 404 would read as kill-switched)."""
        if self._goodput is None:
            return {'enabled': True, 'attached': False}
        return self._goodput.summary()

    # -- roofline profiler (see docs/profiling.md) -----------------------------

    def _stats_snapshot(self):
        """The pool's stats snapshot plus the roofline gauges of the most
        recent :meth:`profile` call (``stage_ceiling_*``,
        ``roofline_fraction``, ``binding_stage``) — what the metrics
        emitter and the debug endpoint's ``/metrics`` serve, so scrapes
        show %-of-ceiling, not just raw samples/s."""
        snapshot = self._pool.stats.snapshot()
        # derived decode-path mix (docs/decode.md): scrapes and flight
        # records should answer "is the device path actually carrying the
        # decode" without re-deriving it from raw counters
        from petastorm_tpu.workers.stats import device_decode_fraction
        fraction = device_decode_fraction(snapshot)
        if fraction is not None:
            snapshot['device_decode_fraction'] = fraction
        if self._roofline_gauges:
            snapshot.update(self._roofline_gauges)
        if self._controller is not None:
            snapshot.update(self._controller.gauges())
        return snapshot

    def profile(self, calibrate='auto', sample_row_groups: int = 3,
                samples_per_sec=None):
        """The roofline profile of this reader right now: measured rate vs
        the calibrated per-stage ceilings of *this host on this dataset*,
        the binding stage, overlap-aware span attribution, and the what-if
        advisor's ranked knob recommendations.

        ``calibrate`` picks how ceilings are obtained: ``'cached'`` only
        loads a previously saved calibration artifact (cheap, never
        probes), ``'auto'`` (default) probes on a cache miss, ``'force'``
        always re-probes. Probes run on the calling thread against sampled
        row groups — seconds of work, on demand, never on the hot path.
        ``samples_per_sec`` overrides the measured rate when the caller
        measured it directly (benchmarks do); otherwise it is estimated
        from the stats window's items/s times the calibrated mean rows per
        row group. See ``docs/profiling.md``."""
        from petastorm_tpu import profiler
        if not profiler.profiler_enabled():
            raise RuntimeError('the roofline profiler is disabled via {}=0'
                               .format(profiler.PROFILER_ENV_VAR))
        # calibrate against the reader's VIEW schema, not the stored one: a
        # column-pruned reader only pays for the columns it decodes, and
        # the digest carries the view so differently-pruned readers over
        # one store never share a calibration artifact
        calibration = profiler.get_calibration(
            self._filesystem_factory(), self._dataset_path, self._pieces,
            self._worker_args['schema'], mode=calibrate,
            sample_row_groups=sample_row_groups)
        spans = self.tracer.spans() if self.tracer is not None else None
        result = profiler.build_profile(
            self._pool.stats.snapshot(), calibration, spans=spans,
            samples_per_sec=samples_per_sec,
            workers_count=self._pool.workers_count,
            io_readahead=self._io_readahead, pool_type=self._pool_type,
            cache_type=self._cache_type)
        self._last_profile = result
        self._roofline_gauges = profiler.roofline_gauges(result)
        return result

    def explain_throughput(self, calibrate='auto') -> str:
        """One sentence: "measured X samples/s = Y% of the binding stage's
        ceiling Z", plus the advisor's top recommendations. Runs
        :meth:`profile` (probing on a calibration-cache miss unless
        ``calibrate='cached'``)."""
        from petastorm_tpu import profiler
        return profiler.explain(self.profile(calibrate=calibrate))

    def _profile_route(self):
        """``GET /profile`` source. An HTTP probe must stay cheap: serve
        the most recent :meth:`profile` result when one exists (periodic
        scrapers must not recompute the dataset digest and span-union
        attribution per request), and only build a fresh cached-calibration
        profile (never probing) before the first ``profile()`` call."""
        if self._last_profile is not None:
            return dict(self._last_profile, from_cache=True)
        fresh = self.profile(calibrate='cached')
        if not fresh.get('calibrated'):
            # don't pin an uncalibrated snapshot: the route stays live
            # until a calibration exists, then starts serving the cache
            self._last_profile = None
            self._roofline_gauges = {}
        return fresh

    # -- lineage (see docs/lineage.md) -----------------------------------------

    @property
    def last_seq(self):
        """Tracker seq of the most recently yielded item (``None`` until the
        first yield or when lineage is off)."""
        return getattr(self._results_reader, 'last_seq', None)

    @property
    def last_row_offset(self):
        """Payload-row offset of the most recently yielded ROW within its
        published item (row readers only; ``None`` for batched output)."""
        return getattr(self._results_reader, 'last_row_offset', None)

    @property
    def last_provenance(self):
        """:class:`~petastorm_tpu.lineage.Provenance` of the most recently
        yielded item/batch (``None`` before the first yield, when lineage is
        off, or after ring eviction)."""
        return self.lineage.resolve(self.last_seq)

    def explain_batch(self, batch=None):
        """Human-readable provenance of a batch.

        ``batch=None`` explains the most recently yielded reader item (for
        batched readers that IS the batch: one row group). A loader batch
        dict carrying ``'_provenance'`` (or a
        :class:`~petastorm_tpu.lineage.BatchProvenance` directly) resolves
        per-row: every distinct source row group with its row count,
        selection and shuffle quality."""
        if batch is None:
            record = self.last_provenance
            if record is None:
                return {'enabled': self.lineage.enabled, 'sources': []}
            return {'enabled': True, 'rows': record.rows,
                    'sources': [dict(record._asdict(),
                                     selection=list(record.selection))]}
        if isinstance(batch, dict):
            batch = batch_provenance_of(batch) or batch
        if isinstance(batch, BatchProvenance):
            return dict(batch.summary(), enabled=True)
        raise TypeError('explain_batch needs None, a loader batch dict with '
                        "a '_provenance' entry, or a BatchProvenance; got "
                        '{!r}'.format(type(batch)))

    def replay(self, provenance):
        """Re-fetch the exact rows behind ``provenance`` (a
        :class:`~petastorm_tpu.lineage.Provenance` record, a registered seq,
        a ``BatchProvenance``, or a loader batch dict) through this reader's
        own row-group machinery. Returns a dict of numpy columns —
        bit-identical to the original delivery for deterministic
        decode/transform paths. See ``docs/lineage.md``."""
        return _lineage_replay(self, provenance)

    def audit(self) -> 'CoverageAuditor':
        """A :class:`~petastorm_tpu.lineage.CoverageAuditor` over this
        reader's ledgers (``audit().report()`` / ``assert_complete()``)."""
        return CoverageAuditor(self.lineage)

    # -- lifecycle -------------------------------------------------------------

    def stop(self):
        """Stop the pipeline. Idempotent, and ordered so the health layer
        (watchdog, emitter) is signalled even when the pool below died
        uncleanly: an unclean pool must never leave monitoring threads
        running against a corpse."""
        self._stopped = True
        if self._controller is not None:
            # signal the controller before the pool goes down: a tick that
            # lands mid-teardown must find the stop event, not a corpse
            self._controller.stop(join=False)
        if self._metrics_emitter is not None:
            self._metrics_emitter.stop(join=False)
        if self._watchdog is not None:
            self._watchdog.stop(join=False)
        try:
            self._pool.stop()
        finally:
            if self._debug_server is not None:
                self._debug_server.stop()

    def join(self):
        """Join every pipeline thread: the pool, then the metrics emitter,
        watchdog and debug server (all with bounded joins). Idempotent —
        every stop below tolerates being called again — so teardown paths
        that cannot know whether an earlier join ran may call it anyway."""
        # the controller joins FIRST: a tick actuating mid-join would race
        # the pool's socket teardown below
        if self._controller is not None:
            self._controller.stop()
        try:
            self._pool.join()
        finally:
            if self._metrics_emitter is not None:
                # joins the emitter thread and writes one final snapshot, so
                # even sub-interval runs record at least one sample
                self._metrics_emitter.stop()
            if self._watchdog is not None:
                self._watchdog.stop()
            if self._debug_server is not None:
                self._debug_server.stop()
        if self._trace_export and self.tracer is not None:
            try:
                self.tracer.export_chrome_trace(self._trace_export)
            except OSError:
                logger.exception('Failed to export chrome trace to %s',
                                 self._trace_export)

    def cleanup(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()
        self.join()

    @property
    def stats(self):
        """The pool's :class:`~petastorm_tpu.workers.stats.ReaderStats` —
        the live per-stage telemetry accumulator. The JAX loaders record
        device staging time into it; ``diagnostics`` snapshots it."""
        return getattr(self._pool, 'stats', None)

    @property
    def slo(self):
        """The reader's :class:`~petastorm_tpu.latency.SLOMonitor` (``None``
        unless built with ``slo=dict(...)``). ``reader.slo.evaluate()`` is
        the on-demand verdict the ``/slo`` route serves."""
        return self._slo

    @property
    def autotune(self):
        """The reader's
        :class:`~petastorm_tpu.autotune.PipelineController` (``None``
        unless autotune resolved on — ``autotune=`` kwarg or
        ``PETASTORM_TPU_AUTOTUNE=1``, minus the kill switch).
        ``reader.autotune.report()`` is what ``/autotune`` serves."""
        return self._controller

    @property
    def latency(self):
        """The pool's :class:`~petastorm_tpu.latency.PipelineLatency` — the
        per-stage streaming histograms (``None`` under the
        ``PETASTORM_TPU_LATENCY=0`` kill switch)."""
        return getattr(self._pool.stats, 'latency', None)

    @property
    def watchdog(self):
        """The reader's :class:`~petastorm_tpu.health.PipelineWatchdog`
        (``None`` unless built with ``stall_timeout=`` or ``debug_port=``).
        ``reader.watchdog.evaluate()`` classifies the pipeline right now."""
        return self._watchdog

    @property
    def debug_port(self):
        """The bound port of the HTTP debug endpoint (``None`` when no
        server runs; differs from the requested port when that was 0)."""
        return self._debug_server.port if self._debug_server is not None \
            else None

    @property
    def tracer(self):
        """The pool's :class:`~petastorm_tpu.tracing.Tracer` (``None`` unless
        the reader was built with ``trace=``/``PETASTORM_TPU_TRACE``). Call
        ``reader.tracer.export_chrome_trace(path)`` for a Perfetto-loadable
        timeline; the JAX loaders record their spans into the same tracer."""
        return getattr(self._pool, 'tracer', None)

    @property
    def diagnostics(self):
        """Pool accounting plus a :class:`ReaderStats` snapshot: per-stage
        wall times (``worker_io_s``/``worker_decode_s``/``serialize_s``/
        ``deserialize_s``/``queue_wait_s``/``device_stage_s``), payload
        bytes/copies/frames, and queue-occupancy gauges."""
        return dict(self._pool.diagnostics)


def _cast_partition(schema, field_name, value):
    field = schema.fields.get(field_name)
    return cast_partition_value(field.numpy_dtype if field is not None else None, value)
