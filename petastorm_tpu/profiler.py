"""Pipeline roofline profiler: calibrated per-stage ceilings, overlap-aware
attribution, and a what-if advisor.

The sensors built in PRs 1-5 (``ReaderStats``, spans, heartbeats) answer
*what the pipeline did*; none of them answer *what the host could have
done*, so a decode-bound line's samples/sec has no measured ceiling to be
judged against. This module is the model layer on top of the sensors (the role
tf.data's AUTOTUNE analysis layer plays over its raw counters):

- **Calibration micro-probes** (:func:`calibrate`) measure this host's
  per-stage ceilings against the *actual dataset*: storage sequential-read
  bandwidth for the dataset's filesystem (plain vs ``pre_buffer`` parquet
  opens — the two open modes the workers pick between), per-codec decode
  throughput over sampled row groups pushed through the real
  ``codecs.py``/``columnar_worker`` decode paths, serializer/transport
  bandwidth (``ZeroCopySerializer`` roundtrip), and host→device staging
  bandwidth via the production ``stage_to_global``. Probes run on demand
  (never on the hot path) and the result is cached as a JSON calibration
  artifact keyed by ``(host, dataset digest)`` — re-probing only when the
  dataset's row-group composition changes.
- **Overlap-aware attribution** (:func:`attribute`) consumes a
  ``ReaderStats`` snapshot plus ``Tracer`` span intervals and produces
  per-stage busy/idle time by **interval union per stage** — readahead,
  decode and infeed deliberately overlap, so naive stage-time sums
  over-count; the union of each stage's span intervals against the observed
  wall is the honest utilization.
- **Roofline verdict** (:func:`build_profile`): "measured X samples/s =
  Y% of the binding stage's ceiling Z", where the binding stage is the
  calibrated stage with the lowest ceiling for the current configuration.
- **What-if advisor** (:func:`advise`): ranked knob recommendations
  (``workers_count``, ``io_readahead``, ``cache_type='shared'``,
  ``reader_pool_type``) with predicted samples/s deltas from the same
  throughput model (:func:`predict_throughput`).

Surfaces: ``reader.profile()`` / ``reader.explain_throughput()``, the
``GET /profile`` route on the debug endpoint, a ``roofline`` section in
flight records and ``infeed_diagnosis``, ``petastorm-tpu-throughput
--profile``, and the ``stage_ceiling_*`` / ``roofline_fraction`` gauges in
``/metrics`` and the metrics emitter. See ``docs/profiling.md``.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import socket
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

#: Environment variable gating the profiler surfaces (default on).
#: ``0``/``false``/``off`` removes the ``/profile`` route and makes
#: ``reader.profile()`` raise — observability layers keep uniform kill
#: switches (``PETASTORM_TPU_HEALTH``, ``PETASTORM_TPU_LINEAGE``, this).
PROFILER_ENV_VAR = 'PETASTORM_TPU_PROFILER'

#: Where calibration artifacts live when ``cache_dir`` is not passed.
CALIBRATION_DIR_ENV_VAR = 'PETASTORM_TPU_CALIBRATION_DIR'

#: Probe-methodology version stamped into every calibration artifact and
#: required on load: a cached ceiling is only comparable to measurements
#: taken through the SAME decode path. Bumped to 2 when the decode probe
#: moved onto the row-group-vectorized path (docs/decode.md) — a per-cell
#: ceiling served against batched measurements would misreport
#: roofline_fraction by up to the batched speedup. Bumped to 3 when the
#: device-decode probe family landed: per-codec entries now record which
#: path (``host-batched`` / ``per-cell`` / ``device``) produced each
#: ceiling, and ``device_decode`` / ``ingest`` ceilings joined the
#: artifact — pre-upgrade artifacts carry neither and must not judge
#: device measurements, so they read as a cache miss. Bumped to 4 when the
#: storage probe gained the coalesced-ranged read mode (``objectstore``):
#: the io ceiling is now max over three open modes, so a version-3 io
#: ceiling would under-report the store a ranged reader actually has.
PROBE_SCHEMA_VERSION = 4

#: Pipeline stages a ceiling is calibrated for, in pipeline order.
#: ``device_decode`` (jitted bytes-through decode) and ``ingest`` (raw
#: payload host→device transfer) are probe-only ceilings reported in the
#: calibration; they never bind the host span attribution.
CEILING_STAGES = ('io', 'decode', 'serialize', 'device_stage')

#: Span name -> attribution stage. Spans whose name is not listed keep their
#: category as the stage (so future span kinds degrade gracefully instead of
#: vanishing from the attribution).
SPAN_STAGE = {
    'parquet_read': 'io',
    'readahead_read': 'io',
    'decode_columns': 'decode',
    'decode_rows': 'decode',
    'transform': 'decode',
    'serialize': 'serialize',
    'deserialize': 'deserialize',
    'device_stage': 'device_stage',
    'train_step': 'train',
    'queue_wait': 'consumer_wait',
    'infeed_wait': 'consumer_wait',
    'process_item': 'worker',
    'ventilate': 'ventilate',
}

#: Stages that mean "waiting, not working": excluded from binding-stage
#: selection (a pipeline is never *bound* by its own idle time).
IDLE_ATTRIBUTION_STAGES = frozenset({'consumer_wait', 'ventilate', 'worker'})

#: A roofline fraction above this is not a fast pipeline, it is a broken
#: measurement: the measured window drained pre-decoded buffers (too short
#: to be steady-state) or the calibration is stale for this host.
SANE_FRACTION_LIMIT = 1.3

_MB = 1024.0 * 1024.0


def profiler_enabled() -> bool:
    """The :data:`PROFILER_ENV_VAR` gate (default on)."""
    value = os.environ.get(PROFILER_ENV_VAR, '').strip().lower()
    return value not in ('0', 'false', 'off')


# ---------------------------------------------------------------------------
# dataset digest + calibration cache
# ---------------------------------------------------------------------------

def dataset_digest(pieces, schema=None) -> str:
    """Content digest of a dataset's row-group composition — every
    ``(path, row_group, num_rows)`` triple — plus the column view when a
    ``schema`` is given. Regenerating a store in place (different rows,
    different grouping) changes the digest, so a stale calibration can
    never be served for it; a pure re-read does not. The view component
    matters because ceilings are per-view: a reader pruned to scalar
    columns decodes orders of magnitude faster than the full image view,
    and the two must not share a calibration artifact."""
    h = hashlib.md5()
    for piece in sorted(pieces, key=lambda p: (str(p.path), p.row_group)):
        h.update('{}:{}:{}\n'.format(piece.path, piece.row_group,
                                     piece.num_rows).encode())
    if schema is not None:
        h.update('view:{}\n'.format(
            ','.join(sorted(schema.fields))).encode())
    return h.hexdigest()[:16]


def calibration_dir(cache_dir: Optional[str] = None) -> str:
    if cache_dir:
        return str(cache_dir)
    env = os.environ.get(CALIBRATION_DIR_ENV_VAR, '').strip()
    if env:
        return env
    return os.path.join(os.path.expanduser('~'), '.cache', 'petastorm_tpu')


def calibration_path(digest: str, cache_dir: Optional[str] = None) -> str:
    """The calibration artifact path for ``(this host, digest)``."""
    host = socket.gethostname().split('.')[0] or 'host'
    return os.path.join(calibration_dir(cache_dir),
                        'roofline_{}_{}.json'.format(host, digest))


def load_calibration(digest: str,
                     cache_dir: Optional[str] = None) -> Optional[dict]:
    """The cached calibration for ``digest`` on this host, or ``None`` on a
    miss, an unreadable artifact, a digest mismatch (defense in depth —
    the digest is in the filename AND the payload), or a probe-version
    mismatch (ceilings measured through an older decode path must not
    judge this one's measurements)."""
    path = calibration_path(digest, cache_dir)
    try:
        with open(path) as f:
            cal = json.load(f)
    except (OSError, ValueError):
        return None
    if cal.get('dataset_digest') != digest:
        return None
    if cal.get('probe_version') != PROBE_SCHEMA_VERSION:
        return None
    return cal


def save_calibration(calibration: dict,
                     cache_dir: Optional[str] = None) -> str:
    from petastorm_tpu.utils import atomic_write
    out_dir = calibration_dir(cache_dir)
    os.makedirs(out_dir, exist_ok=True)
    path = calibration_path(calibration['dataset_digest'], cache_dir)
    return atomic_write(path, lambda f: json.dump(calibration, f, indent=2,
                                                  sort_keys=True))


# ---------------------------------------------------------------------------
# calibration micro-probes
# ---------------------------------------------------------------------------

def _sample_pieces(pieces, sample_row_groups: int):
    """Spread the sampled row groups across the dataset (first/last/middle)
    instead of taking a prefix — a store whose early groups differ from the
    rest (warm page cache, different files) must not skew the ceilings."""
    pieces = list(pieces)
    n = len(pieces)
    k = max(1, min(sample_row_groups, n))
    if k == n:
        return pieces
    step = (n - 1) / (k - 1) if k > 1 else 0
    return [pieces[int(round(i * step))] for i in range(k)]


#: Repetitions per timed probe section; the BEST (minimum-time) rep is the
#: ceiling. Scheduler interference only ever slows a measurement down, so
#: min-of-N is the honest estimator for "what this host can do" — a single
#: timing of a sub-millisecond read under a loaded host reads 2-5x slow,
#: enough to mis-rank io vs decode on small stores.
PROBE_REPS = 5


def _probe_storage(filesystem, sampled) -> dict:
    """Sequential-read bandwidth of the dataset's own files, plus the parquet
    row-group read rate under the three open modes the workers choose between
    (plain for local filesystems, ``pre_buffer=True`` for remote — see
    ``piece_worker._LOCAL_PROTOCOLS`` — and the coalesced parallel-range
    plan of ``objectstore.ParallelRangeReader``, the ranged-ingest
    ceiling). Page-cache state is whatever the
    host has (recorded as ``page_cache: 'ambient'``): these are sustained
    re-read ceilings, the regime epochs 2+ run in."""
    import pyarrow.parquet as pq
    total_bytes = 0
    seq_s = 0.0
    paths = []
    for piece in sampled:
        if piece.path not in paths:
            paths.append(piece.path)
    for path in paths:
        start = time.perf_counter()
        with filesystem.open(path, 'rb') as f:
            while True:
                chunk = f.read(4 * 1024 * 1024)
                if not chunk:
                    break
                total_bytes += len(chunk)
        seq_s += time.perf_counter() - start

    def timed_read(pre_buffer: bool) -> Tuple[float, int]:
        read_s, rows = 0.0, 0
        for piece in sampled:
            handle = filesystem.open(piece.path, 'rb')
            try:
                if pre_buffer:
                    try:
                        pf = pq.ParquetFile(handle, pre_buffer=True)
                    except TypeError:     # pyarrow predating the kwarg
                        pf = pq.ParquetFile(handle)
                else:
                    pf = pq.ParquetFile(handle)
                start = time.perf_counter()
                table = pf.read_row_group(piece.row_group)
                read_s += time.perf_counter() - start
                rows += table.num_rows
            finally:
                handle.close()
        return read_s, rows

    def timed_ranged_read() -> Tuple[float, int]:
        from petastorm_tpu.objectstore import ParallelRangeReader
        reader = ParallelRangeReader(filesystem)
        read_s, rows = 0.0, 0
        for piece in sampled:
            start = time.perf_counter()
            table = reader.read_row_group(piece.path, piece.row_group)
            read_s += time.perf_counter() - start
            rows += table.num_rows
        return read_s, rows

    plain_s, rows = min(timed_read(pre_buffer=False)
                        for _ in range(PROBE_REPS))
    pre_s, _ = min(timed_read(pre_buffer=True) for _ in range(PROBE_REPS))
    ranged_s, _ = min(timed_ranged_read() for _ in range(PROBE_REPS))
    return {
        'page_cache': 'ambient',
        'bytes': total_bytes,
        'seq_read_mb_per_s': round(total_bytes / _MB / seq_s, 2)
        if seq_s else None,
        'parquet_rows_per_s': round(rows / plain_s, 1) if plain_s else None,
        'parquet_pre_buffer_rows_per_s': round(rows / pre_s, 1)
        if pre_s else None,
        'parquet_ranged_rows_per_s': round(rows / ranged_s, 1)
        if ranged_s else None,
        'parquet_read_s': round(plain_s, 4),
        'rows': rows,
    }


def _probe_decode(filesystem, sampled, schema) -> dict:
    """Per-codec decode throughput through the REAL decode path
    (``columnar_worker._column_to_numpy``, honoring each field's codec and
    the same batched/per-cell routing the workers use — the ceiling must
    measure the path the pipeline runs) over the sampled row groups. One
    untimed pass warms codec imports and the column buffers; the timed
    pass is the single-core decode ceiling. Each per-codec entry records
    the cells decoded by the vectorized path (``batched_rows``) so the
    calibration artifact shows which ceilings are batched-path numbers."""
    import pyarrow.parquet as pq

    from petastorm_tpu.codecs import batched_decode_enabled
    from petastorm_tpu.readers.columnar_worker import _column_to_numpy
    batched = batched_decode_enabled()
    names = [name for name, field in schema.fields.items()]
    per_codec: Dict[str, dict] = {}
    rows = 0
    total_s = 0.0
    decoded_bytes = 0
    for piece in sampled:
        handle = filesystem.open(piece.path, 'rb')
        try:
            table = pq.ParquetFile(handle).read_row_group(piece.row_group)
        finally:
            handle.close()
        present = [n for n in names if n in table.column_names]
        # warm pass: codec imports, lazy cv2 init, chunk materialization
        for name in present:
            _column_to_numpy(table.column(name), schema.fields[name], None,
                             batched=batched)
        n = table.num_rows
        rows += n
        for name in present:
            field = schema.fields[name]
            elapsed, out = None, None
            path_counts = {'batched': 0, 'percell': 0}
            for _ in range(PROBE_REPS):
                path_counts = {'batched': 0, 'percell': 0}
                start = time.perf_counter()
                out = _column_to_numpy(table.column(name), field, None,
                                       batched=batched,
                                       path_counts=path_counts)
                took = time.perf_counter() - start
                elapsed = took if elapsed is None else min(elapsed, took)
            total_s += elapsed
            codec = field.codec
            label = type(codec).__name__ if codec is not None else 'none'
            image_format = getattr(codec, '_image_codec', None)
            if image_format:
                label = '{}({})'.format(label, str(image_format).lstrip('.'))
            entry = per_codec.setdefault(label, {'rows': 0, 'seconds': 0.0,
                                                 'decoded_bytes': 0,
                                                 'batched_rows': 0,
                                                 'percell_rows': 0})
            entry['rows'] += n
            entry['seconds'] += elapsed
            entry['batched_rows'] += path_counts['batched']
            entry['percell_rows'] += path_counts['percell']
            nbytes = getattr(out, 'nbytes', 0)
            entry['decoded_bytes'] += int(nbytes)
            decoded_bytes += int(nbytes)
    for entry in per_codec.values():
        entry['rows_per_s'] = (round(entry['rows'] / entry['seconds'], 1)
                               if entry['seconds'] else None)
        entry['mb_per_s'] = (round(entry['decoded_bytes'] / _MB
                                   / entry['seconds'], 1)
                             if entry['seconds'] else None)
        entry['seconds'] = round(entry['seconds'], 4)
        # which path produced this ceiling (probe_version 3): a device
        # measurement judged against a per-cell ceiling — or vice versa —
        # would mis-grade by the whole path speedup
        if entry['batched_rows'] >= entry['percell_rows'] \
                and entry['batched_rows']:
            entry['path'] = 'host-batched'
        elif entry['percell_rows']:
            entry['path'] = 'per-cell'
        else:
            entry['path'] = 'host-native'
    return {
        'rows': rows,
        'seconds': round(total_s, 4),
        'rows_per_s': round(rows / total_s, 1) if total_s else None,
        'decoded_mb_per_s': round(decoded_bytes / _MB / total_s, 1)
        if total_s else None,
        'per_codec': per_codec,
        'decoded_bytes': decoded_bytes,
    }


def _decode_sample_columns(filesystem, sampled, schema) -> Tuple[dict, int]:
    """One decoded row group's columns (numpy dict) for the transport and
    staging probes — the actual payload shape the pipeline ships."""
    import pyarrow.parquet as pq

    from petastorm_tpu.readers.columnar_worker import _column_to_numpy
    piece = sampled[0]
    handle = filesystem.open(piece.path, 'rb')
    try:
        table = pq.ParquetFile(handle).read_row_group(piece.row_group)
    finally:
        handle.close()
    columns = {}
    for name, field in schema.fields.items():
        if name in table.column_names:
            columns[name] = _column_to_numpy(table.column(name), field, None)
    return columns, table.num_rows


def _probe_serialize(columns: dict, rows: int) -> dict:
    """``ZeroCopySerializer`` roundtrip bandwidth on a real decoded payload —
    the worker→consumer transport ceiling for process pools (in-process pools
    skip this stage entirely; their ceiling is effectively infinite)."""
    from petastorm_tpu.workers.serializers import ZeroCopySerializer
    serializer = ZeroCopySerializer()
    frames = serializer.serialize_multipart(columns)     # warm
    serializer.deserialize_multipart(frames)
    payload_bytes = sum(getattr(v, 'nbytes', 0) for v in columns.values())
    elapsed = None
    for _ in range(PROBE_REPS):
        start = time.perf_counter()
        frames = serializer.serialize_multipart(columns)
        serializer.deserialize_multipart(frames)
        took = time.perf_counter() - start
        elapsed = took if elapsed is None else min(elapsed, took)
    return {
        'rows': rows,
        'payload_bytes': int(payload_bytes),
        'seconds': round(elapsed, 6),
        'rows_per_s': round(rows / elapsed, 1) if elapsed else None,
        'mb_per_s': round(payload_bytes / _MB / elapsed, 1)
        if elapsed else None,
    }


def _probe_device_stage(columns: dict, rows: int) -> Optional[dict]:
    """Host→device staging bandwidth through the production
    :func:`~petastorm_tpu.jax_utils.stage_to_global` on a replicated
    single-device sharding. ``None`` when no jax backend initializes (the
    profiler must work on a read-only host with no accelerator runtime)."""
    try:
        import jax
        import numpy as np

        from petastorm_tpu.jax_utils import stage_to_global
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ('data',))
        sharding = jax.sharding.NamedSharding(mesh,
                                              jax.sharding.PartitionSpec())
        staged = stage_to_global(columns, sharding)          # warm + compile
        jax.block_until_ready({k: v for k, v in staged.items()
                               if k != '_host'})
        payload_bytes = sum(getattr(v, 'nbytes', 0)
                            for v in columns.values())
        elapsed = None
        for _ in range(PROBE_REPS):
            start = time.perf_counter()
            staged = stage_to_global(columns, sharding)
            jax.block_until_ready({k: v for k, v in staged.items()
                                   if k != '_host'})
            took = time.perf_counter() - start
            elapsed = took if elapsed is None else min(elapsed, took)
    except Exception as e:  # noqa: BLE001 - probe must degrade, not raise
        logger.debug('device-stage probe unavailable: %r', e)
        return None
    return {
        'rows': rows,
        'payload_bytes': int(payload_bytes),
        'seconds': round(elapsed, 6),
        'rows_per_s': round(rows / elapsed, 1) if elapsed else None,
        'mb_per_s': round(payload_bytes / _MB / elapsed, 1)
        if elapsed else None,
    }


def _raw_sample_columns(filesystem, sampled, schema) -> Optional[Tuple]:
    """``(plans, raw_columns, rows)`` for the bytes-through probes: the
    device-decode plans of this view plus one sampled row group's raw
    ``(n, stride)`` uint8 grids, or ``None`` when nothing plans (host-matrix
    store, kill switch off, no jax backend)."""
    import pyarrow.parquet as pq

    from petastorm_tpu.ops.decode import (plan_device_decode, raw_column_view,
                                          repack_to_raw)
    from petastorm_tpu.readers.columnar_worker import _column_to_numpy
    plans, _ = plan_device_decode(schema)
    if not plans:
        return None
    piece = sampled[0]
    handle = filesystem.open(piece.path, 'rb')
    try:
        table = pq.ParquetFile(handle).read_row_group(piece.row_group)
    finally:
        handle.close()
    raw_columns = {}
    for name, plan in plans.items():
        if name not in table.column_names:
            continue
        raw = raw_column_view(table.column(name), plan)
        if raw is None:
            decoded = _column_to_numpy(table.column(name),
                                       schema.fields[name], None)
            raw = repack_to_raw(plan, decoded)
        raw_columns[name] = raw
    if not raw_columns:
        return None
    return plans, raw_columns, table.num_rows


def _probe_device_decode(plans, raw_columns, rows) -> Optional[dict]:
    """The jitted bytes-through decode ceiling (docs/decode.md): header-strip
    + bitcast + reshape under ``jax.jit`` over resident raw grids — compute
    only, no transfer (the :func:`_probe_ingest` twin measures that). The
    pair says which wall is next once decode moves off the host: device
    decode FLOPs or the PCIe/ICI ingest link. ``None`` when no jax backend
    initializes."""
    try:
        import jax

        from petastorm_tpu.ops.decode import build_fused_infeed
        fused = build_fused_infeed(plans)
        staged = {name: jax.device_put(col)
                  for name, col in raw_columns.items()}
        jax.block_until_ready(fused(staged))          # warm + compile
        decoded_bytes = sum(rows * plans[name].cell_nbytes
                            for name in raw_columns)
        elapsed = None
        for _ in range(PROBE_REPS):
            start = time.perf_counter()
            jax.block_until_ready(fused(staged))
            took = time.perf_counter() - start
            elapsed = took if elapsed is None else min(elapsed, took)
    except Exception as e:  # noqa: BLE001 - probe must degrade, not raise
        logger.debug('device-decode probe unavailable: %r', e)
        return None
    return {
        'rows': rows,
        'columns': sorted(raw_columns),
        'path': 'device',
        'decoded_bytes': int(decoded_bytes),
        'seconds': round(elapsed, 6),
        'rows_per_s': round(rows / elapsed, 1) if elapsed else None,
        'mb_per_s': round(decoded_bytes / _MB / elapsed, 1)
        if elapsed else None,
    }


def _probe_ingest(raw_columns, rows) -> Optional[dict]:
    """Raw-payload host→device transfer ceiling: ``jax.device_put`` of the
    exact ``(n, stride)`` uint8 grids a bytes-through reader ships — the
    PCIe/ICI ingest bandwidth PAPER §5.8 names as the intended pipeline
    ceiling. ``None`` when no jax backend initializes."""
    try:
        import jax
        payload_bytes = sum(col.nbytes for col in raw_columns.values())
        jax.block_until_ready(
            {k: jax.device_put(v) for k, v in raw_columns.items()})  # warm
        elapsed = None
        for _ in range(PROBE_REPS):
            start = time.perf_counter()
            jax.block_until_ready(
                {k: jax.device_put(v) for k, v in raw_columns.items()})
            took = time.perf_counter() - start
            elapsed = took if elapsed is None else min(elapsed, took)
    except Exception as e:  # noqa: BLE001 - probe must degrade, not raise
        logger.debug('ingest probe unavailable: %r', e)
        return None
    return {
        'rows': rows,
        'payload_bytes': int(payload_bytes),
        'seconds': round(elapsed, 6),
        'rows_per_s': round(rows / elapsed, 1) if elapsed else None,
        'mb_per_s': round(payload_bytes / _MB / elapsed, 1)
        if elapsed else None,
    }


def calibrate(filesystem, dataset_path, pieces, schema,
              sample_row_groups: int = 3,
              cache_dir: Optional[str] = None,
              save: bool = True) -> dict:
    """Run every micro-probe against ``sample_row_groups`` row groups of the
    actual dataset and return (and, with ``save``, cache) the calibration
    artifact. All ceilings are rows/sec for THIS dataset's rows on THIS
    host — per-stage, single-stream (the advisor's model scales them)."""
    digest = dataset_digest(pieces, schema)
    sampled = _sample_pieces(pieces, sample_row_groups)
    storage = _probe_storage(filesystem, sampled)
    decode = _probe_decode(filesystem, sampled, schema)
    columns, sample_rows = _decode_sample_columns(filesystem, sampled, schema)
    serialize = _probe_serialize(columns, sample_rows)
    device = _probe_device_stage(columns, sample_rows)
    # bytes-through probe family (docs/decode.md "Device-side decode"):
    # measured only when this view actually plans device columns
    raw_sample = _raw_sample_columns(filesystem, sampled, schema)
    device_decode = ingest = None
    if raw_sample is not None:
        plans, raw_columns, raw_rows = raw_sample
        device_decode = _probe_device_decode(plans, raw_columns, raw_rows)
        ingest = _probe_ingest(raw_columns, raw_rows)
    total_rows = sum(max(0, p.num_rows) for p in pieces)
    # the fastest of the open modes is the storage ceiling: the workers
    # pick per filesystem (and ``remote_read='ranged'`` by request), and
    # the roofline should not punish a dataset for the mode it does not use
    io_rates = [r for r in (storage.get('parquet_rows_per_s'),
                            storage.get('parquet_pre_buffer_rows_per_s'),
                            storage.get('parquet_ranged_rows_per_s'))
                if r]
    ceilings = {
        'io': max(io_rates) if io_rates else None,
        'decode': decode.get('rows_per_s'),
        'serialize': serialize.get('rows_per_s'),
        'device_stage': device.get('rows_per_s') if device else None,
        'device_decode': (device_decode.get('rows_per_s')
                          if device_decode else None),
        'ingest': ingest.get('rows_per_s') if ingest else None,
    }
    calibration = {
        'kind': 'petastorm_tpu_roofline_calibration',
        'probe_version': PROBE_SCHEMA_VERSION,
        'host': socket.gethostname(),
        'cpu_count': os.cpu_count() or 1,
        'dataset_path': str(dataset_path),
        'dataset_digest': digest,
        # deliberate wall clock: artifact timestamp for humans, never
        # compared against monotonic readings
        'written_at': time.time(),  # petalint: disable=monotonic-clock
        'sampled_row_groups': len(sampled),
        'sampled_rows': decode['rows'],
        'total_rows': total_rows,
        'rows_per_group': (decode['rows'] / len(sampled)) if sampled else 0,
        'probes': {
            'storage': storage,
            'decode': decode,
            'serialize': serialize,
            'device_stage': device,
            'device_decode': device_decode,
            'ingest': ingest,
        },
        'ceilings': ceilings,
    }
    if save:
        try:
            save_calibration(calibration, cache_dir)
        except OSError:
            logger.warning('could not cache calibration artifact',
                           exc_info=True)
    return calibration


def get_calibration(filesystem, dataset_path, pieces, schema,
                    mode: str = 'auto',
                    sample_row_groups: int = 3,
                    cache_dir: Optional[str] = None) -> Optional[dict]:
    """Resolve a calibration per ``mode``: ``'cached'`` loads the artifact
    or returns ``None`` (never probes — safe for hot paths and HTTP
    handlers that must stay cheap); ``'auto'`` loads the artifact and
    probes on a miss; ``'force'`` always re-probes."""
    if mode not in ('cached', 'auto', 'force'):
        raise ValueError("calibration mode must be 'cached', 'auto' or "
                         "'force'; got {!r}".format(mode))
    digest = dataset_digest(pieces, schema)
    if mode in ('cached', 'auto'):
        cal = load_calibration(digest, cache_dir)
        if cal is not None or mode == 'cached':
            return cal
    return calibrate(filesystem, dataset_path, pieces, schema,
                     sample_row_groups=sample_row_groups,
                     cache_dir=cache_dir)


# ---------------------------------------------------------------------------
# overlap-aware attribution
# ---------------------------------------------------------------------------

def interval_union(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals. THE
    attribution primitive: two overlapped 1s decode spans are 1s of decode
    wall, not 2 — summing stage durations double-counts exactly the overlap
    the pipeline exists to create."""
    merged = 0.0
    current_start = current_end = None
    # normalize BEFORE sorting: a reversed (end, start) tuple sorted raw
    # breaks the merge invariant (its true start can precede tuples already
    # consumed)
    for start, end in sorted((e, s) if e < s else (s, e)
                             for s, e in intervals):
        if current_end is None:
            current_start, current_end = start, end
        elif start > current_end:
            merged += current_end - current_start
            current_start, current_end = start, end
        elif end > current_end:
            current_end = end
    if current_end is not None:
        merged += current_end - current_start
    return merged


def attribute(spans: Sequence, wall_s: Optional[float] = None,
              snapshot: Optional[dict] = None) -> dict:
    """Per-stage busy/idle attribution from recorded span tuples
    (``Tracer.spans()``: ``(name, cat, start_s, dur_s, pid, tid, args)``).

    Per stage, the busy time is the **interval union** of that stage's
    spans across every track; ``busy_fraction`` divides by the observed
    wall (max span end − min span start unless ``wall_s`` is given). The
    ``critical`` stage is the busiest non-idle stage — with overlap, stage
    fractions do not sum to 1, and the binding constraint is whichever
    stage the wall clock cannot escape. ``overlap_s`` quantifies the win:
    sum of stage busy times minus their global union (0 = fully serial).

    With no spans (tracing off) and a ``snapshot``, falls back to the
    aggregate ``ReaderStats`` stage times — flagged ``'source':
    'snapshot'``, since aggregate sums cannot see overlap across workers.
    """
    spans = list(spans or ())
    if not spans:
        out = {'source': 'snapshot', 'wall_s': None, 'stages': {},
               'critical_stage': None, 'overlap_s': None}
        if snapshot:
            wall = snapshot.get('window_s') or wall_s
            out['wall_s'] = wall
            from petastorm_tpu.workers.stats import effective_io_s
            # same canonical stage names as the spans path, so consumers
            # can join stages[critical_stage] regardless of trace mode;
            # these sum ACROSS workers, so fractions can exceed 1 (flagged
            # by source='snapshot' — only spans see overlap)
            named = {
                'io': effective_io_s(snapshot),
                'decode': snapshot.get('worker_decode_s', 0.0),
                'serialize': snapshot.get('serialize_s', 0.0),
                'deserialize': snapshot.get('deserialize_s', 0.0),
                'device_stage': snapshot.get('device_stage_s', 0.0),
                'consumer_wait': (snapshot.get('queue_wait_s', 0.0)
                                  + snapshot.get('worker_publish_wait_s',
                                                 0.0)),
            }
            for stage, busy in named.items():
                if busy:
                    out['stages'][stage] = {
                        'busy_s': round(busy, 4),
                        'busy_fraction': round(busy / wall, 4)
                        if wall else None,
                    }
            active = {stage: busy for stage, busy in named.items()
                      if busy and stage not in IDLE_ATTRIBUTION_STAGES}
            if active:
                out['critical_stage'] = max(active, key=active.get)
        return out

    by_stage: Dict[str, List[Tuple[float, float]]] = {}
    starts, ends = [], []
    everything = []
    for name, cat, start_s, dur_s, _pid, _tid, _args in spans:
        stage = SPAN_STAGE.get(name, cat or 'other')
        end = start_s + max(0.0, dur_s)
        by_stage.setdefault(stage, []).append((start_s, end))
        everything.append((start_s, end))
        starts.append(start_s)
        ends.append(end)
    wall = wall_s if wall_s else (max(ends) - min(starts))
    stages = {}
    busy_sum = 0.0
    for stage, intervals in sorted(by_stage.items()):
        busy = interval_union(intervals)
        stages[stage] = {
            'spans': len(intervals),
            'busy_s': round(busy, 4),
            'busy_fraction': round(busy / wall, 4) if wall else None,
        }
        if stage not in IDLE_ATTRIBUTION_STAGES:
            busy_sum += busy
    active = {stage: info['busy_s'] for stage, info in stages.items()
              if stage not in IDLE_ATTRIBUTION_STAGES}
    critical = max(active, key=active.get) if active else None
    return {
        'source': 'spans',
        'wall_s': round(wall, 4),
        'stages': stages,
        'critical_stage': critical,
        # how much stage work ran concurrently: serial sum minus the union
        'overlap_s': round(max(0.0, busy_sum - interval_union(everything)), 4),
    }


# ---------------------------------------------------------------------------
# throughput model + roofline profile
# ---------------------------------------------------------------------------

def predict_throughput(ceilings: dict, workers: int = 1,
                       cpu_count: Optional[int] = None,
                       io_overlap: bool = False,
                       in_process: bool = True,
                       cached: bool = False,
                       worker_efficiency: float = 1.0) -> Optional[float]:
    """Predicted samples/s from calibrated single-stream ceilings.

    The model (docs/profiling.md "Attribution math"):

    - decode scales with effective parallel workers ``min(workers,
      cpu_count)`` (workers beyond cores time-slice, they do not add
      decode), damped by ``worker_efficiency``
      — the *measured* marginal value of each extra worker. ``1.0`` is
      ideal scaling (the default and the old behavior); ``0.0`` means
      extra workers add nothing; **negative** values model a GIL convoy
      (on ~10µs decode calls, sub-GIL-quantum work makes thread workers
      serialize on the lock instead of the codecs). Effective parallelism is
      ``1 + worker_efficiency * (eff_workers - 1)``, floored at 0.05 so a
      pathological factor predicts "much slower", never zero;
    - storage is a shared resource (no worker scaling);
    - without readahead a worker serializes read→decode, so the combined
      rate is harmonic (``1/(1/io + 1/decode)``); with ``io_overlap``
      (readahead) it is ``min(io, decode)``;
    - process pools additionally cap at the serializer ceiling,
      in-process pools skip that stage;
    - device staging caps everything (it is downstream of any cache);
    - ``cached`` (warm shared/local tier) skips io+decode entirely.

    Monotone in ``workers`` by construction **for non-negative
    worker_efficiency** — every term is then nondecreasing in the
    effective worker count (the advisor's monotonicity contract, asserted
    in tests). A negative measured factor deliberately breaks monotonicity:
    that is the point (the model must be able to predict that removing a
    worker is the winning move).
    """
    io = ceilings.get('io')
    decode = ceilings.get('decode')
    caps = []
    if not cached:
        eff = max(1, min(workers, cpu_count or workers))
        parallel = max(0.05, 1.0 + worker_efficiency * (eff - 1))
        scaled_decode = decode * parallel if decode else None
        if io and scaled_decode:
            if io_overlap:
                caps.append(min(io, scaled_decode))
            else:
                caps.append(1.0 / (1.0 / io + 1.0 / scaled_decode))
        elif scaled_decode:
            caps.append(scaled_decode)
        elif io:
            caps.append(io)
    if not in_process and ceilings.get('serialize'):
        caps.append(ceilings['serialize'])
    if ceilings.get('device_stage'):
        caps.append(ceilings['device_stage'])
    if not caps and cached:
        # no post-cache stage was calibrated (in-process pool, no jax
        # backend for the staging probe): the measurable FLOOR is the best
        # uncached configuration — a warm cache can only beat it, so the
        # model must not predict nothing at all
        return predict_throughput(ceilings, workers=cpu_count or workers,
                                  cpu_count=cpu_count, io_overlap=True,
                                  in_process=in_process, cached=False)
    if not caps:
        return None
    return min(caps)


def measured_worker_efficiency(measured_samples_per_s,
                               decode_ceiling,
                               workers: int) -> Optional[float]:
    """The per-worker efficiency factor implied by a *measured* rate on a
    decode-bound pipeline: solve ``measured = ceiling * (1 + e*(w-1))`` for
    ``e``, clamped to ``[-1, 1]``. ``None`` when underdetermined (one
    worker, or no decode ceiling) — with one worker the marginal value of a
    second is unknowable until tried, which is exactly why the autotune
    controller pairs this model with revert-on-regression.

    A GIL convoy (two workers slower than one) implies a strongly negative
    ``e``, and the model then predicts the *removal* of a worker as a
    gain."""
    if workers is None or workers <= 1:
        return None
    if not decode_ceiling or not measured_samples_per_s:
        return None
    e = (measured_samples_per_s / decode_ceiling - 1.0) / (workers - 1)
    return max(-1.0, min(1.0, e))


def build_profile(snapshot: dict, calibration: Optional[dict] = None,
                  spans: Optional[Sequence] = None,
                  samples_per_sec: Optional[float] = None,
                  workers_count: Optional[int] = None,
                  io_readahead=0, pool_type: str = 'thread',
                  cache_type: str = 'null') -> dict:
    """Assemble the roofline profile: measured rate, calibrated ceilings,
    the binding stage, the %-of-ceiling verdict, overlap-aware attribution,
    and the advisor's ranked recommendations. Everything JSON-able."""
    measured = samples_per_sec
    estimated = False
    if measured is None:
        items_per_s = snapshot.get('items_per_s') or 0.0
        rows_per_group = (calibration or {}).get('rows_per_group') or 0
        if items_per_s and rows_per_group:
            # the stats layer counts published items (row groups for
            # columnar/batch readers); scale by the calibrated mean rows
            # per group to talk samples/s like the benchmarks do
            measured = items_per_s * rows_per_group
            estimated = True
        else:
            measured = items_per_s
    profile = {
        'kind': 'petastorm_tpu_roofline_profile',
        'measured_samples_per_s': round(measured, 2) if measured else 0.0,
        'measured_is_estimated_from_items': estimated,
        'attribution': attribute(spans, snapshot=snapshot),
        'config': {'workers_count': workers_count,
                   'io_readahead': io_readahead,
                   'pool_type': pool_type,
                   'cache_type': cache_type},
    }
    if calibration is None:
        profile['calibrated'] = False
        profile['ceilings'] = {}
        profile['binding_stage'] = None
        profile['roofline_fraction'] = None
        return profile
    ceilings = dict(calibration.get('ceilings') or {})
    in_process = pool_type != 'process'
    workers = max(1, workers_count or 1)
    cpu_count = calibration.get('cpu_count') or 1
    io_overlap = bool(io_readahead) \
        or snapshot.get('io_overlap_fraction', 0.0) > 0.5
    # A warm cache legitimately skips the io+decode the ceilings measure:
    # when the snapshot proves the reads
    # were mostly cache hits, judge against the post-cache stages instead.
    hits = snapshot.get('shared_hits', 0)
    misses = snapshot.get('shared_misses', 0)
    cache_warm = (cache_type == 'shared' and hits + misses > 0
                  and hits / (hits + misses) > 0.5)
    # effective per-stage ceilings for THIS configuration: decode scaled by
    # usable workers, serializer dropped for in-process pools, io+decode
    # dropped for a proven-warm cache
    effective = {}
    if not cache_warm:
        if ceilings.get('io'):
            effective['io'] = ceilings['io']
        if ceilings.get('decode'):
            effective['decode'] = \
                ceilings['decode'] * min(workers, cpu_count)
    if not in_process and ceilings.get('serialize'):
        effective['serialize'] = ceilings['serialize']
    if ceilings.get('device_stage'):
        effective['device_stage'] = ceilings['device_stage']
    if cache_warm and not effective:
        # no post-cache stage was calibrated (in-process pool, no jax
        # backend): fall back to the uncached ceilings so the verdict
        # stays defined — a warm cache legitimately exceeding them gets
        # the benign cache-replay warning below, not a None binding stage
        if ceilings.get('io'):
            effective['io'] = ceilings['io']
        if ceilings.get('decode'):
            effective['decode'] = \
                ceilings['decode'] * min(workers, cpu_count)
    binding = min(effective, key=effective.get) if effective else None
    fraction = None
    if binding and effective[binding]:
        fraction = measured / effective[binding] if measured else 0.0
    predicted = predict_throughput(
        ceilings, workers=workers, cpu_count=cpu_count,
        io_overlap=io_overlap, in_process=in_process, cached=cache_warm)
    profile.update({
        'calibrated': True,
        'cache_warm': cache_warm,
        'calibration_host': calibration.get('host'),
        'dataset_digest': calibration.get('dataset_digest'),
        'cpu_count': cpu_count,
        'ceilings': {k: round(v, 2) for k, v in ceilings.items()
                     if v is not None},
        'effective_ceilings': {k: round(v, 2)
                               for k, v in effective.items()},
        'binding_stage': binding,
        'binding_ceiling_samples_per_s': round(effective[binding], 2)
        if binding else None,
        'roofline_fraction': round(fraction, 4)
        if fraction is not None else None,
        'predicted_samples_per_s': round(predicted, 2)
        if predicted else None,
    })
    if fraction is not None and fraction > SANE_FRACTION_LIMIT:
        if cache_type != 'null':
            # a replaying cache (proven warm, or local-disk whose hits no
            # counter records) is the benign explanation — name it
            # instead of crying broken measurement
            profile['warning'] = (
                'measured rate is {:.1f}x the calibrated {} ceiling; with '
                "cache_type={!r} a cache-replay epoch legitimately beats "
                'the io+decode ceilings — judge cached epochs against the '
                'post-cache stages, not this one'.format(
                    fraction, binding, cache_type))
        else:
            profile['warning'] = (
                'measured rate is {:.1f}x the calibrated {} ceiling — a '
                'sustained pipeline cannot beat its binding stage, so '
                'either the measured window drained pre-decoded buffers '
                '(lengthen it past steady state) or the calibration is '
                "stale (profile(calibrate='force'))".format(
                    fraction, binding))
    profile['advisor'] = advise(profile)
    return profile


def explain(profile: dict) -> str:
    """One human sentence per roofline verdict — what ``reader
    .explain_throughput()`` and the CLI's ``--profile`` print."""
    measured = profile.get('measured_samples_per_s') or 0.0
    if not profile.get('calibrated'):
        return ('measured {:.1f} samples/s; no calibration for this '
                'dataset yet — run reader.profile() to measure the '
                'per-stage ceilings'
                .format(measured))
    binding = profile.get('binding_stage')
    ceiling = profile.get('binding_ceiling_samples_per_s') or 0.0
    fraction = profile.get('roofline_fraction') or 0.0
    lines = ['measured {:.1f} samples/s = {:.1f}% of the binding stage '
             "({}) ceiling of {:.1f} samples/s".format(
                 measured, 100.0 * fraction, binding, ceiling)]
    if profile.get('warning'):
        lines.append('WARNING: ' + profile['warning'])
    for rec in (profile.get('advisor') or [])[:2]:
        lines.append('try {}: {}'.format(rec['knob'], rec['reason']))
    return '; '.join(lines)


def roofline_gauges(profile: dict) -> dict:
    """The profile as flat metric gauges merged into stats snapshots —
    ``stage_ceiling_<stage>``, ``roofline_fraction`` and the (string-
    valued, label-exported) ``binding_stage`` — so Prometheus scrapes show
    %-of-ceiling next to raw samples/s."""
    gauges = {}
    for stage, value in (profile.get('effective_ceilings') or {}).items():
        gauges['stage_ceiling_{}'.format(stage)] = value
    if profile.get('roofline_fraction') is not None:
        gauges['roofline_fraction'] = profile['roofline_fraction']
    if profile.get('binding_stage'):
        gauges['binding_stage'] = profile['binding_stage']
    if profile.get('measured_samples_per_s') is not None:
        gauges['roofline_samples_per_s'] = profile['measured_samples_per_s']
    return gauges


def roofline_summary(profile: dict) -> dict:
    """The compact roofline section embedded in flight records and
    ``infeed_diagnosis`` output."""
    return {
        'measured_samples_per_s': profile.get('measured_samples_per_s'),
        'binding_stage': profile.get('binding_stage'),
        'binding_ceiling_samples_per_s':
            profile.get('binding_ceiling_samples_per_s'),
        'roofline_fraction': profile.get('roofline_fraction'),
        'critical_stage': (profile.get('attribution') or {})
            .get('critical_stage'),
    }


# ---------------------------------------------------------------------------
# what-if advisor
# ---------------------------------------------------------------------------

def advise(profile: dict, max_workers: Optional[int] = None) -> List[dict]:
    """Ranked knob recommendations with predicted samples/s deltas.

    Each entry: ``{'knob', 'from', 'to', 'predicted_samples_per_s',
    'predicted_delta_pct', 'reason'}``, sorted by predicted delta
    descending; only positive-delta recommendations are emitted. The
    predictions replay :func:`predict_throughput` — the same model the
    roofline verdict uses — so a recommendation can never promise more than
    the calibrated ceilings admit."""
    if not profile.get('calibrated'):
        return []
    ceilings = {k: v for k, v in (profile.get('ceilings') or {}).items()}
    config = profile.get('config') or {}
    workers = max(1, config.get('workers_count') or 1)
    cpu_count = profile.get('cpu_count') or 1
    in_process = config.get('pool_type') != 'process'
    io_overlap = bool(config.get('io_readahead'))
    base = predict_throughput(ceilings, workers=workers, cpu_count=cpu_count,
                              io_overlap=io_overlap, in_process=in_process)
    if not base:
        return []
    recommendations = []

    def consider(knob, from_value, to_value, predicted, reason):
        if predicted is None:
            return
        delta = 100.0 * (predicted - base) / base
        if delta < 1.0:       # sub-percent predictions are noise, not advice
            return
        recommendations.append({
            'knob': knob, 'from': from_value, 'to': to_value,
            'predicted_samples_per_s': round(predicted, 1),
            'predicted_delta_pct': round(delta, 1),
            'reason': reason,
        })

    target_workers = max_workers or cpu_count
    if target_workers > workers:
        predicted = predict_throughput(
            ceilings, workers=target_workers, cpu_count=cpu_count,
            io_overlap=io_overlap, in_process=in_process)
        consider('workers_count', workers, target_workers, predicted,
                 'decode is parallel up to the {} host cores'
                 .format(cpu_count))
    if not io_overlap and ceilings.get('io') and ceilings.get('decode'):
        predicted = predict_throughput(
            ceilings, workers=workers, cpu_count=cpu_count,
            io_overlap=True, in_process=in_process)
        consider('io_readahead', 0, 'auto', predicted,
                 'overlap storage reads with decode instead of serializing '
                 'them per row group')
    if config.get('cache_type') in (None, 'null', 'local-disk'):
        cached = predict_throughput(ceilings, workers=workers,
                                    cpu_count=cpu_count, io_overlap=True,
                                    in_process=in_process, cached=True)
        consider("cache_type='shared'", config.get('cache_type') or 'null',
                 'shared', cached,
                 'epochs 2+ (and every concurrent reader on this host) '
                 'skip io+decode entirely via the host-wide decoded tier')
    if not in_process:
        # the inverse direction: a process pool whose serializer ceiling
        # binds should drop to threads when decode would not regress
        without = predict_throughput(ceilings, workers=workers,
                                     cpu_count=cpu_count,
                                     io_overlap=io_overlap, in_process=True)
        consider("reader_pool_type='thread'", 'process', 'thread', without,
                 'the zero-copy transport ceiling binds before decode does')
    recommendations.sort(key=lambda r: -r['predicted_delta_pct'])
    return recommendations
