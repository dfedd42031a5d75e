"""Worker-side row-group readahead: overlap storage I/O with decode.

Without it, each piece worker performs a blocking ``read_row_group`` and
only then decodes, so storage latency and decode CPU serialize. This
module pipelines them — a single background reader thread per worker issues
the parquet reads for the next K ventilated pieces while the worker thread
decodes the current one (the tf.data-style prefetch discipline petastorm's
ancestors rely on).

Design constraints that shaped the shape of this class:

- **One background reader thread.** A ``pq.ParquetFile`` handle is not safe
  for concurrent reads, and the readahead therefore keeps its *own*
  file-handle cache (see ``ParquetPieceWorker``), fully disjoint from the
  worker thread's. Cross-file read parallelism comes from ``workers_count``;
  the readahead's job is only to hide the current worker's next read behind
  its current decode.
- **FIFO contract.** The pool's worker loop hints the worker with the exact
  upcoming item order, and the worker consumes reads in that same order.
  :meth:`sync` therefore treats the outstanding prefetches as a prefix of the
  hinted plan list and self-heals (cancels everything) on any mismatch —
  a desynced prefetch degrades to an inline read, never to wrong data.
- **Stats without cross-thread races.** ``WorkerBase.record_time`` is not
  thread-safe against the pool draining ``stage_times``, so the background
  thread accumulates into this object's own lock-protected dict and the
  *worker thread* transfers it out on every :meth:`take` call
  (:meth:`drain_stats_into`).

``depth='auto'`` sizes K from live measurements: the background thread knows
the average read time, and the gap between consecutive :meth:`take` calls is
the worker's decode+publish time for one piece — their ratio is the live
io:decode ratio that :func:`petastorm_tpu.workers.stats.recommend_io_readahead`
derives from a ``ReaderStats`` snapshot on the consumer side.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from collections import deque
from typing import List, Optional, Tuple

#: Upper bound for ``depth='auto'`` (also the ventilation-queue sizing bound
#: the reader uses for 'auto'); deeper queues only smooth variance once the
#: single reader thread is saturated.
AUTO_MAX_DEPTH = 8

#: Starting depth for ``depth='auto'`` until enough samples arrive.
AUTO_INITIAL_DEPTH = 2


class _Prefetch:
    """One in-flight background read."""

    __slots__ = ('key', 'piece', 'columns', 'table', 'error', 'done',
                 'cancelled', 'read_s')

    def __init__(self, key, piece, columns):
        self.key = key
        self.piece = piece
        self.columns = columns
        self.table = None
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        self.cancelled = False
        self.read_s = 0.0


class RowGroupReadahead:
    """Bounded prefetch queue + one background reader thread.

    :param read_fn: ``read_fn(piece, columns) -> pa.Table``; runs **only** on
        the background thread (it must use its own file handles).
    :param depth: max outstanding prefetched reads, or ``'auto'``. ``0`` is
        **dormant**: the machinery exists (hints flow, :meth:`set_depth` can
        activate it live) but nothing is prefetched — the shape the autotune
        controller constructs when the reader starts with readahead off.
    :param trace: record a ``readahead_read`` span per background read
        (stamped with the background thread's track, drained into the worker
        alongside the stats).
    :param beat: optional ``beat(stage)`` callable publishing the background
        reader thread's liveness (the owning worker routes it to its own
        heartbeat records as a ``readahead-<id>`` entity; see
        :mod:`petastorm_tpu.health`). Called from the background thread —
        must be cross-thread safe (``WorkerBase.beat_entity`` is).
    :param controlled: the depth is **controller-owned**
        (:mod:`petastorm_tpu.autotune`): the local auto-retune never runs —
        two controllers adjusting one knob would oscillate — and only
        :meth:`set_depth` moves it.
    """

    def __init__(self, read_fn, depth, trace: bool = False, beat=None,
                 controlled: bool = False):
        if depth != 'auto' and (not isinstance(depth, int) or depth < 0):
            raise ValueError(
                "readahead depth must be a non-negative int or 'auto', got "
                '{!r}'.format(depth))
        self._read_fn = read_fn
        self._controlled = controlled
        self._auto = depth == 'auto' and not controlled
        self._depth = AUTO_INITIAL_DEPTH if depth == 'auto' else depth
        self._trace = trace
        self._beat = beat
        self._lock = threading.Lock()
        self._scheduled: deque = deque()      # FIFO of un-consumed _Prefetch
        self._requests: queue.Queue = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._stopped = False
        # accumulated telemetry, drained into the worker on its own thread
        self._stats_times = {'readahead_io_s': 0.0, 'readahead_wait_s': 0.0}
        self._stats_counts = {'readahead_hits': 0, 'readahead_misses': 0}
        self._trace_spans: list = []
        # auto-depth measurement state (all mutated under self._lock)
        self._read_s_sum = 0.0
        self._read_samples = 0
        self._gap_s_sum = 0.0
        self._gap_samples = 0
        self._last_serve_end: Optional[float] = None

    # -- sizing ----------------------------------------------------------------

    @property
    def depth(self) -> int:
        """Current target depth (fixed, live auto-tuned, or
        controller-set)."""
        with self._lock:
            return self._depth

    def set_depth(self, depth: int) -> None:
        """Pin the target depth live (the autotune controller's actuator).

        Pinning disables the local auto-retune for good — once a controller
        owns the knob, two tuners must never fight over it. ``0`` makes the
        readahead dormant (outstanding reads drain normally, new ones are
        not scheduled); a later positive depth re-activates it."""
        if not isinstance(depth, int) or depth < 0:
            raise ValueError('readahead depth must be a non-negative int, '
                             'got {!r}'.format(depth))
        with self._lock:
            self._auto = False
            self._depth = min(depth, AUTO_MAX_DEPTH)

    def _retune_locked(self) -> None:
        if not self._auto or self._read_samples < 2 or self._gap_samples < 2:
            return
        avg_read = self._read_s_sum / self._read_samples
        avg_gap = self._gap_s_sum / self._gap_samples
        ratio = avg_read / max(avg_gap, 1e-9)
        self._depth = int(min(AUTO_MAX_DEPTH, max(1, math.ceil(ratio))))

    # -- scheduling ------------------------------------------------------------

    def sync(self, plans: List[Tuple]) -> int:
        """Reconcile outstanding prefetches with the ordered upcoming ``plans``
        (``(key, piece, columns)`` tuples) and schedule new reads up to the
        current depth. Returns the number of outstanding prefetches.

        The outstanding FIFO must be a prefix of ``plans``; any mismatch
        (an item was processed without consuming its read, or the pool
        re-ordered work) cancels every outstanding read — prefetching is an
        optimization, and falling back to inline reads is always correct.
        """
        with self._lock:
            if self._stopped:
                return 0
            matches = len(self._scheduled) <= len(plans) and all(
                entry.key == plan[0]
                for entry, plan in zip(self._scheduled, plans))
            if not matches:
                self._cancel_all_locked()
            for key, piece, columns in plans[len(self._scheduled):]:
                if len(self._scheduled) >= self._depth:
                    break
                entry = _Prefetch(key, piece, columns)
                self._scheduled.append(entry)
                self._requests.put(entry)
            occupancy = len(self._scheduled)
            if occupancy and self._thread is None:
                self._thread = threading.Thread(
                    target=self._reader_loop, daemon=True,
                    name='petastorm-tpu-readahead')
                self._thread.start()
        return occupancy

    def take(self, key):
        """The table prefetched for ``key`` (blocking on its completion), or
        ``None`` when the read was not prefetched — the caller reads inline.

        Must be called from the worker thread, in the same order reads were
        hinted. Time blocked here is recorded as both ``readahead_wait_s``
        (the un-hidden I/O) and the stall the caller folds into
        ``worker_io_s``.
        """
        now = time.perf_counter()
        with self._lock:
            entry = None
            if self._scheduled and self._scheduled[0].key == key:
                entry = self._scheduled.popleft()
            if entry is None:
                if self._depth > 0:
                    # a dormant (depth-0) readahead never prefetches, so an
                    # inline read is its contract, not a miss to diagnose
                    self._stats_counts['readahead_misses'] += 1
                # inline read follows; its end time is unknown — skip the
                # next decode-gap sample rather than pollute it
                self._last_serve_end = None
                return None
            if self._last_serve_end is not None:
                self._gap_s_sum += now - self._last_serve_end
                self._gap_samples += 1
        wait_start = time.perf_counter()
        entry.done.wait()
        waited = time.perf_counter() - wait_start
        with self._lock:
            self._stats_counts['readahead_hits'] += 1
            self._stats_times['readahead_wait_s'] += waited
            self._last_serve_end = time.perf_counter()
            self._retune_locked()
        if entry.error is not None:
            raise entry.error
        return entry.table

    def drain_stats_into(self, worker) -> None:
        """Transfer accumulated telemetry into ``worker`` (a ``WorkerBase``).
        Called from the worker thread so ``stage_times`` is never mutated
        concurrently with the pool's drain. The blocked-wait portion also
        counts as ``worker_io_s`` — it is the storage stall the readahead
        failed to hide, and keeping it there preserves the decode-derivation
        contract of ``finalize_item_times``."""
        with self._lock:
            times = dict(self._stats_times)
            counts = dict(self._stats_counts)
            for stage in self._stats_times:
                self._stats_times[stage] = 0.0
            for name in self._stats_counts:
                self._stats_counts[name] = 0
            spans, self._trace_spans = self._trace_spans, []
            occupancy = len(self._scheduled)
        for stage, seconds in times.items():
            if seconds:
                worker.record_time(stage, seconds)
        if times['readahead_wait_s']:
            worker.record_time('worker_io_s', times['readahead_wait_s'])
        for name, n in counts.items():
            if n:
                worker.record_count(name, n)
        if spans and getattr(worker, 'tracing_enabled', False):
            # already stamped with the background thread's (pid, tid) track
            worker.trace_spans.extend(spans)
        worker.record_gauge('readahead_depth', occupancy)

    # -- lifecycle -------------------------------------------------------------

    def _cancel_all_locked(self) -> None:
        for entry in self._scheduled:
            entry.cancelled = True
        self._scheduled.clear()
        self._last_serve_end = None

    def stop(self) -> None:
        """Cancel outstanding reads and stop the background thread."""
        with self._lock:
            if self._stopped:
                return
            self._stopped = True
            self._cancel_all_locked()
        self._requests.put(None)
        thread = self._thread
        if thread is not None:
            thread.join(timeout=10)

    # -- background thread -----------------------------------------------------

    def _reader_loop(self) -> None:
        beat = self._beat
        while True:
            if beat is not None:
                beat('idle')
            entry = self._requests.get()
            if entry is None:
                if beat is not None:
                    beat('stopped')
                return
            if entry.cancelled:
                entry.done.set()
                continue
            if beat is not None:
                beat('io')
            start = time.perf_counter()
            try:
                entry.table = self._read_fn(entry.piece, entry.columns)
            except BaseException as e:  # noqa: BLE001 - re-raised in take()
                entry.error = e
            entry.read_s = time.perf_counter() - start
            with self._lock:
                if not entry.cancelled:
                    self._stats_times['readahead_io_s'] += entry.read_s
                    if self._trace:
                        from petastorm_tpu.tracing import make_span
                        self._trace_spans.append(make_span(
                            'readahead_read', 'io', start, entry.read_s))
                self._read_s_sum += entry.read_s
                self._read_samples += 1
                self._retune_locked()
            entry.done.set()
