"""Training goodput plane: per-step data-stall attribution (docs/goodput.md).

Every sensor before this one watches the host pipeline; the
:class:`GoodputMonitor` answers the question the framework exists to
answer — *is the accelerator actually fed?* It decomposes every training
step the consumer loop takes into

    total_s = infeed_wait_s + train_wall_s
    infeed_wait_s = stall_s + h2d_stage_s          (data-path cost)
    train_wall_s  = device_step_s + host_overhead_s (with the opt-in fence)

using the timing sites the loader already owns (``jax_utils.LoopBoundary``
times the blocking fetch on the training loop's thread;
``stage_to_global``/``prefetch_to_device`` report their staging seconds
via :meth:`GoodputMonitor.note_stage`) plus
an opt-in ``block_until_ready`` step fence (:meth:`GoodputMonitor.fence`).
Without the fence the device/host split inside the train wall is unknown
and the whole wall is attributed to ``device_step`` (recorded as
unfenced, so verdicts stay honest about what was measured).

The monitor is **threadless** and records three ways, all mergeable:

- a bounded per-step ring (:meth:`steps`) for :meth:`explain_step`;
- the shared latency plane (new ``device_step`` / ``host_overhead``
  stages — the loader itself already records ``infeed_wait`` and
  ``train_step``), whose log-bucketed histograms merge bit-identically
  across hosts (docs/latency.md);
- summed-seconds counters in ``ReaderStats`` (``goodput_total_s`` et al.)
  from which the derived ``goodput_fraction`` / ``data_stall_fraction``
  are computed — pod aggregation sums the seconds and re-derives the
  fraction, never averages fractions (docs/pod_observability.md).

Default-on behind the structural ``PETASTORM_TPU_GOODPUT=0`` kill switch:
off means no monitor object, no ring, no new latency stages recorded, no
``/goodput`` route — not a no-op shim.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = ['GOODPUT_ENV_VAR', 'GoodputMonitor', 'goodput_enabled',
           'classify_step', 'DATA_STALL', 'COMPUTE_BOUND', 'HOST_OVERHEAD',
           'BALANCED']

#: Kill switch (default ON, the observability-plane convention): ``0`` /
#: ``false`` / ``off`` yields loaders with no monitor, no recorded
#: stages and no ``/goodput`` route.
GOODPUT_ENV_VAR = 'PETASTORM_TPU_GOODPUT'

#: Verdict vocabulary (docs/goodput.md): the dominant component of a
#: step's wall time, when it dominates at all.
DATA_STALL = 'data-stall'
COMPUTE_BOUND = 'compute-bound'
HOST_OVERHEAD = 'host-overhead'
BALANCED = 'balanced'

#: A component must carry at least this fraction of the step wall to be
#: named the verdict; below it no single stage dominates.
DOMINANCE_THRESHOLD = 0.4

#: Per-step ring bound: explain_step() reaches this far back.
DEFAULT_STEP_RING = 512

#: Rolling goodput window (steps) for :meth:`GoodputMonitor.summary`.
DEFAULT_WINDOW_STEPS = 32


def goodput_enabled() -> bool:
    """The goodput plane's kill switch (default on)."""
    return os.environ.get(GOODPUT_ENV_VAR, '1').lower() not in (
        '0', 'false', 'off')


def classify_step(entry: dict) -> str:
    """The verdict for one ring entry: the dominant wall-time component
    (data stall / device compute / host overhead) when one carries at
    least :data:`DOMINANCE_THRESHOLD` of the step, else ``balanced``."""
    total = entry.get('total_s') or 0.0
    if total <= 0.0:
        return BALANCED
    stall_f = (entry.get('stall_s', 0.0) + entry.get('h2d_stage_s', 0.0)) / total
    device_f = entry.get('device_step_s', 0.0) / total
    host_f = entry.get('host_overhead_s', 0.0) / total
    best, verdict = stall_f, DATA_STALL
    if device_f > best:
        best, verdict = device_f, COMPUTE_BOUND
    if host_f > best:
        best, verdict = host_f, HOST_OVERHEAD
    return verdict if best >= DOMINANCE_THRESHOLD else BALANCED


class GoodputMonitor:
    """Per-step goodput accounting for one consumer loop.

    Constructed by ``JaxLoaderBase`` when :func:`goodput_enabled`; the
    loop boundary (``jax_utils.LoopBoundary``) drives :meth:`note_fetch` /
    :meth:`finish_step` from the training loop's clock readings (under a
    prefetcher, on the producer thread shortly after) and the
    staging sites drive :meth:`note_stage` (possibly
    from the prefetch producer thread — the pending accumulators are
    lock-protected; the monitor itself never starts a thread).

    ``stats`` / ``tracer`` are the reader's planes (any may be ``None``):
    summed seconds land in ``ReaderStats`` counters, per-step
    ``device_step``/``host_overhead`` observations in the latency
    histograms, and one ``'step'`` span per step (cat ``'goodput'``,
    args carrying the verdict + stall ms) in the tracer — complete spans,
    so ``stitch_pod_trace`` aligns step boundaries across hosts unchanged.
    """

    def __init__(self, stats=None, tracer=None, latency=None,
                 ring_size: int = DEFAULT_STEP_RING,
                 window_steps: int = DEFAULT_WINDOW_STEPS,
                 host: Optional[str] = None):
        self._stats = stats
        self._tracer = tracer
        # a standalone monitor (benchmarks, pod fixtures) can record into
        # a latency plane directly; a loader-attached one routes through
        # stats.record_latency so histograms live with the reader's plane
        self._latency = latency
        self._host = host
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=ring_size)
        self._window_steps = max(1, int(window_steps))
        self._steps = 0
        self._fenced_steps = 0
        # summed-seconds totals (the mergeable pod export)
        self._total_s = 0.0
        self._stall_s = 0.0
        self._h2d_s = 0.0
        self._device_s = 0.0
        self._host_s = 0.0
        # pending state for the step in flight
        self._pending_infeed_s = 0.0
        self._pending_h2d_s = 0.0
        # fences of the open step: (perf_counter at its end, seconds)
        self._pending_fences = []
        self._pending_provenance = None
        self._step_open = False

    # -- hot-path hooks (loader / staging sites) -------------------------------

    def note_fetch(self, infeed_wait_s: float, batch=None, fetched_at=None,
                   provenance=None) -> None:
        """The loader fetched a batch after blocking ``infeed_wait_s``
        seconds; opens the step the consumer is about to run. A caller that
        reports the fetch after the fact (``jax_utils.LoopBoundary.fold``)
        gives its ``time.perf_counter()`` as ``fetched_at``: only fences
        that ended before it are dropped, not those of the step since. The
        step's ``provenance`` is ``batch['_provenance']`` unless given."""
        if provenance is None and isinstance(batch, dict):
            provenance = batch.get('_provenance')
        with self._lock:
            self._pending_infeed_s = max(0.0, float(infeed_wait_s))
            self._pending_provenance = provenance
            self._pending_fences = [] if fetched_at is None else [
                f for f in self._pending_fences if f[0] >= fetched_at]
            self._step_open = True

    def note_stage(self, elapsed_s: float) -> None:
        """``elapsed_s`` seconds of host→device staging happened; it is
        attributed to the next step to finish (staging may run ahead on
        the prefetch producer thread — attribution, not measurement)."""
        with self._lock:
            self._pending_h2d_s += max(0.0, float(elapsed_s))

    def fence(self, outputs):
        """Opt-in step fence: ``jax.block_until_ready(outputs)`` timed, so
        the train wall splits into device time (the wait here) and host
        overhead (the rest). Call it on the step's outputs inside the
        training loop; returns ``outputs``. Without it the whole train
        wall counts as device time and the step records ``fenced=False``."""
        import jax
        start = time.perf_counter()
        outputs = jax.block_until_ready(outputs)
        end = time.perf_counter()
        with self._lock:
            self._pending_fences.append((end, end - start))
        return outputs

    def finish_step(self, train_wall_s: float,
                    ended_at=None) -> Optional[dict]:
        """Close the step the consumer just ran (``train_wall_s`` is the
        yield-to-next-fetch wall the loader measured). A caller that
        reports the step after the fact gives its end's
        ``time.perf_counter()`` as ``ended_at``: fences that ended later
        belong to the next step. Returns the ring entry, or ``None`` when
        no step was open."""
        train_wall_s = max(0.0, float(train_wall_s))
        with self._lock:
            if not self._step_open:
                return None
            infeed = self._pending_infeed_s
            h2d = self._pending_h2d_s
            fences = self._pending_fences
            if ended_at is not None:
                self._pending_fences = [f for f in fences if f[0] > ended_at]
                fences = [f for f in fences if f[0] <= ended_at]
            else:
                self._pending_fences = []
            fence_s = sum(seconds for _, seconds in fences)
            fenced = bool(fences)
            provenance = self._pending_provenance
            self._pending_infeed_s = 0.0
            self._pending_h2d_s = 0.0
            self._pending_provenance = None
            self._step_open = False
            step = self._steps
            self._steps += 1
            # the h2d seconds on the critical path are at most the time
            # the consumer actually waited; the rest overlapped compute
            h2d_attrib = min(h2d, infeed)
            stall = infeed - h2d_attrib
            if fenced:
                device = min(fence_s, train_wall_s)
                host = train_wall_s - device
                self._fenced_steps += 1
            else:
                device = train_wall_s
                host = 0.0
            total = infeed + train_wall_s
            entry = {
                'step': step,
                'total_s': total,
                'infeed_wait_s': infeed,
                'stall_s': stall,
                'h2d_stage_s': h2d_attrib,
                'device_step_s': device,
                'host_overhead_s': host,
                'fenced': fenced,
                'provenance': provenance,
            }
            self._ring.append(entry)
            self._total_s += total
            self._stall_s += stall
            self._h2d_s += h2d_attrib
            self._device_s += device
            self._host_s += host
        self._record(entry, ended_at)
        return entry

    def _record(self, entry: dict, ended_at=None) -> None:
        """Export one closed step to the shared planes (outside the lock:
        stats/tracer take their own locks)."""
        stats = self._stats
        if stats is not None:
            stats.merge_times({'goodput_total_s': entry['total_s'],
                               'goodput_stall_s': entry['stall_s'],
                               'goodput_h2d_s': entry['h2d_stage_s'],
                               'goodput_device_s': entry['device_step_s'],
                               'goodput_host_s': entry['host_overhead_s']})
            stats.record_latency('device_step', entry['device_step_s'])
            if entry['fenced']:
                stats.record_latency('host_overhead', entry['host_overhead_s'])
        elif self._latency is not None:
            self._latency.record('device_step', entry['device_step_s'])
            if entry['fenced']:
                self._latency.record('host_overhead',
                                     entry['host_overhead_s'])
        tracer = self._tracer
        if tracer is not None:
            now = time.perf_counter() if ended_at is None else ended_at
            stall_ms = (entry['stall_s'] + entry['h2d_stage_s']) * 1000.0
            tracer.add_span('step', 'goodput', now - entry['total_s'],
                            entry['total_s'],
                            args={'step': entry['step'],
                                  'verdict': classify_step(entry),
                                  'stall_ms': round(stall_ms, 3),
                                  'fenced': entry['fenced']})

    # -- read side -------------------------------------------------------------

    def steps(self) -> List[dict]:
        """The bounded per-step ring, oldest first (copies)."""
        with self._lock:
            return [dict(e) for e in self._ring]

    def step(self, n: int) -> Optional[dict]:
        """Ring entry for step ``n`` (``None`` when evicted/unknown)."""
        with self._lock:
            for entry in reversed(self._ring):
                if entry['step'] == n:
                    return dict(entry)
        return None

    def state(self) -> dict:
        """The mergeable pod export: summed seconds + step counts. Pod
        aggregation adds these and re-derives fractions — fractions are
        never averaged across hosts (``_NON_ADDITIVE_SUFFIXES``)."""
        with self._lock:
            return {
                'steps': self._steps,
                'fenced_steps': self._fenced_steps,
                'total_s': self._total_s,
                'stall_s': self._stall_s,
                'h2d_s': self._h2d_s,
                'device_s': self._device_s,
                'host_s': self._host_s,
            }

    def window(self, steps: Optional[int] = None) -> dict:
        """Rolling goodput over the last ``steps`` ring entries."""
        limit = steps or self._window_steps
        with self._lock:
            tail = list(self._ring)[-limit:]
        total = sum(e['total_s'] for e in tail)
        if not tail or total <= 0.0:
            return {'steps': len(tail), 'goodput_fraction': None,
                    'data_stall_fraction': None}
        stall = sum(e['stall_s'] + e['h2d_stage_s'] for e in tail)
        device = sum(e['device_step_s'] for e in tail)
        return {
            'steps': len(tail),
            'goodput_fraction': round(device / total, 4),
            'data_stall_fraction': round(stall / total, 4),
        }

    def summary(self) -> dict:
        """Cumulative + rolling-window goodput; what ``/goodput`` serves."""
        state = self.state()
        total = state['total_s']
        out = {
            'enabled': True,
            'steps': state['steps'],
            'fenced_steps': state['fenced_steps'],
            'goodput_fraction': (round(state['device_s'] / total, 4)
                                 if total > 0 else None),
            'data_stall_fraction': (
                round((state['stall_s'] + state['h2d_s']) / total, 4)
                if total > 0 else None),
            'window': self.window(),
            'state': state,
        }
        if self._host is not None:
            out['host'] = self._host
        return out

    def flight_summary(self) -> dict:
        """The flight-record section: summary + the last few ring entries
        (JSON-able — provenance objects summarized)."""
        tail = self.steps()[-8:]
        for entry in tail:
            entry['provenance'] = _provenance_summary(entry.get('provenance'))
            entry['verdict'] = classify_step(entry)
        return dict(self.summary(), recent_steps=tail)

    def explain_step(self, n: Optional[int] = None, snapshot: Optional[dict] = None,
                     heartbeats=None) -> dict:
        """The per-step verdict, joined with the pipeline's own evidence:
        which component dominated step ``n`` (latest when ``None``), and —
        when it was a data stall — the culprit stage chain walked from
        ``bottleneck_signals`` over ``snapshot`` ("step 412 stalled 38ms on
        infeed_wait → queue_wait p99 tail → io_range"), prefetch-buffer
        occupancy at the snapshot, and the batch's ``_provenance`` naming
        the source row groups. ``heartbeats`` is accepted for parity with
        the health surfaces (reserved for stalled-entity naming)."""
        if n is None:
            entries = self.steps()
            entry = entries[-1] if entries else None
        else:
            entry = self.step(n)
        if entry is None:
            return {'enabled': True, 'step': n, 'verdict': None,
                    'explanation': 'no such step in the ring '
                                   '(evicted or never recorded)'}
        verdict = classify_step(entry)
        total = entry['total_s'] or 0.0
        stall_s = entry['stall_s'] + entry['h2d_stage_s']
        chain: List[str] = []
        if verdict == DATA_STALL:
            chain.append('h2d_stage' if entry['h2d_stage_s'] > entry['stall_s']
                         else 'infeed_wait')
            signals = None
            if snapshot:
                from petastorm_tpu.health import bottleneck_signals
                signals = bottleneck_signals(snapshot)
                if signals.get('tail_stall'):
                    chain.append('queue_wait p99 tail')
                if signals.get('slow_object_store'):
                    chain.append('io_range')
                elif signals.get('slow_peer_cache'):
                    chain.append('peer_fetch')
                elif not signals.get('tail_stall'):
                    bottleneck = signals.get('bottleneck')
                    if bottleneck and bottleneck != 'none':
                        chain.append(bottleneck)
        elif verdict == HOST_OVERHEAD:
            chain.append('host_overhead')
        elif verdict == COMPUTE_BOUND:
            chain.append('device_step')
        provenance = _provenance_summary(entry.get('provenance'))
        if chain and provenance and provenance.get('sources'):
            source = provenance['sources'][0]
            where = source.get('path')
            if where:
                suffix = where.rsplit('/', 1)[-1]
                chain[-1] = '{} ({} rg{})'.format(
                    chain[-1], suffix, source.get('row_group'))
        occupancy = None
        if snapshot:
            occupancy = snapshot.get('prefetch_occupancy')
        if verdict == DATA_STALL:
            explanation = 'step {} stalled {:.0f}ms on {}'.format(
                entry['step'], stall_s * 1000.0,
                ' → '.join(chain) if chain else 'infeed_wait')
        elif verdict == COMPUTE_BOUND:
            explanation = ('step {} spent {:.0f}ms of {:.0f}ms in device '
                           'compute — the input pipeline kept up'.format(
                               entry['step'],
                               entry['device_step_s'] * 1000.0,
                               total * 1000.0))
        elif verdict == HOST_OVERHEAD:
            explanation = ('step {} spent {:.0f}ms in host-side work '
                           'between fetch and device completion'.format(
                               entry['step'],
                               entry['host_overhead_s'] * 1000.0))
        else:
            explanation = ('step {} is balanced: no component carries '
                           '{:.0%} of the wall'.format(
                               entry['step'], DOMINANCE_THRESHOLD))
        out: Dict[str, Any] = {
            'enabled': True,
            'step': entry['step'],
            'verdict': verdict,
            'explanation': explanation,
            'chain': chain,
            'stall_ms': round(stall_s * 1000.0, 3),
            'decomposition': {
                'total_s': entry['total_s'],
                'infeed_wait_s': entry['infeed_wait_s'],
                'stall_s': entry['stall_s'],
                'h2d_stage_s': entry['h2d_stage_s'],
                'device_step_s': entry['device_step_s'],
                'host_overhead_s': entry['host_overhead_s'],
                'fenced': entry['fenced'],
            },
        }
        if occupancy is not None:
            out['prefetch_occupancy'] = occupancy
        if provenance is not None:
            out['provenance'] = provenance
        if self._host is not None:
            out['host'] = self._host
        return out


def _provenance_summary(provenance) -> Optional[dict]:
    """A JSON-able view of a ring entry's provenance (a
    ``BatchProvenance``, a ``Provenance`` record, or ``None``)."""
    if provenance is None:
        return None
    summary = getattr(provenance, 'summary', None)
    if callable(summary):
        try:
            return summary()
        except Exception:  # a foreign object with a summary() of its own
            return None
    asdict = getattr(provenance, '_asdict', None)
    if callable(asdict):
        record = asdict()
        record.pop('selection', None)
        return {'rows': record.get('rows'), 'sources': [record]}
    if isinstance(provenance, dict):
        return provenance
    return None
