"""Pod-scale observability plane: one surface for cross-host metrics,
read-plane latency, and the pod-wide decode-once certificate.

Every sensor the repo grew so far — spans (``docs/tracing.md``), mergeable
latency histograms (``docs/latency.md``), ``/healthz`` verdicts
(``docs/health.md``), lineage coverage (``docs/lineage.md``), shared-cache
counters (``docs/cache.md``) — stops at the host boundary, while the planes
added by the object-store and pod-cache PRs are explicitly *cross-host*.
This module cashes in the design decision that made PR 12's histograms
bucket-additive: any two hosts' states merge by integer bucket addition, so
a pod-wide p99 carries exactly the same
:data:`~petastorm_tpu.latency.QUANTILE_REL_ERROR_BOUND` as a single host's.

Three pieces:

- **The per-host surface.** :func:`make_observe_fn` builds the
  ``GET /observe/snapshot`` payload a ``DebugServer`` serves: stats
  counters, raw latency-histogram bucket states, the health verdict with
  degraded causes, SLO burn, the lineage coverage digest, shared-cache
  ``global_counters`` (``fills``/``peer_hits``), a span tail, and the
  host's ``time.perf_counter()`` reading (the clock-offset anchor).
- **The aggregator.** :class:`PodObserver` polls a ``host:port`` peer list
  (the same convention as the shared cache's ``peers=``) and merges:
  counters by addition, histograms by bucket-count addition (pod p99s are
  **bit-identical** to direct recording — integer counts have no merge
  order), health by worst-of with per-host causes named, and the pod
  decode-once certificate ``sum(fills) == distinct row groups`` machine-
  checked the way ``CoverageAuditor.assert_complete()`` is. A dead or
  unreachable host degrades the verdict to a **named** :data:`PARTIAL_POD`
  — never a silent shrink of the certificate's denominator.
- **Clock alignment.** Peer HTTP requests and ``/observe`` responses carry
  :data:`TRACE_HEADER` (a request id) and :data:`CLOCK_HEADER` (the
  server's monotonic reading); the observer estimates each host's clock
  offset as ``remote_clock - (t0 + t1) / 2`` so
  :func:`petastorm_tpu.tracing.stitch_pod_trace` can emit one aligned
  timeline across hosts.

Everything is **on by default**; set ``PETASTORM_TPU_PODOBS=0`` to create
no thread, no routes, and no files: the observe/podmetrics routes 404, the
read-plane span/latency instrumentation compiles out to one boolean test,
and no aggregator state exists anywhere. The observer itself never spawns a
thread — it polls on demand (a call, a CLI run, or an HTTP request to the
``/podmetrics`` route of whichever host embeds it). See
``docs/pod_observability.md``.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import socket
import sys
import time
import urllib.request
import uuid
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from petastorm_tpu.latency import (LatencyHistogram, NUM_BUCKETS,
                                   QUANTILE_REL_ERROR_BOUND)

logger = logging.getLogger(__name__)

#: Environment variable gating the whole pod-observability plane (default
#: on). ``0``/``false``/``off`` mean: no ``/observe/snapshot`` or
#: ``/podmetrics`` routes, no ``range_fetch``/``peer_fetch`` span or
#: latency recording, no trace headers on peer-cache requests.
PODOBS_ENV_VAR = 'PETASTORM_TPU_PODOBS'

#: Comma-separated ``host:port`` peer list naming the pod's debug
#: endpoints; when set (and the plane is on), the reader embeds a
#: :class:`PodObserver` and serves the aggregate on ``/podmetrics``.
PODOBS_PEERS_ENV_VAR = 'PETASTORM_TPU_PODOBS_PEERS'

#: Request/response header carrying the trace id — one id stamped by the
#: client rides through a peer-cache fetch (and any observe poll) so the
#: per-host span rings can be joined into one pod timeline.
TRACE_HEADER = 'X-Petastorm-Trace'

#: Response header carrying the server's ``time.perf_counter()`` reading
#: at reply time — the clock-offset anchor (monotonic clocks are not
#: comparable across hosts; the offset estimate makes them so).
CLOCK_HEADER = 'X-Petastorm-Clock-S'

#: Route the per-host snapshot is served on (``DebugServer``).
SNAPSHOT_ROUTE = '/observe/snapshot'

#: Route an aggregator host serves the merged pod report on.
PODMETRICS_ROUTE = '/podmetrics'

#: The named degraded verdict when any polled host is unreachable: the
#: report still merges every host that answered, but the certificate
#: refuses to certify against an incomplete denominator.
PARTIAL_POD = 'partial_pod'

#: Pipeline health states from best to worst — must mirror
#: ``petastorm_tpu.health`` (asserted by tests; kept literal here so the
#: pod plane does not import the HTTP/watchdog module).
VERDICT_ORDER = ('healthy', 'degraded', 'starving', 'stalled')

#: Snapshot keys that are NOT mergeable by addition: window spans and
#: fractions would double-count, percentile estimates must come from the
#: merged histograms instead (suffix-matched below).
_NON_ADDITIVE_KEYS = frozenset({'window_s', 'io_overlap_fraction', 'pid',
                                'epoch',
                                # pod-wide constants every elastic host
                                # reports identically: summing K copies
                                # would inflate the certificate denominator
                                'expected_batches'})
_NON_ADDITIVE_SUFFIXES = ('_p50_s', '_p90_s', '_p99_s', '_p999_s',
                          '_fraction')

#: Default poll timeout per peer, matching the shared cache's
#: ``peer_timeout_s`` default.
DEFAULT_TIMEOUT_S = 2.0


def podobs_enabled() -> bool:
    """The :data:`PODOBS_ENV_VAR` gate (default on)."""
    value = os.environ.get(PODOBS_ENV_VAR, '').strip().lower()
    return value not in ('0', 'false', 'off')


def pod_peers_from_env() -> Tuple[str, ...]:
    """The :data:`PODOBS_PEERS_ENV_VAR` peer list (empty tuple when
    unset)."""
    return parse_peers(os.environ.get(PODOBS_PEERS_ENV_VAR, ''))


def parse_peers(peers) -> Tuple[str, ...]:
    """Normalize a peer spec — a comma-separated string or an iterable of
    ``host:port`` strings (the shared cache's ``peers=`` convention) —
    into a tuple. Rejects entries without a port: a silent DNS-only entry
    would poll the wrong surface."""
    if peers is None:
        return ()
    if isinstance(peers, str):
        parts: Iterable[str] = peers.split(',')
    else:
        parts = peers
    out = []
    for part in parts:
        part = str(part).strip()
        if not part:
            continue
        if ':' not in part:
            raise ValueError('peer {!r} is not host:port (the shared-cache '
                             'peers= convention)'.format(part))
        out.append(part)
    return tuple(out)


def new_trace_id() -> str:
    """A fresh trace id for :data:`TRACE_HEADER`."""
    return uuid.uuid4().hex


# -- per-host snapshot surface ------------------------------------------------

def make_observe_fn(snapshot_fn: Optional[Callable[[], dict]] = None,
                    health_fn: Optional[Callable[[], dict]] = None,
                    slo_fn: Optional[Callable[[], dict]] = None,
                    coverage_fn: Optional[Callable[[], dict]] = None,
                    cache_counters_fn: Optional[Callable[[], dict]] = None,
                    span_tail_fn: Optional[Callable[[], list]] = None,
                    elastic_fn: Optional[Callable[[], dict]] = None,
                    goodput_fn: Optional[Callable[[], dict]] = None,
                    host: Optional[str] = None) -> Callable[[], dict]:
    """Build the ``observe_fn`` a ``DebugServer`` serves on
    :data:`SNAPSHOT_ROUTE`: one JSON-able dict with every per-host surface
    the pod aggregation consumes. Each section is fenced — a broken sensor
    reports ``{'error': ...}`` in its section instead of killing the whole
    snapshot (the aggregator must keep seeing the healthy sections of a
    sick host)."""
    host = host or socket.gethostname()

    def _section(fn):
        if fn is None:
            return None
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - fence per sensor
            logger.debug('observe snapshot section failed', exc_info=True)
            return {'error': '{}: {}'.format(type(e).__name__, e)}

    def observe() -> dict:
        from petastorm_tpu.workers.stats import LATENCY_HISTOGRAMS_KEY
        stats = _section(snapshot_fn) or {}
        histograms = {}
        if isinstance(stats, dict):
            stats = dict(stats)
            histograms = stats.pop(LATENCY_HISTOGRAMS_KEY, None) or {}
        snap = {
            'kind': 'petastorm_tpu.observe_snapshot',
            'version': 1,
            'host': host,
            'pid': os.getpid(),
            'clock_s': time.perf_counter(),
            'stats': stats,
            'latency_histograms': histograms,
            'health': _section(health_fn),
            'slo': _section(slo_fn),
            'coverage': _section(coverage_fn),
            'cache': _section(cache_counters_fn),
            'span_tail': _section(span_tail_fn),
            'elastic': _section(elastic_fn),
            'goodput': _section(goodput_fn),
        }
        return snap

    return observe


# -- merge semantics ----------------------------------------------------------

def merge_counters(snapshots: Sequence[Optional[dict]]) -> dict:
    """Merge per-host scalar counters **by addition**, skipping keys that
    are not additive (window spans, fractions, percentile estimates — the
    pod tail comes from :func:`merge_histogram_states`, never from
    averaging per-host percentiles)."""
    totals: Dict[str, float] = {}
    for snap in snapshots:
        for key, value in (snap or {}).items():
            if key.startswith('_') or isinstance(value, bool):
                continue
            if not isinstance(value, (int, float)):
                continue
            if key in _NON_ADDITIVE_KEYS or key.endswith(
                    _NON_ADDITIVE_SUFFIXES):
                continue
            totals[key] = totals.get(key, 0) + value
    return totals


def merge_histogram_states(
        state_maps: Sequence[Optional[Dict[str, dict]]]) -> Dict[str, dict]:
    """Merge per-host ``{stage: state}`` histogram exports (the
    ``LatencyHistogram.state()`` shape) by pure bucket-count addition.
    Because bucket counts are integers over module-fixed boundaries, the
    merge is associative and order-free: the pod histogram is
    **bit-identical** to one histogram that recorded every observation
    directly (the float ``sum`` is addition-order sensitive and therefore
    only approximately equal)."""
    merged: Dict[str, dict] = {}
    for states in state_maps:
        for stage, state in (states or {}).items():
            agg = merged.setdefault(stage, {'buckets': {}, 'sum': 0.0,
                                            'count': 0})
            for index, n in (state.get('buckets') or ()):
                index = min(int(index), NUM_BUCKETS)
                agg['buckets'][index] = agg['buckets'].get(index, 0) + int(n)
            agg['sum'] += float(state.get('sum', 0.0))
            agg['count'] += int(state.get('count', 0))
    return {stage: {'buckets': [[i, n]
                                for i, n in sorted(agg['buckets'].items())
                                if n],
                    'sum': agg['sum'], 'count': agg['count']}
            for stage, agg in merged.items()}


def state_percentiles(state: dict) -> Dict[str, Optional[float]]:
    """p50/p90/p99/p999 of one histogram ``state`` — computed by loading
    the bucket counts into a :class:`~petastorm_tpu.latency.LatencyHistogram`
    so the estimator (and its error bound) is the ONE the per-host plane
    uses, not a reimplementation that could drift."""
    histogram = LatencyHistogram()
    histogram.merge_delta({
        'buckets': {int(i): int(n) for i, n in (state.get('buckets') or ())},
        'sum': float(state.get('sum', 0.0)),
        'count': int(state.get('count', 0))})
    return histogram.percentiles()


def merge_health(verdicts_by_host: Dict[str, Optional[dict]]) -> dict:
    """Worst-of health merge with per-host causes **named**: the pod state
    is the worst per-host state (:data:`VERDICT_ORDER`), and every host's
    own state, hint, and ``degraded_causes`` ride out under ``by_host`` so
    "the pod is degraded" always answers "because host X: <cause>"."""
    worst, worst_rank = VERDICT_ORDER[0], 0
    by_host = {}
    causes: List[str] = []
    for host, verdict in sorted(verdicts_by_host.items()):
        verdict = verdict or {}
        state = verdict.get('state') or VERDICT_ORDER[0]
        try:
            rank = VERDICT_ORDER.index(state)
        except ValueError:
            rank = 1    # unknown state: treat as degraded, never healthy
        host_causes = list(verdict.get('degraded_causes') or [])
        by_host[host] = {'state': state, 'hint': verdict.get('hint'),
                        'causes': host_causes}
        causes.extend('{}: {}'.format(host, c) for c in host_causes)
        if rank > worst_rank:
            worst, worst_rank = state, rank
    return {'state': worst, 'by_host': by_host, 'causes': causes}


class PodCertificateError(AssertionError):
    """The pod decode-once certificate failed (or could not be checked
    against a full denominator). ``AssertionError`` so benchmark/CI
    assertion handling treats it like ``CoverageAuditor.assert_complete``'s
    failures."""


def check_pod_certificate(cache_totals: Optional[dict],
                          expected_row_groups: Optional[int] = None,
                          unreachable: Sequence[str] = (),
                          elastic_totals: Optional[dict] = None,
                          expected_batches: Optional[int] = None) -> dict:
    """Machine-check the pod decode-once certificate from summed
    shared-cache counters: ``sum(fills) == distinct row groups`` (every
    row group decoded exactly once somewhere in the pod), with
    ``peer_hits`` tallied as the dedup evidence. An unreachable host makes
    the certificate **uncheckable** — its fills are missing from the sum,
    so the denominator silently shrank; that is reported as a named
    problem, never as a pass.

    When the elasticity plane is on, ``elastic_totals`` (summed
    ``ElasticHost.elastic_snapshot()`` counters) and ``expected_batches``
    (the lease grid's total) extend the certificate to **exactly-once row
    delivery across membership changes**: ``sum(batches_delivered)`` must
    equal the grid total — more means a batch was delivered twice across a
    rebalance, fewer means one was dropped — with
    ``batches_skipped_claimed`` tallied as the fencing evidence (a takeover
    host that found the batch already claimed and did NOT re-deliver it).
    The per-lease naming of any duplicate/drop (host + path + row group)
    comes from ``podelastic.ElasticCoverageAuditor``."""
    cache_totals = cache_totals or {}
    fills = int(cache_totals.get('fills', 0) or 0)
    peer_hits = int(cache_totals.get('peer_hits', 0) or 0)
    problems: List[str] = []
    unreachable = list(unreachable)
    if unreachable:
        problems.append(
            '{}: {} host(s) unreachable ({}) — their fills are missing '
            'from the sum, so the certificate denominator is incomplete; '
            'refusing to certify'.format(PARTIAL_POD, len(unreachable),
                                         ', '.join(map(str, unreachable))))
    checked = expected_row_groups is not None and not unreachable
    if checked:
        expected = int(expected_row_groups)  # type: ignore[arg-type]
        if fills > expected:
            problems.append(
                'duplicate fills: {} fills recorded for {} distinct row '
                'groups — some row group was decoded more than once '
                '(a forged or double-published fill)'.format(fills,
                                                             expected))
        elif fills < expected:
            problems.append(
                'missing fills: {} fills recorded for {} distinct row '
                'groups — either the run is incomplete or a fill counter '
                'was lost'.format(fills, expected))
    elastic_totals = elastic_totals or {}
    delivered = int(elastic_totals.get('batches_delivered', 0) or 0)
    elastic_checked = expected_batches is not None and not unreachable
    if elastic_checked:
        expected_b = int(expected_batches)  # type: ignore[arg-type]
        if delivered > expected_b:
            problems.append(
                'duplicate delivery: {} batches delivered for a {}-batch '
                'lease grid — some batch was delivered more than once '
                'across a rebalance (the delivery claim fence was '
                'bypassed)'.format(delivered, expected_b))
        elif delivered < expected_b:
            problems.append(
                'dropped delivery: {} batches delivered for a {}-batch '
                'lease grid — a batch was lost across a membership '
                'change'.format(delivered, expected_b))
    ok: Optional[bool]
    if unreachable:
        ok = False
    elif checked or elastic_checked:
        ok = not problems
    else:
        ok = None   # nothing to certify against; never a silent pass
    certificate = {'fills': fills, 'peer_hits': peer_hits,
                   'peer_misses': int(cache_totals.get('peer_misses', 0) or 0),
                   'peer_errors': int(cache_totals.get('peer_errors', 0) or 0),
                   'expected_row_groups': expected_row_groups,
                   'unreachable': unreachable,
                   'checked': checked, 'ok': ok, 'problems': problems}
    if expected_batches is not None or elastic_totals:
        certificate['elastic'] = {
            'batches_delivered': delivered,
            'batches_skipped_claimed': int(
                elastic_totals.get('batches_skipped_claimed', 0) or 0),
            'leases_rebalanced': int(
                elastic_totals.get('leases_rebalanced', 0) or 0),
            'rows_resumed': int(elastic_totals.get('rows_resumed', 0) or 0),
            'expected_batches': expected_batches,
            'checked': elastic_checked,
        }
    return certificate


def check_pod_goodput(goodput_by_host: Optional[Dict[str, Optional[dict]]],
                      min_goodput: Optional[float] = None,
                      unreachable: Sequence[str] = ()) -> dict:
    """The pod goodput verdict from per-host ``/goodput`` summaries
    (``GoodputMonitor.summary()`` shape): the pod fractions are re-derived
    from the SUMMED per-host seconds — never averaged, so a straggler
    cannot hide behind K-1 healthy hosts' means — and the worst-stalling
    host is **named** as the straggler. ``min_goodput`` arms the check
    (the same ``[0, 1]`` target the SLOMonitor takes); an unreachable host
    makes the verdict uncheckable the way :func:`check_pod_certificate`'s
    is — a named :data:`PARTIAL_POD` refusal, never a silent pass."""
    totals = {'steps': 0, 'fenced_steps': 0, 'total_s': 0.0, 'stall_s': 0.0,
              'h2d_s': 0.0, 'device_s': 0.0, 'host_s': 0.0}
    by_host: Dict[str, dict] = {}
    for host, section in sorted((goodput_by_host or {}).items()):
        state = (section or {}).get('state') or {}
        total = float(state.get('total_s', 0.0) or 0.0)
        if total <= 0.0:
            continue
        stall = float(state.get('stall_s', 0.0) or 0.0)
        h2d = float(state.get('h2d_s', 0.0) or 0.0)
        device = float(state.get('device_s', 0.0) or 0.0)
        totals['steps'] += int(state.get('steps', 0) or 0)
        totals['fenced_steps'] += int(state.get('fenced_steps', 0) or 0)
        totals['total_s'] += total
        totals['stall_s'] += stall
        totals['h2d_s'] += h2d
        totals['device_s'] += device
        totals['host_s'] += float(state.get('host_s', 0.0) or 0.0)
        by_host[host] = {
            'steps': int(state.get('steps', 0) or 0),
            'goodput_fraction': round(device / total, 4),
            'data_stall_fraction': round((stall + h2d) / total, 4),
        }
    pod_total = totals['total_s']
    goodput_fraction = (round(totals['device_s'] / pod_total, 4)
                        if pod_total > 0 else None)
    data_stall_fraction = (
        round((totals['stall_s'] + totals['h2d_s']) / pod_total, 4)
        if pod_total > 0 else None)
    straggler = None
    if by_host:
        worst = max(by_host, key=lambda h: by_host[h]['data_stall_fraction'])
        straggler = dict(by_host[worst], host=worst)
    problems: List[str] = []
    unreachable = list(unreachable)
    if unreachable:
        problems.append(
            '{}: {} host(s) unreachable ({}) — their step seconds are '
            'missing from the sum; refusing to certify pod goodput'.format(
                PARTIAL_POD, len(unreachable),
                ', '.join(map(str, unreachable))))
    checked = (min_goodput is not None and goodput_fraction is not None
               and not unreachable)
    if checked and goodput_fraction < float(min_goodput):  # type: ignore[arg-type]
        detail = ''
        if straggler is not None:
            detail = (' — straggler {}: data_stall_fraction {}, '
                      'goodput_fraction {}'.format(
                          straggler['host'],
                          straggler['data_stall_fraction'],
                          straggler['goodput_fraction']))
        problems.append('pod goodput {} below min_goodput {}{}'.format(
            goodput_fraction, float(min_goodput), detail))
    ok: Optional[bool]
    if unreachable:
        ok = False
    elif checked:
        ok = not problems
    else:
        ok = None   # no target or no data; never a silent pass
    return {'goodput_fraction': goodput_fraction,
            'data_stall_fraction': data_stall_fraction,
            'totals': totals, 'by_host': by_host, 'straggler': straggler,
            'min_goodput': min_goodput, 'unreachable': unreachable,
            'checked': checked, 'ok': ok, 'problems': problems}


# -- the aggregator -----------------------------------------------------------

class PodObserver:
    """Poll a pod's per-host ``/observe/snapshot`` surfaces and merge them
    into one report.

    Embeddable (a reader serves :meth:`report` on ``/podmetrics`` when
    :data:`PODOBS_PEERS_ENV_VAR` names the pod), scriptable
    (``petastorm-tpu-podstat`` — :func:`main`), and callable from
    benchmarks/tests. Never spawns a thread: every poll happens on the
    caller's thread, so the kill switch truly means "no pod-plane
    machinery exists".

    ``expected_row_groups`` arms the decode-once certificate;
    :meth:`assert_certificate` raises :class:`PodCertificateError` the way
    ``CoverageAuditor.assert_complete`` raises on a coverage hole."""

    def __init__(self, peers, timeout_s: float = DEFAULT_TIMEOUT_S,
                 expected_row_groups: Optional[int] = None,
                 trace_id: Optional[str] = None,
                 expected_batches: Optional[int] = None,
                 min_goodput: Optional[float] = None):
        self.peers = parse_peers(peers)
        if not self.peers:
            raise ValueError('PodObserver needs at least one host:port peer')
        self.timeout_s = float(timeout_s)
        self.expected_row_groups = expected_row_groups
        self.expected_batches = expected_batches
        #: Arms the pod goodput verdict (:func:`check_pod_goodput`): the
        #: pod-wide goodput fraction (re-derived from summed seconds) must
        #: meet this floor, with the straggler host named on breach.
        self.min_goodput = min_goodput
        self.trace_id = trace_id or new_trace_id()
        self.last_report: Optional[dict] = None

    # -- polling ---------------------------------------------------------------

    def fetch_snapshot(self, peer: str) -> dict:
        """Fetch one peer's snapshot, annotating it with the poll metadata:
        ``_peer``, ``_rtt_s``, and ``_clock_offset_s`` — the estimate
        ``remote_clock - (t0 + t1) / 2``, i.e. what to ADD to a local
        ``perf_counter`` reading to land on that host's clock (good to
        about half the RTT)."""
        url = 'http://{}{}'.format(peer, SNAPSHOT_ROUTE)
        request = urllib.request.Request(
            url, headers={TRACE_HEADER: self.trace_id})
        t0 = time.perf_counter()
        with urllib.request.urlopen(request,
                                    timeout=self.timeout_s) as response:
            body = response.read()
            t1 = time.perf_counter()
            clock_header = response.headers.get(CLOCK_HEADER)
        snapshot = json.loads(body.decode('utf-8'))
        remote_clock = None
        if clock_header:
            try:
                remote_clock = float(clock_header)
            except ValueError:
                remote_clock = None
        if remote_clock is None:
            remote_clock = snapshot.get('clock_s')
        snapshot['_peer'] = peer
        snapshot['_rtt_s'] = t1 - t0
        snapshot['_clock_offset_s'] = (
            remote_clock - (t0 + t1) / 2.0
            if isinstance(remote_clock, (int, float)) else None)
        return snapshot

    def poll(self) -> Tuple[List[dict], List[dict]]:
        """``(snapshots, unreachable)``: every peer that answered, and a
        named ``{'peer', 'error'}`` record for every one that did not."""
        snapshots, unreachable = [], []
        for peer in self.peers:
            try:
                snapshots.append(self.fetch_snapshot(peer))
            except Exception as e:  # noqa: BLE001 - a dead peer is a verdict
                unreachable.append({'peer': peer,
                                    'error': '{}: {}'.format(
                                        type(e).__name__, e)})
        return snapshots, unreachable

    # -- merging ---------------------------------------------------------------

    def merge(self, snapshots: List[dict],
              unreachable: Optional[List[dict]] = None) -> dict:
        """Merge polled snapshots into the pod report (pure function of its
        inputs — tests drive it with simulated hosts, no HTTP needed)."""
        unreachable = list(unreachable or [])
        hosts = []
        health_by_host: Dict[str, Optional[dict]] = {}
        stats_list, histogram_maps, cache_list = [], [], []
        elastic_list: List[Optional[dict]] = []
        goodput_by_host: Dict[str, Optional[dict]] = {}
        slo_burns: Dict[str, float] = {}
        hard_breach_hosts: List[str] = []
        coverage_by_host = {}
        trace_tracks = []
        for snapshot in snapshots:
            label = str(snapshot.get('_peer') or snapshot.get('host'))
            health = snapshot.get('health')
            hosts.append({
                'peer': snapshot.get('_peer'),
                'host': snapshot.get('host'),
                'pid': snapshot.get('pid'),
                'rtt_s': snapshot.get('_rtt_s'),
                'clock_offset_s': snapshot.get('_clock_offset_s'),
                'state': (health or {}).get('state'),
            })
            health_by_host[label] = health
            stats_list.append(snapshot.get('stats'))
            histogram_maps.append(snapshot.get('latency_histograms'))
            cache_list.append(snapshot.get('cache'))
            elastic_list.append(snapshot.get('elastic'))
            goodput = snapshot.get('goodput')
            if goodput is not None:
                goodput_by_host[label] = goodput
            slo = snapshot.get('slo') or {}
            burn = slo.get('burn_rate')
            if isinstance(burn, (int, float)):
                slo_burns[label] = float(burn)
            if slo.get('hard_breach'):
                hard_breach_hosts.append(label)
            coverage = snapshot.get('coverage')
            if coverage is not None:
                coverage_by_host[label] = coverage
            span_tail = snapshot.get('span_tail')
            if span_tail:
                trace_tracks.append({
                    'host': label,
                    'pid': snapshot.get('pid'),
                    'clock_offset_s': snapshot.get('_clock_offset_s'),
                    'spans': span_tail,
                })
        merged_histograms = merge_histogram_states(histogram_maps)
        latency = {}
        for stage, state in sorted(merged_histograms.items()):
            entry = {'count': state['count'],
                     'sum_s': round(state['sum'], 6)}
            for name, value in state_percentiles(state).items():
                entry[name + '_s'] = (round(value, 9)
                                      if value is not None else None)
            latency[stage] = entry
        health = merge_health(health_by_host)
        cache_totals = merge_counters(cache_list)
        elastic_totals = merge_counters(elastic_list)
        certificate = check_pod_certificate(
            cache_totals, self.expected_row_groups,
            unreachable=[u['peer'] for u in unreachable],
            elastic_totals=elastic_totals,
            expected_batches=self.expected_batches)
        goodput = check_pod_goodput(
            goodput_by_host, min_goodput=self.min_goodput,
            unreachable=[u['peer'] for u in unreachable])
        verdict = PARTIAL_POD if unreachable else health['state']
        report = {
            'kind': 'petastorm_tpu.podmetrics',
            'version': 1,
            'trace_id': self.trace_id,
            'peers': list(self.peers),
            'hosts': hosts,
            'hosts_reporting': len(snapshots),
            'unreachable': unreachable,
            'verdict': verdict,
            'health': health,
            'counters': merge_counters(stats_list),
            'latency': latency,
            'latency_histograms': merged_histograms,
            'quantile_rel_error_bound': QUANTILE_REL_ERROR_BOUND,
            'slo': {'burn_rate_by_host': slo_burns,
                    'worst_burn_rate': (max(slo_burns.values())
                                        if slo_burns else None),
                    'hard_breach_hosts': hard_breach_hosts},
            'coverage': coverage_by_host,
            'cache': {'totals': cache_totals,
                      'by_host': {str(h.get('peer') or h.get('host')):
                                  c for h, c in zip(hosts, cache_list)
                                  if c is not None}},
            'elastic': {'totals': elastic_totals,
                        'by_host': {str(h.get('peer') or h.get('host')):
                                    e for h, e in zip(hosts, elastic_list)
                                    if e is not None}},
            'certificate': certificate,
            'goodput': goodput,
            'trace_tracks': trace_tracks,
        }
        self.last_report = report
        return report

    def report(self) -> dict:
        """One poll + merge round: THE pod report (also what an aggregator
        host serves on :data:`PODMETRICS_ROUTE`)."""
        snapshots, unreachable = self.poll()
        return self.merge(snapshots, unreachable)

    def assert_certificate(self, report: Optional[dict] = None) -> dict:
        """Machine-check the decode-once certificate of ``report`` (or of a
        fresh :meth:`report`): raises :class:`PodCertificateError` naming
        every problem — duplicate/missing fills, or an unreachable host
        that makes the denominator incomplete."""
        if report is None:
            report = self.report()
        certificate = report.get('certificate') or {}
        if certificate.get('ok') is True:
            return certificate
        problems = list(certificate.get('problems') or [])
        if certificate.get('ok') is None:
            problems.append('certificate unchecked: pass '
                            'expected_row_groups to arm it')
        raise PodCertificateError(
            'pod decode-once certificate failed: ' + '; '.join(problems))

    def export_pod_chrome_trace(self, path: str,
                                report: Optional[dict] = None) -> str:
        """Stitch the polled hosts' span tails into one clock-aligned
        chrome trace (``chrome://tracing`` / Perfetto) at ``path``."""
        if report is None:
            report = self.last_report or self.report()
        from petastorm_tpu.tracing import stitch_pod_trace
        return stitch_pod_trace(report.get('trace_tracks') or [], path)


# -- CLI ----------------------------------------------------------------------

def main(argv=None) -> int:
    """``petastorm-tpu-podstat``: poll a pod's debug endpoints and print
    the merged report. Exits 1 on a :data:`PARTIAL_POD` verdict or (with
    ``--expect-row-groups``) a failed certificate — scriptable the way
    ``/healthz`` status codes are."""
    parser = argparse.ArgumentParser(
        prog='petastorm-tpu-podstat',
        description='Aggregate pod-wide petastorm-tpu observability: poll '
                    'each host\'s /observe/snapshot and merge counters, '
                    'latency histograms, health, and the decode-once '
                    'certificate onto one surface.')
    parser.add_argument('peers', nargs='?', default=None,
                        help='comma-separated host:port list of debug '
                             'endpoints (default: ${})'.format(
                                 PODOBS_PEERS_ENV_VAR))
    parser.add_argument('--timeout', type=float, default=DEFAULT_TIMEOUT_S,
                        help='per-peer poll timeout in seconds '
                             '(default %(default)s)')
    parser.add_argument('--expect-row-groups', type=int, default=None,
                        help='arm the decode-once certificate: the number '
                             'of distinct row groups the pod must have '
                             'decoded exactly once')
    parser.add_argument('--min-goodput', type=float, default=None,
                        help='arm the pod goodput verdict: the pod-wide '
                             'goodput fraction (summed seconds, straggler '
                             'named) must meet this [0, 1] floor')
    parser.add_argument('--trace-out', default=None,
                        help='also write the stitched pod chrome trace '
                             'to this path')
    parser.add_argument('--compact', action='store_true',
                        help='single-line JSON output')
    args = parser.parse_args(argv)
    peers = args.peers or os.environ.get(PODOBS_PEERS_ENV_VAR, '')
    if not parse_peers(peers):
        parser.error('no peers: pass host:port[,host:port...] or set '
                     '{}'.format(PODOBS_PEERS_ENV_VAR))
    observer = PodObserver(peers, timeout_s=args.timeout,
                           expected_row_groups=args.expect_row_groups,
                           min_goodput=args.min_goodput)
    report = observer.report()
    print(json.dumps(report, indent=None if args.compact else 2,
                     sort_keys=True, default=str))
    if args.trace_out:
        observer.export_pod_chrome_trace(args.trace_out, report)
        print('pod trace written to {}'.format(args.trace_out),
              file=sys.stderr)
    if report['verdict'] == PARTIAL_POD:
        return 1
    if args.expect_row_groups is not None:
        try:
            observer.assert_certificate(report)
        except PodCertificateError as e:
            print(str(e), file=sys.stderr)
            return 1
    if args.min_goodput is not None:
        goodput = report.get('goodput') or {}
        if goodput.get('ok') is not True:
            for problem in goodput.get('problems') or (
                    'pod goodput unchecked: no host reported step data',):
                print(problem, file=sys.stderr)
            return 1
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
