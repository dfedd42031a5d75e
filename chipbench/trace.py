"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy and idle time over the benchmark's window, the
device time of the train-step program, collective time and the part of it
no compute overlaps, the device ops that took most time, and each idle gap
named by the benchmark's host span that was open in it.

Device planes are ``/device:TPU:<n>``, whose ``XLA Ops`` line holds one
event per executed op and ``XLA Modules`` one per program run. The host
side is not traced: at host tracer level 1 the TPU runtime's own threads
wrote 664 MB of events in 6 s of the decode cell and cut its rate to a
third (PERF.md, PR 22). The benchmark keeps its own spans on the host
clock (``perf_counter_ns``) and brackets the window with two runs of a
tiny program, ``chipbench_mark``; each mark's device run, set against the
host interval that dispatched and awaited it, gives the offset that puts
the host spans on the device's clock.
"""

import collections
import dataclasses
import os
import re
import time

DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
COLLECTIVE = re.compile(
    r'all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all',
    re.IGNORECASE)
MARK = 'chipbench_mark'
#: The benchmark's host spans, by which an idle gap is named.
HOST_SPANS = ('infeed_wait', 'dispatch', 'block')


@dataclasses.dataclass
class Summary:
    window_s: float
    devices: int
    busy_s: float                  # mean over devices
    idle_share: list               # per device, 0..1
    program_s: float               # mean over devices
    program_runs: float            # mean over devices
    collective_s: float            # mean over devices
    collective_exposed_s: float    # mean over devices
    top_ops: list                  # [[name, seconds summed over devices]]
    idle_gaps: list                # [[host span, seconds]], longest first


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(i) for i in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, cover):
    """Parts of ``intervals`` (disjoint) that ``cover`` (disjoint) leaves
    uncovered."""
    out = []
    for start, end in intervals:
        cursor = start
        for cs, ce in cover:
            if ce <= cursor or cs >= end:
                continue
            if cs > cursor:
                out.append((cursor, cs))
            cursor = max(cursor, ce)
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(busy, lo, hi):
    """Idle intervals of ``[lo, hi]`` between merged busy intervals."""
    return subtract([(lo, hi)], busy)


def op_name(text):
    """An op's HLO name from the trace's event name, which is the op's
    whole HLO line (``%fusion.26 = bf16[...] fusion(...)``): operands name
    other ops, so only the part before `` = `` says what this op is."""
    return text.split(' = ', 1)[0].lstrip('%')


def _events(line):
    return [(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def offset_ns(device_marks, host_marks):
    """Device clock minus host clock: the mean, over the marks, of the
    midpoint of each mark's device run less the midpoint of the host
    interval that dispatched it and waited for it."""
    if len(device_marks) != len(host_marks) or not host_marks:
        raise ValueError('{} device runs of {!r} for {} host marks'.format(
            len(device_marks), MARK, len(host_marks)))
    pairs = zip(sorted(device_marks), host_marks)
    return sum((ds + de) / 2 - (hs + he) / 2
               for (ds, de), (hs, he) in pairs) / len(host_marks)


def reduce(path, program, host):
    """The :class:`Summary` of the trace at ``path``. ``program`` is the
    train step's stable name; ``host`` is the benchmark's record on the host
    clock, in ``perf_counter_ns``: ``window`` ``[t0, t1]``, ``marks`` (the
    host interval around each ``chipbench_mark`` run) and ``spans``
    (``[name, start, end]``)."""
    devices = []
    for plane in load(path).planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append((plane.name, {line.name: _events(line)
                                         for line in plane.lines}))
    if not devices:
        raise ValueError('{}: no device plane'.format(path))
    devices.sort()
    marks = [(s, e) for n, s, e in devices[0][1].get('XLA Modules', [])
             if MARK in n]
    shift = offset_ns(marks, host['marks'])
    lo, hi = (t + shift for t in host['window'])
    spans = [(name, s + shift, e + shift) for name, s, e in host['spans']]
    busy_ns, idle_share, program_ns, runs = [], [], [], []
    coll_ns, exposed_ns = [], []
    op_ns = collections.Counter()
    worst_gaps = None
    for _, lines in devices:
        ops = [(n, s, e) for n, s, e in lines.get('XLA Ops', [])
               if e > lo and s < hi]
        busy = clip(merge((s, e) for _, s, e in ops), lo, hi)
        busy_ns.append(total(busy))
        idle_share.append(1.0 - busy_ns[-1] / (hi - lo))
        for name, s, e in ops:
            op_ns[name] += min(e, hi) - max(s, lo)
        modules = [(s, e) for n, s, e in lines.get('XLA Modules', [])
                   if program in n and e > lo and s < hi]
        program_ns.append(total(clip(modules, lo, hi)))
        runs.append(len(modules))
        coll = clip(merge((s, e) for n, s, e in ops if COLLECTIVE.search(n)),
                    lo, hi)
        compute = merge((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
        coll_ns.append(total(coll))
        exposed_ns.append(total(subtract(coll, compute)))
        device_gaps = gaps(busy, lo, hi)
        if worst_gaps is None or total(device_gaps) > total(worst_gaps):
            worst_gaps = device_gaps
    named = []
    for start, end in worst_gaps:
        overlap = collections.Counter()
        for name, s, e in spans:
            if e > start and s < end:
                overlap[name] += min(e, end) - max(s, start)
        name = overlap.most_common(1)[0][0] if overlap else 'other'
        named.append([name, (end - start) / 1e9])
    named.sort(key=lambda g: -g[1])
    n = len(devices)
    return Summary(
        window_s=(hi - lo) / 1e9, devices=n, busy_s=sum(busy_ns) / n / 1e9,
        idle_share=idle_share, program_s=sum(program_ns) / n / 1e9,
        program_runs=sum(runs) / n, collective_s=sum(coll_ns) / n / 1e9,
        collective_exposed_s=sum(exposed_ns) / n / 1e9,
        top_ops=[[name, ns / 1e9] for name, ns in op_ns.most_common(10)],
        idle_gaps=named)


class Recorder:
    """The benchmark's side of a traced window: the profiler with host and
    Python tracing off, a ``chipbench_mark`` run on ``device`` at each end,
    and the host record :func:`reduce` reads."""

    def __init__(self, device):
        import jax
        import jax.numpy as jnp

        def chipbench_mark(x):
            return x + 1

        self._mark = jax.jit(chipbench_mark)
        self._x = jax.device_put(jnp.zeros((), jnp.int32), device)
        self._mark(self._x).block_until_ready()     # compiled before tracing
        self.marks = []

    def mark(self):
        start = time.perf_counter_ns()
        self._mark(self._x).block_until_ready()
        self.marks.append((start, time.perf_counter_ns()))

    def start(self, directory):
        import jax
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 0
        options.python_tracer_level = 0
        jax.profiler.start_trace(directory, profiler_options=options)
        self.mark()

    def stop(self, directory):
        """Ends the trace; returns the path of its ``.xplane.pb``."""
        import jax
        self.mark()
        jax.profiler.stop_trace()
        found = [os.path.join(d, f) for d, _, fs in os.walk(directory)
                 for f in fs if f.endswith('.xplane.pb')]
        if len(found) != 1:
            raise ValueError('{} traces under {}'.format(len(found), directory))
        return found[0]


def describe(path, events_per_line=3):
    """The planes, lines and a few events of a trace, for a first look."""
    out = []
    for plane in load(path).planes:
        out.append('PLANE {}'.format(plane.name))
        for line in plane.lines:
            events = list(line.events)
            out.append('  LINE {!r}: {} events'.format(line.name, len(events)))
            for e in events[:events_per_line]:
                out.append('    {!r} start {} dur {} stats {}'.format(
                    e.name, e.start_ns, e.duration_ns, dict(e.stats)))
    return '\n'.join(out)
