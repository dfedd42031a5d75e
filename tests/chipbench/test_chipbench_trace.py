"""The trace reduction: interval arithmetic, and the whole reduction on
small traces recorded on one and on four v5e chips by
``chipbench/record_fixture.py`` (six steps of a tiny
``chipbench_train_step``, each behind about 2 ms of host sleep in an
``infeed_wait`` span; on four chips its gradient is all-reduced)."""

import json
import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(__file__), 'data')


def fixture(chips):
    path = os.path.join(DATA, 'v5e_{}chip.xplane.pb'.format(chips))
    with open(path + '.host.json') as f:
        return path, json.load(f)


def test_merge_joins_overlapping_and_touching_intervals():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_subtract_and_gaps():
    busy = [(2, 4), (6, 7)]
    assert trace.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert trace.subtract([(0, 10)], [(0, 10)]) == []
    assert trace.total(trace.clip([(-5, 3), (8, 20)], 0, 10)) == 5


@pytest.mark.parametrize('chips', [1, 4])
def test_fixture_reduces_to_the_known_structure(chips):
    path, host = fixture(chips)
    s = trace.reduce(path, 'chipbench_train_step', host)
    assert s.devices == chips
    assert 0 < s.busy_s < s.window_s
    assert all(0 < share < 1 for share in s.idle_share)
    assert s.program_runs == pytest.approx(6, abs=1)
    assert s.program_s > 0
    if chips == 1:
        assert s.collective_s == 0 and s.collective_exposed_s == 0
    else:
        assert 0 < s.collective_exposed_s <= s.collective_s < s.busy_s
    assert s.top_ops and all(seconds > 0 for _, seconds in s.top_ops)
    # the device idles behind the host's sleeps, and the gaps say so
    names = [name for name, _ in s.idle_gaps]
    assert names[0] in trace.HOST_SPANS
    assert 'infeed_wait' in names
    # the gaps are the idlest chip's; busy_s is the mean over the chips
    assert sum(sec for _, sec in s.idle_gaps) == pytest.approx(
        s.window_s * max(s.idle_share), rel=1e-6)


def test_host_clock_is_put_on_the_device_clock_by_the_marks():
    path, host = fixture(1)
    marks = [(s, e) for plane in trace.load(path).planes
             if trace.DEVICE_PLANE.match(plane.name)
             for line in plane.lines if line.name == 'XLA Modules'
             for n, s, e in trace._events(line) if trace.MARK in n]
    shift = trace.offset_ns(marks, host['marks'])
    # each mark's device run lies inside the host interval around it
    for (ds, de), (hs, he) in zip(sorted(marks), host['marks']):
        assert hs + shift - 1e6 < ds and de < he + shift + 1e6


def test_marks_must_match(tmp_path):
    with pytest.raises(ValueError, match='device runs'):
        trace.offset_ns([(0, 1)], [(0, 1), (5, 6)])
