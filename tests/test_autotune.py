"""Autotune controller + live-actuator tests (see docs/autotune.md).

Covers the hard contracts from the acceptance criteria:

- pool resize (thread AND process) mid-epoch preserves exactly-once
  delivery per the lineage ``CoverageAuditor``, and the no-dangling-threads
  conftest fixture passes (this module is in ``_THREAD_GUARDED_MODULES``);
- the controller converges on an injected io-bound reader (raises
  readahead) and an injected decode-bound reader (raises workers);
- revert-on-regression fires on a rigged model (predicted gain, measured
  collapse) and quarantines the (knob, direction);
- the kill switch creates no controller thread and no scratch files;
- every action is observable: ``/autotune`` route, flight-record section,
  ``/metrics`` gauges, ``report()`` prediction grading;
- the host arbiter splits the CPU budget proportionally to measured
  deficit and ignores stale peers.

Runs under the lockdep-lite harness in CI (``petastorm_tpu.autotune`` is a
lockdep target module).
"""

import json
import os
import threading
import time
import urllib.request

import pytest

from petastorm_tpu.autotune import (AUTOTUNE_DIR_ENV_VAR, AUTOTUNE_ENV_VAR,
                                    HostArbiter, PipelineController,
                                    resolve_autotune, scratch_dir)
from petastorm_tpu.reader import make_reader
from petastorm_tpu.readers.readahead import RowGroupReadahead
from petastorm_tpu.workers.thread_pool import ThreadPool
from petastorm_tpu.workers.ventilator import ConcurrentVentilator

# ---------------------------------------------------------------------------
# fakes
# ---------------------------------------------------------------------------


class FakeActuators:
    """In-memory actuator set; every set_* is recorded."""

    pool_type = 'thread'

    def __init__(self, workers=1, readahead=0, vent=4, qbound=50):
        self.workers = workers
        self.readahead = readahead
        self.vent = vent
        self.qbound = qbound
        self.calls = []

    def get_workers(self):
        return self.workers

    def set_workers(self, n):
        self.calls.append(('workers', n))
        self.workers = n
        return n

    def get_readahead(self):
        return self.readahead

    def set_readahead(self, k):
        self.calls.append(('readahead', k))
        self.readahead = k
        return k

    def get_vent_window(self):
        return self.vent

    def set_vent_window(self, n):
        self.vent = n
        return n

    def get_queue_bound(self):
        return self.qbound

    def set_queue_bound(self, n):
        self.calls.append(('qbound', n))
        self.qbound = n
        return n

    def reap(self):
        pass


def make_controller(actuators, snapshot_state, ceilings, cpu_count=4,
                    clock=None, latency=None, slo_targets=None,
                    options=None):
    """A headless controller over fakes; ``snapshot_state`` is a mutable
    dict whose 'items_out' the test advances between ticks."""
    calibration = {'ceilings': ceilings, 'cpu_count': cpu_count,
                   'rows_per_group': 10.0}

    def snapshot():
        base = {'worker_io_s': 0.0, 'worker_decode_s': 0.0,
                'readahead_io_s': 0.0, 'readahead_wait_s': 0.0,
                'worker_publish_wait_s': 0.0, 'queue_wait_s': 0.0,
                'bytes_moved': 0}
        base.update(snapshot_state)
        return base

    return PipelineController(actuators, snapshot,
                              calibration_fn=lambda: calibration,
                              latency=latency, slo_targets=slo_targets,
                              options=options,
                              clock=clock or time.perf_counter)


def run_ticks(controller, clock_box, state, n, rate_fn):
    for _ in range(n):
        clock_box[0] += 5.0
        state['items_out'] = state.get('items_out', 0) + rate_fn()
        controller.tick()


# ---------------------------------------------------------------------------
# controller policy (injected sensors + model)
# ---------------------------------------------------------------------------


def test_converges_decode_bound_raises_workers():
    """io ceiling huge, decode small: the model's best neighbors walk
    workers up to the cpu budget, one hysteresis-clearing move at a time."""
    clock = [0.0]
    state = {'items_out': 0, 'worker_decode_s': 5.0, 'worker_io_s': 0.1}
    act = FakeActuators(workers=1, readahead=1)
    c = make_controller(act, state, {'io': 10000.0, 'decode': 100.0},
                        cpu_count=4, clock=lambda: clock[0])
    run_ticks(c, clock, state, 10, lambda: 50)
    assert act.workers == 4
    knobs = [(a['knob'], a['direction']) for a in c.actions()]
    assert knobs == [('workers_count', 'up')] * 3
    # companion: the ventilation window followed every worker move
    assert act.vent == 4 * (1 + act.readahead) + 2


def test_converges_io_bound_raises_readahead():
    """io ceiling binds and readahead is off: overlapping beats harmonic by
    >hysteresis, so the controller turns readahead on."""
    clock = [0.0]
    state = {'items_out': 0, 'worker_io_s': 5.0, 'worker_decode_s': 1.0}
    act = FakeActuators(workers=1, readahead=0)
    c = make_controller(act, state, {'io': 100.0, 'decode': 400.0},
                        cpu_count=2, clock=lambda: clock[0])
    run_ticks(c, clock, state, 6, lambda: 50)
    assert act.readahead >= 1
    assert ('io_readahead', 'up') in [(a['knob'], a['direction'])
                                      for a in c.actions()]


def test_revert_on_regression_fires_and_quarantines():
    """Rigged model: predicted +100% from a second worker, measured -80%
    (the GIL-convoy shape). The move must be undone and that
    (knob, direction) locked out for quarantine_ticks."""
    clock = [0.0]
    state = {'items_out': 0, 'worker_decode_s': 5.0}
    act = FakeActuators(workers=1, readahead=1)
    c = make_controller(act, state, {'io': 10000.0, 'decode': 100.0},
                        cpu_count=4, clock=lambda: clock[0])
    run_ticks(c, clock, state, 8, lambda: 50 if act.workers == 1 else 10)
    assert act.workers == 1           # moved up, measured, reverted
    report = c.report()
    assert report['reverts_total'] == 1
    assert report['quarantined'] == [{'knob': 'workers_count',
                                      'direction': 'up',
                                      'until_tick': report['quarantined'][0][
                                          'until_tick']}]
    graded = [a for a in c.actions()
              if a.get('prediction_error_pct') is not None]
    assert graded and graded[0]['measured_delta_pct'] < -10.0
    # while quarantined, no further up move happened
    ups = [a for a in c.actions() if a['direction'] == 'up']
    assert len(ups) == 1


def test_hysteresis_blocks_sub_threshold_gains():
    """A predicted gain below hysteresis_pct is noise, not a move."""
    clock = [0.0]
    state = {'items_out': 0, 'worker_io_s': 5.0, 'worker_decode_s': 1.0}
    act = FakeActuators(workers=1, readahead=0)
    # io 100 / decode 1000: overlap gain = 100/90.9 - 1 = 10% exactly at
    # the default threshold boundary; with hysteresis at 15 nothing moves
    c = make_controller(act, state, {'io': 100.0, 'decode': 1000.0},
                        cpu_count=2, clock=lambda: clock[0],
                        options={'hysteresis_pct': 15.0})
    run_ticks(c, clock, state, 5, lambda: 50)
    assert act.readahead == 0 and act.workers == 1
    assert c.actions() == []


def test_slo_constraint_blocks_predicted_breach():
    """A candidate whose (crude) predicted p99 breaches the reader's
    p99_e2e_ms target is never taken, even with a predicted throughput
    gain."""

    class FakeLatency:
        def window_p99s(self):
            return {'e2e_batch': 0.100, 'queue_wait': 0.001}

        def quantile(self, stage, q, window=False):
            return 0.0005

    clock = [0.0]
    state = {'items_out': 0, 'worker_io_s': 5.0, 'worker_decode_s': 1.0}
    act = FakeActuators(workers=1, readahead=0)
    # cpu_count=1 keeps workers off the table: the only candidate with a
    # predicted gain is readahead 0->1, and that one must be SLO-blocked
    calibration = {'ceilings': {'io': 100.0, 'decode': 400.0},
                   'cpu_count': 1, 'rows_per_group': 10.0}

    def snapshot():
        return dict(state, readahead_io_s=0.0, readahead_wait_s=0.0,
                    worker_publish_wait_s=0.0, queue_wait_s=0.0,
                    bytes_moved=0)

    # readahead 0->1 grows the buffering capacity (capacity_scale > 1), and
    # the measured window p99 (100ms) already sits AT the target: the
    # predicted p99 breaches, so the move is blocked
    c = PipelineController(act, snapshot,
                           calibration_fn=lambda: calibration,
                           latency=FakeLatency(),
                           slo_targets={'p99_e2e_ms': 100.0},
                           clock=lambda: clock[0])
    run_ticks(c, clock, state, 5, lambda: 50)
    assert act.readahead == 0
    assert c.actions() == []


def test_tail_stall_raises_queue_bound():
    """Sensor-driven move: queue-wait p99 dwarfing p50 (the tail-stall
    verdict) asks for a deeper results queue — no throughput model term
    involved."""

    class StallLatency:
        def window_p99s(self):
            return {'queue_wait': 0.2}

        def quantile(self, stage, q, window=False):
            return 0.0001      # p50: most deliveries instant

    clock = [0.0]
    state = {'items_out': 0, 'worker_decode_s': 1.0, 'worker_io_s': 1.0}
    act = FakeActuators(workers=1, readahead=1, qbound=50)

    def snapshot():
        return dict(state, readahead_io_s=0.0, readahead_wait_s=0.0,
                    worker_publish_wait_s=0.0, queue_wait_s=0.0,
                    bytes_moved=0)

    c = PipelineController(act, snapshot, calibration_fn=lambda: None,
                           latency=StallLatency(), clock=lambda: clock[0])
    run_ticks(c, clock, state, 4, lambda: 50)
    assert act.qbound > 50
    sensor_moves = [a for a in c.actions() if a['policy'] == 'sensor']
    assert sensor_moves and sensor_moves[0]['knob'] == 'results_queue_bound'


def test_report_grades_predictions():
    clock = [0.0]
    state = {'items_out': 0, 'worker_decode_s': 5.0, 'worker_io_s': 0.1}
    act = FakeActuators(workers=1, readahead=1)
    c = make_controller(act, state, {'io': 10000.0, 'decode': 100.0},
                        cpu_count=2, clock=lambda: clock[0])
    # perfect model: rate doubles when workers double
    run_ticks(c, clock, state, 6, lambda: 50 * act.workers)
    report = c.report()
    assert report['prediction']['graded'] >= 1
    assert report['prediction']['mean_abs_error_pct'] is not None
    assert report['prediction']['direction_accuracy'] == 1.0
    action = [a for a in c.actions() if a.get('graded') == 'measured'][0]
    assert action['predicted_gain_pct'] == pytest.approx(100.0, abs=1.0)
    assert action['measured_delta_pct'] == pytest.approx(100.0, abs=5.0)


def test_options_validation_rejects_typos():
    with pytest.raises(ValueError, match='unknown autotune option'):
        resolve_autotune({'tick_intervall_s': 5})
    with pytest.raises(ValueError, match='tick_interval_s'):
        resolve_autotune({'tick_interval_s': 0})
    assert resolve_autotune(False) is None
    assert resolve_autotune(None) is None
    # every falsy non-dict spelling means OFF (autotune=0 must never
    # start a controller)
    assert resolve_autotune(0) is None
    assert resolve_autotune('') is None
    assert resolve_autotune(True)['tick_interval_s'] == 5.0
    # an EMPTY options dict means "on, all defaults" — not off
    assert resolve_autotune({})['tick_interval_s'] == 5.0


# ---------------------------------------------------------------------------
# live actuators on real pools
# ---------------------------------------------------------------------------


def _readahead_url(tmp_path, rows=96, rows_per_group=8):
    from petastorm_tpu.test_util.dataset_gen import generate_readahead_dataset
    url = 'file://' + str(tmp_path / 'ds')
    generate_readahead_dataset(url, rows=rows, rows_per_group=rows_per_group)
    return url


@pytest.mark.timeout(120)
def test_thread_pool_resize_up_down_mid_epoch(tmp_path):
    url = _readahead_url(tmp_path)
    with make_reader(url, reader_pool_type='thread', workers_count=2,
                     num_epochs=4, shuffle_row_groups=False,
                     io_readahead=1) as reader:
        pool = reader._pool
        n = 0
        for _ in reader:
            n += 1
            if n == 30:
                assert pool.resize(4) == 4
            if n == 200:
                assert pool.resize(1) == 1
        assert n == 96 * 4
        assert pool.workers_count == 1
        # every retiree joined; exactly-once delivery held through both
        # resizes (clean handback, not the killed-worker drop path)
        assert pool.reap_retired() == 0
        reader.audit().assert_complete()


@pytest.mark.timeout(180)
def test_process_pool_resize_up_down_mid_epoch(tmp_path):
    url = _readahead_url(tmp_path)
    with make_reader(url, reader_pool_type='process', workers_count=2,
                     num_epochs=6, shuffle_row_groups=False) as reader:
        pool = reader._pool
        results = {}

        def resizer():
            results['up'] = pool.resize(3, timeout_s=30)
            results['down'] = pool.resize(1, timeout_s=30)

        # the resize quiesce needs the consumer draining concurrently —
        # exactly the controller-thread / consumer-thread split production
        # runs with
        thread = threading.Thread(target=resizer)
        n = 0
        for _ in reader:
            n += 1
            if n == 50:
                thread.start()
        thread.join(timeout=60)
        assert not thread.is_alive()
        assert n == 96 * 6
        assert results == {'up': 3, 'down': 1}
        assert pool.workers_count == 1
        reader.audit().assert_complete()


@pytest.mark.timeout(60)
def test_thread_pool_live_readahead_depth(tmp_path):
    """set_readahead_depth reaches a dormant (depth-0, controlled)
    readahead and activates it live."""
    url = _readahead_url(tmp_path, rows=64)
    with make_reader(url, reader_pool_type='thread', workers_count=1,
                     num_epochs=3, shuffle_row_groups=False,
                     autotune=dict(tick_interval_s=3600.0,
                                   calibrate='cached')) as reader:
        pool = reader._pool
        n = 0
        hits_before = reader.stats.snapshot()['readahead_hits']
        assert hits_before == 0
        for _ in reader:
            n += 1
            if n == 16:
                pool.set_readahead_depth(4)
        snap = reader.stats.snapshot()
        assert snap['readahead_hits'] > 0
        reader.audit().assert_complete()


@pytest.mark.timeout(60)
def test_grown_worker_inherits_live_readahead_depth(tmp_path):
    """A worker spawned by a grow AFTER a live set_readahead_depth must run
    at the controller-set depth, not the construction-time one (the
    broadcast/iteration paths only reach workers that already exist)."""
    url = _readahead_url(tmp_path, rows=64)
    with make_reader(url, reader_pool_type='thread', workers_count=1,
                     num_epochs=3, shuffle_row_groups=False,
                     autotune=dict(tick_interval_s=3600.0,
                                   calibrate='cached')) as reader:
        pool = reader._pool
        pool.set_readahead_depth(3)
        pool.resize(2)
        with pool._membership_lock:
            depths = [w._readahead.depth for w in pool._workers
                      if getattr(w, '_readahead', None) is not None]
        assert depths == [3, 3]
        for _ in reader:
            pass
        reader.audit().assert_complete()


def test_ventilator_pause_resume_and_window():
    ventilated = []
    vent = ConcurrentVentilator(ventilated.append, list(range(6)),
                                iterations=1, max_ventilation_queue_size=2,
                                ventilation_interval_s=0.01)
    assert vent.max_in_flight == 2
    vent.pause()
    vent.start()
    time.sleep(0.15)
    assert ventilated == []           # paused: nothing admitted
    vent.resume()
    deadline = time.monotonic() + 5
    while len(ventilated) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(ventilated) == 2       # in-flight bound holds
    vent.set_max_in_flight(6)
    while len(ventilated) < 6 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(ventilated) == 6       # growing the window admits the rest
    for _ in range(6):
        vent.processed_item()
    vent.stop()


def test_readahead_set_depth_pins_and_dormant():
    reads = []

    def read_fn(piece, columns):
        reads.append(piece)
        return piece

    ra = RowGroupReadahead(read_fn, 0, controlled=True)
    assert ra.depth == 0
    assert ra.sync([('k1', 'p1', None), ('k2', 'p2', None)]) == 0
    assert ra.take('k1') is None      # dormant: inline read, not a miss
    ra.set_depth(2)
    ra.sync([('k1', 'p1', None), ('k2', 'p2', None)])
    assert ra.take('k1') == 'p1'
    assert ra.take('k2') == 'p2'
    with pytest.raises(ValueError):
        ra.set_depth(-1)
    ra.stop()


def test_thread_pool_queue_bound_live_enlarge():
    pool = ThreadPool(1, results_queue_size=1)
    assert pool.results_queue_bound == 1
    pool._results_queue.put('a')      # full at bound 1
    blocked = threading.Event()
    unblocked = threading.Event()

    def putter():
        blocked.set()
        pool._results_queue.put('b')  # blocks until the bound grows
        unblocked.set()

    thread = threading.Thread(target=putter)
    thread.start()
    blocked.wait(5)
    time.sleep(0.05)
    assert not unblocked.is_set()
    pool.set_results_queue_bound(4)
    assert unblocked.wait(5)          # woken by the live enlargement
    thread.join(5)
    assert pool.results_queue_bound == 4


# ---------------------------------------------------------------------------
# kill switch + observability on a real reader
# ---------------------------------------------------------------------------


@pytest.mark.timeout(60)
def test_kill_switch_no_controller_thread_no_files(tmp_path, monkeypatch):
    scratch = tmp_path / 'autotune_scratch'
    monkeypatch.setenv(AUTOTUNE_DIR_ENV_VAR, str(scratch))
    monkeypatch.setenv(AUTOTUNE_ENV_VAR, '0')
    url = _readahead_url(tmp_path, rows=32)
    with make_reader(url, reader_pool_type='thread', workers_count=1,
                     num_epochs=1, shuffle_row_groups=False,
                     autotune=True) as reader:
        assert reader.autotune is None
        assert not any(t.name == 'petastorm-tpu-autotune'
                       for t in threading.enumerate())
        for _ in reader:
            pass
    assert not scratch.exists()       # kill switch: no files, ever


@pytest.mark.timeout(120)
def test_autotuned_reader_observability(tmp_path, monkeypatch):
    """The /autotune route serves the report, gauges land in /metrics and
    the stats snapshot, flight records embed the controller section, and
    the scratch record exists while the controller runs."""
    scratch = tmp_path / 'autotune_scratch'
    monkeypatch.setenv(AUTOTUNE_DIR_ENV_VAR, str(scratch))
    url = _readahead_url(tmp_path, rows=64)
    with make_reader(url, reader_pool_type='thread', workers_count=2,
                     num_epochs=10, shuffle_row_groups=False,
                     autotune=dict(tick_interval_s=0.1, calibrate='cached'),
                     debug_port=0) as reader:
        assert reader.autotune is not None
        n = 0
        for _ in reader:
            n += 1
        deadline = time.monotonic() + 10
        while reader.autotune.report()['ticks'] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        base = 'http://127.0.0.1:{}'.format(reader.debug_port)
        report = json.loads(urllib.request.urlopen(
            base + '/autotune', timeout=10).read())
        assert report['ticks'] >= 2
        assert report['config']['pool_type'] == 'thread'
        assert 'prediction' in report
        snap = reader._stats_snapshot()
        assert snap['autotune_ticks'] >= 2
        assert snap['autotune_workers'] == reader._pool.workers_count
        metrics = urllib.request.urlopen(
            base + '/metrics', timeout=10).read().decode()
        assert 'petastorm_tpu_autotune_ticks' in metrics
        record = reader.dump_flight_record(
            path=str(tmp_path / 'flight.json'))
        blob = json.load(open(record))
        assert 'autotune' in blob and 'ticks' in blob['autotune']
        # arbitration record exists while the controller runs
        assert list(scratch.glob('controller-*.json'))
    # and is cleaned up on stop
    assert not list(scratch.glob('controller-*.json'))


@pytest.mark.timeout(60)
def test_autotune_route_404_when_off(tmp_path):
    url = _readahead_url(tmp_path, rows=32)
    with make_reader(url, reader_pool_type='thread', workers_count=1,
                     num_epochs=1, shuffle_row_groups=False,
                     debug_port=0) as reader:
        base = 'http://127.0.0.1:{}'.format(reader.debug_port)
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(base + '/autotune', timeout=10)
        assert err.value.code == 404
        for _ in reader:
            pass


# ---------------------------------------------------------------------------
# multi-reader arbitration
# ---------------------------------------------------------------------------


def test_arbiter_splits_cpu_budget_by_deficit(tmp_path):
    directory = str(tmp_path / 'arb')
    a = HostArbiter(directory, cpu_count=8, tick_interval_s=5.0,
                    controller_id='a')
    b = HostArbiter(directory, cpu_count=8, tick_interval_s=5.0,
                    controller_id='b')
    # alone on the host: the whole budget
    a.publish(deficit=0.9, workers=1)
    assert a.worker_cap(0.9) == 8
    # two controllers: proportional to deficit, floored at 1 each
    b.publish(deficit=0.1, workers=4)
    assert a.worker_cap(0.9) == 7
    assert b.worker_cap(0.1) == 1
    # equal (zero) deficits: equal split
    a.publish(deficit=0.0, workers=1)
    b.publish(deficit=0.0, workers=1)
    assert a.worker_cap(0.0) == 4
    assert b.worker_cap(0.0) == 4
    # a stale peer record is ignored
    stale = os.path.join(directory, 'controller-b.json')
    blob = json.load(open(stale))
    blob['ts'] -= 3600.0
    with open(stale, 'w') as f:
        json.dump(blob, f)
    assert a.worker_cap(0.5) == 8
    a.cleanup()
    b.cleanup()
    assert not os.listdir(directory)


def test_scratch_dir_resolution(monkeypatch, tmp_path):
    monkeypatch.setenv(AUTOTUNE_DIR_ENV_VAR, str(tmp_path / 'x'))
    assert scratch_dir() == str(tmp_path / 'x')
    assert scratch_dir({'scratch_dir': '/y'}) == '/y'
    monkeypatch.delenv(AUTOTUNE_DIR_ENV_VAR)
    assert 'petastorm_tpu_autotune' in scratch_dir()
