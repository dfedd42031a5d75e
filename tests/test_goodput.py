"""Training goodput plane: per-step decomposition math, verdicts,
explain_step evidence joins, pod-wide merge bit-identity with the
straggler named, the ``min_goodput`` SLO target, trace step markers, the
``/goodput`` route, the loader end-to-end wiring (including the sharded
loader's shared monitor), the prefetch-occupancy gauge, and the
structural ``PETASTORM_TPU_GOODPUT=0`` kill switch — plus the
``stage_to_global``/``prefetch_to_device`` edge cases on CPU jax and the
``PETASTORM_TPU_DEVICE_DECODE`` interplay."""

import json

import numpy as np
import pytest

from petastorm_tpu import goodput as goodput_mod
from petastorm_tpu.goodput import (BALANCED, COMPUTE_BOUND, DATA_STALL,
                                   DOMINANCE_THRESHOLD, GOODPUT_ENV_VAR,
                                   HOST_OVERHEAD, GoodputMonitor,
                                   classify_step, goodput_enabled)
from petastorm_tpu.health import (HEALTHY, DebugServer, build_flight_record)
from petastorm_tpu.latency import (PipelineLatency, SLOMonitor,
                                   validate_slo_targets)
from petastorm_tpu.podobs import (PARTIAL_POD, check_pod_goodput,
                                  merge_histogram_states)
from petastorm_tpu.tracing import (GOODPUT_STEP_CAT, Tracer,
                                   step_stall_marker, stitch_pod_trace)
from petastorm_tpu.workers.stats import (ReaderStats, data_stall_fraction,
                                         goodput_fraction)

jax = pytest.importorskip('jax')


def _run_step(monitor, infeed_s, wall_s, h2d_s=0.0, batch=None):
    """Drive one step through the monitor's hot-path hooks."""
    monitor.note_fetch(infeed_s, batch)
    if h2d_s:
        monitor.note_stage(h2d_s)
    return monitor.finish_step(wall_s)


class TestEnabling:
    def test_default_on_and_kill_switch(self, monkeypatch):
        monkeypatch.delenv(GOODPUT_ENV_VAR, raising=False)
        assert goodput_enabled()
        for off in ('0', 'false', 'off'):
            monkeypatch.setenv(GOODPUT_ENV_VAR, off)
            assert not goodput_enabled()
        monkeypatch.setenv(GOODPUT_ENV_VAR, 'on')
        assert goodput_enabled()


class TestClassify:
    def test_verdict_vocabulary(self):
        assert classify_step({'total_s': 1.0, 'stall_s': 0.8,
                              'device_step_s': 0.2}) == DATA_STALL
        assert classify_step({'total_s': 1.0, 'stall_s': 0.1,
                              'device_step_s': 0.9}) == COMPUTE_BOUND
        assert classify_step({'total_s': 1.0, 'stall_s': 0.1,
                              'device_step_s': 0.2,
                              'host_overhead_s': 0.7}) == HOST_OVERHEAD

    def test_h2d_counts_toward_the_stall_side(self):
        entry = {'total_s': 1.0, 'stall_s': 0.25, 'h2d_stage_s': 0.25,
                 'device_step_s': 0.5}
        assert classify_step(entry) == DATA_STALL

    def test_below_dominance_is_balanced(self):
        third = (DOMINANCE_THRESHOLD - 0.05)
        entry = {'total_s': 1.0, 'stall_s': third, 'device_step_s': third,
                 'host_overhead_s': 1.0 - 2 * third}
        assert classify_step(entry) == BALANCED

    def test_zero_total_is_balanced(self):
        assert classify_step({'total_s': 0.0}) == BALANCED
        assert classify_step({}) == BALANCED


class TestDecomposition:
    def test_unfenced_wall_is_all_device(self):
        monitor = GoodputMonitor()
        entry = _run_step(monitor, 0.25, 0.75)
        assert entry['total_s'] == 1.0
        assert entry['stall_s'] == 0.25
        assert entry['device_step_s'] == 0.75
        assert entry['host_overhead_s'] == 0.0
        assert entry['fenced'] is False

    def test_h2d_attribution_is_capped_at_the_fetch_wait(self):
        # staging that overlapped compute is not on the critical path:
        # only min(h2d, infeed) counts, the rest of the wait is pure stall
        monitor = GoodputMonitor()
        entry = _run_step(monitor, 0.25, 0.75, h2d_s=1.0)
        assert entry['h2d_stage_s'] == 0.25
        assert entry['stall_s'] == 0.0
        entry = _run_step(monitor, 0.5, 0.5, h2d_s=0.125)
        assert entry['h2d_stage_s'] == 0.125
        assert entry['stall_s'] == 0.375

    def test_fence_splits_the_train_wall(self, monkeypatch):
        class _TickingClock:
            now = 0.0

            def perf_counter(self):
                _TickingClock.now += 0.02
                return _TickingClock.now

        monkeypatch.setattr(goodput_mod, 'time', _TickingClock())
        monitor = GoodputMonitor()
        monitor.note_fetch(0.0)
        monitor.fence(np.zeros(3))       # fence_s == one 0.02 tick
        entry = monitor.finish_step(0.05)
        assert entry['fenced'] is True
        assert entry['device_step_s'] == pytest.approx(0.02)
        assert entry['host_overhead_s'] == pytest.approx(0.03)
        assert monitor.state()['fenced_steps'] == 1

    def test_fence_device_time_is_capped_at_the_wall(self, monkeypatch):
        class _BigTick:
            now = 0.0

            def perf_counter(self):
                _BigTick.now += 10.0
                return _BigTick.now

        monkeypatch.setattr(goodput_mod, 'time', _BigTick())
        monitor = GoodputMonitor()
        monitor.note_fetch(0.0)
        monitor.fence(np.zeros(1))
        entry = monitor.finish_step(0.5)
        assert entry['device_step_s'] == 0.5
        assert entry['host_overhead_s'] == 0.0

    def test_a_late_report_keeps_each_fence_with_its_step(self):
        """A prefetcher reports the loop's steps after the fact, with the
        times the loop read: a fence counts in the step it ran in, even if
        the report comes after the next step's fence, and one taken while
        the loop waited for a batch counts in none."""
        import time
        monitor = GoodputMonitor()
        fetched_1 = time.perf_counter()
        monitor.fence(np.zeros(1))          # inside step 1
        ended_1 = time.perf_counter()
        monitor.fence(np.zeros(1))          # while the loop waits
        fetched_2 = time.perf_counter()
        monitor.fence(np.zeros(1))          # inside step 2
        monitor.note_fetch(0.0, fetched_at=fetched_1)
        first = monitor.finish_step(ended_1 - fetched_1, ended_at=ended_1)
        monitor.note_fetch(0.0, fetched_at=fetched_2)
        assert len(monitor._pending_fences) == 1
        second = monitor.finish_step(0.5)
        assert first['fenced'] and second['fenced']
        assert monitor.state()['fenced_steps'] == 2
        assert monitor._pending_fences == []

    def test_finish_without_open_step_is_none(self):
        monitor = GoodputMonitor()
        assert monitor.finish_step(0.5) is None
        assert monitor.state()['steps'] == 0

    def test_ring_is_bounded_and_step_lookup_works(self):
        monitor = GoodputMonitor(ring_size=4)
        for _ in range(10):
            _run_step(monitor, 0.0, 0.25)
        steps = monitor.steps()
        assert len(steps) == 4
        assert [e['step'] for e in steps] == [6, 7, 8, 9]
        assert monitor.step(8)['step'] == 8
        assert monitor.step(0) is None      # evicted
        assert monitor.state()['steps'] == 10

    def test_summary_and_window_rederive_from_seconds(self):
        monitor = GoodputMonitor(window_steps=2)
        _run_step(monitor, 0.75, 0.25)      # stalled step
        _run_step(monitor, 0.0, 1.0)        # clean step
        _run_step(monitor, 0.0, 1.0)        # clean step
        summary = monitor.summary()
        assert summary['enabled'] is True
        assert summary['steps'] == 3
        assert summary['goodput_fraction'] == pytest.approx(2.25 / 3.0)
        assert summary['data_stall_fraction'] == pytest.approx(0.25)
        # the rolling window only sees the two clean steps
        assert summary['window']['steps'] == 2
        assert summary['window']['goodput_fraction'] == 1.0
        assert summary['window']['data_stall_fraction'] == 0.0

    def test_empty_monitor_summary_has_no_fractions(self):
        summary = GoodputMonitor().summary()
        assert summary['goodput_fraction'] is None
        assert summary['window']['goodput_fraction'] is None

    def test_stats_export_and_derived_fractions(self):
        stats = ReaderStats()
        monitor = GoodputMonitor(stats=stats)
        _run_step(monitor, 0.5, 0.5, h2d_s=0.25)
        snapshot = stats.snapshot()
        assert snapshot['goodput_total_s'] == pytest.approx(1.0)
        assert snapshot['goodput_stall_s'] == pytest.approx(0.25)
        assert snapshot['goodput_h2d_s'] == pytest.approx(0.25)
        assert snapshot['goodput_device_s'] == pytest.approx(0.5)
        assert snapshot['goodput_fraction'] == pytest.approx(0.5)
        assert snapshot['data_stall_fraction'] == pytest.approx(0.5)

    def test_latency_stages_record_device_step_and_fenced_overhead(self):
        plane = PipelineLatency()
        monitor = GoodputMonitor(latency=plane)
        _run_step(monitor, 0.0, 0.5)        # unfenced: no host_overhead obs
        assert plane.histograms['device_step'].state()['count'] == 1
        assert plane.histograms['host_overhead'].state()['count'] == 0
        monitor.note_fetch(0.0)
        monitor.fence(np.zeros(1))
        monitor.finish_step(0.5)
        assert plane.histograms['device_step'].state()['count'] == 2
        assert plane.histograms['host_overhead'].state()['count'] == 1

    def test_fraction_helpers_none_before_any_step(self):
        assert goodput_fraction({}) is None
        assert data_stall_fraction({'goodput_total_s': 0.0}) is None
        assert goodput_fraction({'goodput_total_s': 2.0,
                                 'goodput_device_s': 1.0}) == 0.5
        assert data_stall_fraction({'goodput_total_s': 2.0,
                                    'goodput_stall_s': 0.5,
                                    'goodput_h2d_s': 0.5}) == 0.5


class _FakeProvenance:
    """Stands in for a ``BatchProvenance`` (duck-typed ``summary()``)."""

    def summary(self):
        return {'rows': 16,
                'sources': [{'seq': 0, 'rows': 16,
                             'path': '/data/train/part-00002.parquet',
                             'row_group': 7, 'epoch': 0, 'shard': 2,
                             'selection': None}],
                'shuffle': None}


class TestExplainStep:
    def test_data_stall_chain_names_the_culprit(self):
        monitor = GoodputMonitor(host='host-2')
        _run_step(monitor, 0.8, 0.2, batch={'_provenance': _FakeProvenance()})
        snapshot = {'queue_wait_p50_s': 0.0001, 'queue_wait_p99_s': 0.2,
                    'io_range_p99_s': 5.0, 'prefetch_occupancy': 0}
        verdict = monitor.explain_step(snapshot=snapshot)
        assert verdict['verdict'] == DATA_STALL
        assert verdict['chain'][0] == 'infeed_wait'
        assert 'queue_wait p99 tail' in verdict['chain']
        assert any('io_range' in link for link in verdict['chain'])
        # the provenance names the file + row group on the last link
        assert 'part-00002.parquet' in verdict['chain'][-1]
        assert 'rg7' in verdict['chain'][-1]
        assert 'stalled' in verdict['explanation']
        assert '→' in verdict['explanation']
        assert verdict['prefetch_occupancy'] == 0
        assert verdict['host'] == 'host-2'
        assert verdict['stall_ms'] == pytest.approx(800.0)

    def test_h2d_heavy_stall_leads_with_h2d_stage(self):
        monitor = GoodputMonitor()
        _run_step(monitor, 0.8, 0.2, h2d_s=0.6)
        verdict = monitor.explain_step()
        assert verdict['verdict'] == DATA_STALL
        assert verdict['chain'][0] == 'h2d_stage'

    def test_compute_bound_says_the_pipeline_kept_up(self):
        monitor = GoodputMonitor()
        _run_step(monitor, 0.05, 0.95)
        verdict = monitor.explain_step()
        assert verdict['verdict'] == COMPUTE_BOUND
        assert 'kept up' in verdict['explanation']
        assert verdict['decomposition']['device_step_s'] == 0.95

    def test_unknown_step_is_explicit(self):
        verdict = GoodputMonitor().explain_step(99)
        assert verdict['verdict'] is None
        assert 'no such step' in verdict['explanation']

    def test_flight_summary_is_jsonable_with_verdicts(self):
        monitor = GoodputMonitor()
        _run_step(monitor, 0.9, 0.1, batch={'_provenance': _FakeProvenance()})
        flight = monitor.flight_summary()
        json.dumps(flight)      # provenance must have been summarized
        assert flight['recent_steps'][-1]['verdict'] == DATA_STALL
        assert (flight['recent_steps'][-1]['provenance']['sources'][0]
                ['row_group'] == 7)


class TestPodGoodput:
    # binary-exact seconds so summation order cannot perturb the totals:
    # the pod sum must be bit-identical to direct recording
    HOST_STEPS = {
        'host-0': [(0.25, 0.75), (0.0, 1.0)],
        'host-1': [(0.125, 0.875), (0.25, 0.75)],
        'host-2': [(1.5, 0.5), (1.75, 0.25)],     # the straggler
    }

    def _monitors(self):
        monitors = {}
        for host, steps in self.HOST_STEPS.items():
            monitor = GoodputMonitor(host=host)
            for infeed, wall in steps:
                _run_step(monitor, infeed, wall)
            monitors[host] = monitor
        return monitors

    def test_merge_bit_identical_to_direct_recording(self):
        monitors = self._monitors()
        direct = GoodputMonitor()
        for host in sorted(self.HOST_STEPS):
            for infeed, wall in self.HOST_STEPS[host]:
                _run_step(direct, infeed, wall)
        pod = check_pod_goodput(
            {host: m.summary() for host, m in monitors.items()})
        state = direct.state()
        for key in ('steps', 'total_s', 'stall_s', 'h2d_s', 'device_s',
                    'host_s'):
            assert pod['totals'][key] == state[key]
        assert pod['goodput_fraction'] == round(
            state['device_s'] / state['total_s'], 4)

    def test_straggler_is_named_not_averaged_away(self):
        monitors = self._monitors()
        pod = check_pod_goodput(
            {host: m.summary() for host, m in monitors.items()},
            min_goodput=0.75)
        assert pod['straggler']['host'] == 'host-2'
        assert pod['straggler']['data_stall_fraction'] > 0.8
        assert pod['checked'] is True
        assert pod['ok'] is False
        assert any('host-2' in p for p in pod['problems'])

    def test_unreachable_host_refuses_to_certify(self):
        monitors = self._monitors()
        pod = check_pod_goodput(
            {host: m.summary() for host, m in monitors.items()},
            min_goodput=0.1, unreachable=['10.0.0.9:7777'])
        assert pod['ok'] is False
        assert pod['checked'] is False
        assert any(PARTIAL_POD in p for p in pod['problems'])

    def test_unarmed_or_empty_is_never_a_silent_pass(self):
        assert check_pod_goodput({})['ok'] is None
        monitors = self._monitors()
        unarmed = check_pod_goodput(
            {host: m.summary() for host, m in monitors.items()})
        assert unarmed['ok'] is None and unarmed['checked'] is False

    def test_device_step_histograms_merge_bit_identical(self):
        planes = {host: PipelineLatency() for host in self.HOST_STEPS}
        direct = PipelineLatency()
        for host, steps in sorted(self.HOST_STEPS.items()):
            monitor = GoodputMonitor(latency=planes[host])
            for infeed, wall in steps:
                _run_step(monitor, infeed, wall)
                direct.record('device_step', wall)
        merged = merge_histogram_states(
            [{'device_step': planes[h].histograms['device_step'].state()}
             for h in planes])
        want = direct.histograms['device_step'].state()
        assert merged['device_step']['buckets'] == want['buckets']
        assert merged['device_step']['count'] == want['count']


class TestSloTarget:
    def test_min_goodput_validation(self):
        validate_slo_targets({'min_goodput': 0.9})
        with pytest.raises(ValueError, match='min_goodput'):
            validate_slo_targets({'min_goodput': 1.5})

    def test_skips_loudly_without_step_data(self):
        monitor = SLOMonitor({'min_goodput': 0.9})
        verdict = monitor.evaluate({})
        assert verdict['skipped_checks'] == ['min_goodput']
        assert not verdict['breached']
        assert verdict['checks']['min_goodput']['ok'] is None

    def test_breach_below_target(self):
        monitor = SLOMonitor({'min_goodput': 0.9})
        good = monitor.evaluate({'goodput_fraction': 0.95})
        assert not good['breached']
        bad = monitor.evaluate({'goodput_fraction': 0.4})
        assert 'min_goodput' in bad['breached_checks']
        assert bad['checks']['min_goodput']['measured'] == 0.4


class TestTraceMarkers:
    def _traced_monitor(self):
        tracer = Tracer()
        monitor = GoodputMonitor(tracer=tracer)
        _run_step(monitor, 0.9, 0.1)        # data stall
        _run_step(monitor, 0.0, 1.0)        # compute bound
        return tracer

    def test_one_step_span_per_step_plus_stall_marker(self):
        events = self._traced_monitor().chrome_trace_events()
        spans = [e for e in events
                 if e.get('cat') == GOODPUT_STEP_CAT and e['ph'] == 'X']
        assert len(spans) == 2
        assert spans[0]['args']['verdict'] == DATA_STALL
        assert spans[1]['args']['verdict'] == COMPUTE_BOUND
        markers = [e for e in events if e.get('ph') == 'i']
        assert len(markers) == 1
        assert markers[0]['name'].startswith('data-stall')
        assert markers[0]['args']['step'] == 0

    def test_marker_helper_ignores_other_events(self):
        assert step_stall_marker({'cat': 'pipeline', 'ph': 'X',
                                  'args': {'verdict': DATA_STALL}}) is None
        assert step_stall_marker({'cat': GOODPUT_STEP_CAT, 'ph': 'X',
                                  'ts': 0.0, 'pid': 1,
                                  'args': {'verdict': COMPUTE_BOUND}}) is None

    def test_stitch_pod_trace_carries_the_markers(self, tmp_path):
        tracer = self._traced_monitor()
        path = str(tmp_path / 'pod_trace.json')
        stitch_pod_trace([{'host': 'host-0', 'clock_offset_s': 0.0,
                           'spans': tracer.tail()}], path)
        with open(path) as f:
            events = json.load(f)['traceEvents']
        markers = [e for e in events if e.get('ph') == 'i']
        assert len(markers) == 1
        assert markers[0]['cat'] == GOODPUT_STEP_CAT


def _http_get(port, route):
    from http.client import HTTPConnection
    conn = HTTPConnection('127.0.0.1', port, timeout=10)
    try:
        conn.request('GET', route)
        response = conn.getresponse()
        return response.status, response.read()
    finally:
        conn.close()


class TestHttpSurfaces:
    def test_goodput_route_serves_the_summary(self):
        monitor = GoodputMonitor()
        _run_step(monitor, 0.25, 0.75)
        server = DebugServer(lambda: {'state': HEALTHY},
                             goodput_fn=monitor.summary).start()
        try:
            status, body = _http_get(server.port, '/goodput')
            assert status == 200
            blob = json.loads(body)
            assert blob['steps'] == 1
            assert blob['goodput_fraction'] == 0.75
            # /diagnostics embeds the same section
            status, body = _http_get(server.port, '/diagnostics')
            assert json.loads(body)['goodput']['steps'] == 1
        finally:
            server.stop()

    def test_goodput_route_404s_when_unwired(self):
        server = DebugServer(lambda: {'state': HEALTHY}).start()
        try:
            status, body = _http_get(server.port, '/goodput')
            assert status == 404
            assert b'PETASTORM_TPU_GOODPUT' in body
        finally:
            server.stop()


class TestFlightRecord:
    def test_goodput_section_rides_the_record(self):
        monitor = GoodputMonitor()
        _run_step(monitor, 0.9, 0.1)
        record = build_flight_record({'state': HEALTHY}, {},
                                     goodput=monitor.flight_summary())
        json.dumps(record)
        assert record['goodput']['steps'] == 1
        assert record['goodput']['recent_steps'][0]['verdict'] == DATA_STALL
        bare = build_flight_record({'state': HEALTHY}, {})
        assert 'goodput' not in bare


@pytest.fixture(scope='module')
def token_store(tmp_path_factory):
    from petastorm_tpu.benchmark.northstar import generate_token_dataset
    url = 'file://' + str(tmp_path_factory.mktemp('goodput') / 'tok')
    generate_token_dataset(url, rows=48, seq_len=8, vocab=64, seed=5,
                           row_group_size_mb=0.01, ndarray_codec=True)
    return url


class TestLoaderIntegration:
    def test_default_on_records_steps_and_registers(self, token_store,
                                                    monkeypatch):
        from petastorm_tpu.jax_utils import JaxDataLoader
        from petastorm_tpu.reader import make_columnar_reader
        monkeypatch.delenv(GOODPUT_ENV_VAR, raising=False)
        with make_columnar_reader(token_store, num_epochs=1, workers_count=1,
                                  shuffle_row_groups=False) as reader:
            with JaxDataLoader(reader, batch_size=16) as loader:
                assert loader.goodput is not None
                assert reader._goodput is loader.goodput
                batches = sum(1 for _ in loader)
                summary = loader.goodput.summary()
            snapshot = reader._stats_snapshot()
        assert batches == 3
        # every step but the final one closes (the last yield has no
        # follow-up fetch to measure its train wall against)
        assert summary['steps'] >= batches - 1
        assert snapshot['goodput_total_s'] > 0.0
        assert 'goodput_fraction' in snapshot
        assert 'data_stall_fraction' in snapshot

    def test_kill_switch_is_structural(self, token_store, monkeypatch):
        from petastorm_tpu.jax_utils import JaxDataLoader
        from petastorm_tpu.reader import make_columnar_reader
        from petastorm_tpu.workers.stats import LATENCY_HISTOGRAMS_KEY
        monkeypatch.setenv(GOODPUT_ENV_VAR, '0')
        with make_columnar_reader(token_store, num_epochs=1, workers_count=1,
                                  shuffle_row_groups=False) as reader:
            with JaxDataLoader(reader, batch_size=16) as loader:
                assert loader.goodput is None           # no monitor object
                for _ in loader:
                    pass
            assert reader._goodput is None              # never registered
            snapshot = reader._stats_snapshot()
        assert snapshot['goodput_total_s'] == 0.0       # no counters fed
        assert 'goodput_fraction' not in snapshot       # no derived keys
        histograms = snapshot.get(LATENCY_HISTOGRAMS_KEY) or {}
        for stage in ('device_step', 'host_overhead'):  # no stage records
            assert histograms.get(stage, {}).get('count', 0) == 0

    def test_provenance_rides_into_the_ring(self, token_store, monkeypatch):
        from petastorm_tpu.jax_utils import JaxDataLoader
        from petastorm_tpu.reader import make_columnar_reader
        monkeypatch.delenv(GOODPUT_ENV_VAR, raising=False)
        with make_columnar_reader(token_store, num_epochs=1, workers_count=1,
                                  shuffle_row_groups=False) as reader:
            with JaxDataLoader(reader, batch_size=16) as loader:
                for _ in loader:
                    pass
                steps = loader.goodput.steps()
                verdict = loader.goodput.explain_step(
                    steps[0]['step'], snapshot=reader._stats_snapshot())
        assert steps and steps[0]['provenance'] is not None
        assert verdict['provenance']['sources']

    def test_sharded_loader_shares_the_outer_monitor(self, token_store,
                                                     monkeypatch):
        from jax.sharding import Mesh
        from petastorm_tpu.jax_utils import ShardedJaxLoader
        from petastorm_tpu.reader import make_columnar_reader
        monkeypatch.delenv(GOODPUT_ENV_VAR, raising=False)
        mesh = Mesh(np.array(jax.devices()[:1]), ('data',))
        with make_columnar_reader(token_store, num_epochs=1, workers_count=1,
                                  shuffle_row_groups=False) as reader:
            with ShardedJaxLoader(reader, mesh,
                                  local_batch_size=16) as loader:
                # the inner loader's __iter__ is bypassed: its monitor MUST
                # be the outer one, and the reader must serve the outer one
                assert loader.goodput is not None
                assert loader._loader.goodput is loader.goodput
                assert reader._goodput is loader.goodput
                for _ in loader:
                    pass
                summary = loader.goodput.summary()
            snapshot = reader._stats_snapshot()
        assert summary['steps'] >= 1
        # the staging site fed the h2d leg of at least the later steps
        assert snapshot['goodput_h2d_s'] >= 0.0
        assert snapshot['goodput_total_s'] > 0.0

    def test_fence_inside_the_loop_records_fenced_steps(self, token_store,
                                                        monkeypatch):
        from petastorm_tpu.jax_utils import JaxDataLoader
        from petastorm_tpu.reader import make_columnar_reader
        monkeypatch.delenv(GOODPUT_ENV_VAR, raising=False)
        with make_columnar_reader(token_store, num_epochs=1, workers_count=1,
                                  shuffle_row_groups=False) as reader:
            with JaxDataLoader(reader, batch_size=16) as loader:
                for batch in loader:
                    loader.goodput.fence(jax.numpy.asarray(batch['tokens']))
                summary = loader.goodput.summary()
        assert summary['fenced_steps'] >= 1
        assert summary['fenced_steps'] <= summary['steps']

    def test_fence_inside_a_prefetched_loop_fences_every_step(
            self, token_store, monkeypatch):
        """Under a prefetcher the producer thread folds the loop's steps
        into the monitor after the loop has moved on: every fence still
        lands in its own step, and every step is in by the end."""
        from petastorm_tpu.jax_utils import JaxDataLoader, prefetch_to_device
        from petastorm_tpu.reader import make_columnar_reader
        monkeypatch.delenv(GOODPUT_ENV_VAR, raising=False)
        with make_columnar_reader(token_store, num_epochs=1, workers_count=1,
                                  shuffle_row_groups=False) as reader:
            with JaxDataLoader(reader, batch_size=16) as loader:
                count = 0
                for batch in prefetch_to_device(loader, size=2):
                    loader.goodput.fence(batch['tokens'])
                    count += 1
                state = loader.goodput.state()
            waits = reader.stats.latency.histograms['infeed_wait'].count
        assert count == 3
        assert state['steps'] == state['fenced_steps'] == waits == count

    def test_device_decode_off_interplay(self, token_store, monkeypatch):
        """PETASTORM_TPU_DEVICE_DECODE=off must not take the goodput plane
        down with it (and vice versa: goodput off leaves device decode on)."""
        from petastorm_tpu.jax_utils import JaxDataLoader
        from petastorm_tpu.ops.decode import DEVICE_DECODE_ENV_VAR
        from petastorm_tpu.reader import make_columnar_reader
        monkeypatch.setenv(DEVICE_DECODE_ENV_VAR, 'off')
        monkeypatch.delenv(GOODPUT_ENV_VAR, raising=False)
        with make_columnar_reader(token_store, num_epochs=1, workers_count=1,
                                  shuffle_row_groups=False) as reader:
            with JaxDataLoader(reader, batch_size=16) as loader:
                assert loader.goodput is not None
                for _ in loader:
                    pass
            snapshot = reader._stats_snapshot()
        assert snapshot['rows_decoded_device'] == 0
        assert snapshot['goodput_total_s'] > 0.0
        diag_device = __import__(
            'petastorm_tpu.jax_utils', fromlist=['infeed_diagnosis']
        ).infeed_diagnosis(snapshot)['device']
        assert diag_device['device_decode_fraction'] == 0.0
        assert diag_device['goodput_fraction'] is not None


class TestStagingEdges:
    """``stage_to_global`` / ``prefetch_to_device`` edge cases on CPU jax
    plus the prefetch-occupancy gauge."""

    def test_resolve_prefetch_depth_rejects_zero_and_floats(self):
        from petastorm_tpu.jax_utils import resolve_prefetch_depth
        assert resolve_prefetch_depth(2) == 2
        with pytest.raises(ValueError):
            resolve_prefetch_depth(0)
        with pytest.raises(ValueError):
            resolve_prefetch_depth(1.5)

    def test_stage_to_global_feeds_the_h2d_leg(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from petastorm_tpu.jax_utils import stage_to_global
        mesh = Mesh(np.array(jax.devices()[:1]), ('data',))
        sharding = NamedSharding(mesh, PartitionSpec('data'))
        monitor = GoodputMonitor()
        monitor.note_fetch(10.0)    # a huge wait: h2d stays under the cap
        staged = stage_to_global({'x': np.ones((4, 2), dtype=np.float32)},
                                 sharding, goodput=monitor)
        entry = monitor.finish_step(0.1)
        assert isinstance(staged['x'], jax.Array)
        assert entry['h2d_stage_s'] > 0.0
        assert entry['stall_s'] == pytest.approx(10.0 - entry['h2d_stage_s'])

    def test_prefetch_to_device_without_sharding_on_cpu(self):
        """The zero-device / no-sharding fallback: plain device_put of each
        leaf, and every staged batch still reaches the consumer in order."""
        from petastorm_tpu.jax_utils import prefetch_to_device
        stats = ReaderStats()
        monitor = GoodputMonitor(stats=stats)
        batches = [{'x': np.full((2,), i, dtype=np.float32)}
                   for i in range(4)]
        out = list(prefetch_to_device(iter(batches), size=2, stats=stats,
                                      goodput=monitor))
        assert [int(b['x'][0]) for b in out] == [0, 1, 2, 3]
        assert all(isinstance(b['x'], jax.Array) for b in out)
        snapshot = stats.snapshot()
        # the ring was gauged at every enqueue/dequeue
        assert 'prefetch_occupancy' in snapshot
        assert snapshot['prefetch_occupancy_max'] >= 1
        # staging seconds accrued to the monitor's pending step
        monitor.note_fetch(0.0)
        assert monitor.finish_step(0.0) is not None

    def test_prefetch_batches_gauges_occupancy(self):
        from petastorm_tpu.jax_utils import prefetch_batches
        stats = ReaderStats()
        batches = [{'x': np.zeros(1)} for _ in range(6)]
        out = list(prefetch_batches(iter(batches), size=3, stats=stats))
        assert len(out) == 6
        snapshot = stats.snapshot()
        assert snapshot['prefetch_occupancy_max'] >= 1
        assert snapshot['prefetch_occupancy'] == 0      # drained at the end

    def test_iter_prefetched_keeps_the_goodput_plane(self, token_store,
                                                     monkeypatch):
        from petastorm_tpu.jax_utils import JaxDataLoader
        from petastorm_tpu.reader import make_columnar_reader
        monkeypatch.delenv(GOODPUT_ENV_VAR, raising=False)
        with make_columnar_reader(token_store, num_epochs=1, workers_count=1,
                                  shuffle_row_groups=False) as reader:
            with JaxDataLoader(reader, batch_size=16) as loader:
                count = sum(1 for _ in loader.iter_prefetched())
                summary = loader.goodput.summary()
            snapshot = reader._stats_snapshot()
        assert count == 3
        assert summary['steps'] >= 1
        assert snapshot['prefetch_occupancy_max'] >= 1
