"""Pipeline tracing tests: Tracer ring-buffer semantics, chrome trace-event
JSON schema validity, span shipment across the process-pool boundary
(including worker death), loader train-step/infeed spans, and the metrics
emitter lifecycle."""

import json
import time

import pytest

from petastorm_tpu.jax_utils import JaxDataLoader, prefetch_to_device
from petastorm_tpu.reader import make_columnar_reader, make_reader
from petastorm_tpu.tracing import (MetricsEmitter, Tracer, make_span,
                                   prometheus_text, resolve_trace)


def _assert_valid_chrome_trace(path, expect_names=(), min_pids=1):
    """The schema contract Perfetto/chrome://tracing depend on: one JSON
    object with a traceEvents list; complete events carry ph='X', numeric
    ts/dur (microseconds) and pid/tid track ids; events are ts-sorted."""
    with open(path) as f:
        blob = json.load(f)
    events = blob['traceEvents']
    span_events = [e for e in events if e['ph'] == 'X']
    assert span_events, 'no span events exported'
    for event in span_events:
        assert isinstance(event['name'], str) and event['name']
        assert isinstance(event['ts'], (int, float)) and event['ts'] >= 0
        assert isinstance(event['dur'], (int, float)) and event['dur'] >= 0
        assert isinstance(event['pid'], int)
        assert isinstance(event['tid'], int)
    timestamps = [e['ts'] for e in span_events]
    assert timestamps == sorted(timestamps), 'events must be ts-monotonic'
    names = {e['name'] for e in span_events}
    for expected in expect_names:
        assert expected in names, (expected, sorted(names))
    pids = {e['pid'] for e in span_events}
    assert len(pids) >= min_pids
    # process_name metadata names every pid's track
    meta_pids = {e['pid'] for e in events if e['ph'] == 'M'
                 and e['name'] == 'process_name'}
    assert pids <= meta_pids
    return span_events


class TestTracerUnit:
    def test_span_context_and_export(self, tmp_path):
        tracer = Tracer()
        with tracer.span('outer', 'test'):
            with tracer.span('inner', 'test', args={'k': 1}):
                time.sleep(0.001)
        assert len(tracer) == 2
        path = str(tmp_path / 'trace.json')
        assert tracer.export_chrome_trace(path) == 2
        events = _assert_valid_chrome_trace(path,
                                            expect_names=('outer', 'inner'))
        inner = next(e for e in events if e['name'] == 'inner')
        assert inner['args'] == {'k': 1}
        outer = next(e for e in events if e['name'] == 'outer')
        # inner nests within outer on the same track
        assert outer['tid'] == inner['tid']
        assert outer['ts'] <= inner['ts']
        assert outer['ts'] + outer['dur'] >= inner['ts'] + inner['dur']

    def test_ring_buffer_bound_and_dropped(self):
        tracer = Tracer(capacity=10)
        for i in range(25):
            tracer.add_span('s{}'.format(i), 'test', float(i), 0.1)
        assert len(tracer) == 10
        assert tracer.dropped == 15
        # the ring keeps the most recent window
        assert [s[0] for s in tracer.spans()] == \
            ['s{}'.format(i) for i in range(15, 25)]

    def test_reset(self):
        tracer = Tracer(capacity=4)
        for i in range(8):
            tracer.add_span('s', 'test', float(i), 0.1)
        tracer.reset()
        assert len(tracer) == 0
        assert tracer.dropped == 0

    def test_merge_preserves_foreign_tracks(self):
        tracer = Tracer()
        shipped = [('parquet_read', 'io', 1.0, 0.5, 4242, 7, None)]
        tracer.merge(shipped)
        (name, cat, start, dur, pid, tid, args) = tracer.spans()[0]
        assert (name, pid, tid) == ('parquet_read', 4242, 7)

    def test_make_span_stamps_caller_track(self):
        import os
        import threading
        span = make_span('x', 'test', 0.0, 1.0)
        assert span[4] == os.getpid()
        assert span[5] == threading.get_ident()

    def test_resolve_trace(self, monkeypatch):
        monkeypatch.delenv('PETASTORM_TPU_TRACE', raising=False)
        assert resolve_trace(None) == (False, None)
        assert resolve_trace(True) == (True, None)
        assert resolve_trace(False) == (False, None)
        assert resolve_trace('/tmp/t.json') == (True, '/tmp/t.json')
        monkeypatch.setenv('PETASTORM_TPU_TRACE', '1')
        assert resolve_trace(None) == (True, None)
        monkeypatch.setenv('PETASTORM_TPU_TRACE', 'off')
        assert resolve_trace(None) == (False, None)
        monkeypatch.setenv('PETASTORM_TPU_TRACE', '/out/trace.json')
        assert resolve_trace(None) == (True, '/out/trace.json')
        # an explicit kwarg beats the env var
        assert resolve_trace(False) == (False, None)


class TestReaderTracing:
    def test_off_by_default(self, synthetic_dataset, monkeypatch):
        monkeypatch.delenv('PETASTORM_TPU_TRACE', raising=False)
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         num_epochs=1) as reader:
            assert reader.tracer is None
            sum(1 for _ in reader)

    def test_thread_pool_stage_spans(self, synthetic_dataset, tmp_path):
        with make_reader(synthetic_dataset.url, reader_pool_type='thread',
                         workers_count=2, num_epochs=1, trace=True) as reader:
            count = sum(1 for _ in reader)
            path = str(tmp_path / 'thread_trace.json')
            reader.tracer.export_chrome_trace(path)
        assert count == len(synthetic_dataset.data)
        _assert_valid_chrome_trace(
            path, expect_names=('ventilate', 'parquet_read', 'decode_columns',
                                'process_item', 'queue_wait'))

    def test_process_pool_span_shipment_and_tracks(self, synthetic_dataset,
                                                   tmp_path):
        """The acceptance-criteria scenario: a process-pool run must export a
        valid chrome trace with distinct worker (one pid per spawned
        interpreter) and consumer tracks on one timeline."""
        import os
        with make_reader(synthetic_dataset.url, reader_pool_type='process',
                         workers_count=2, num_epochs=1, trace=True) as reader:
            count = sum(1 for _ in reader)
            path = str(tmp_path / 'process_trace.json')
            reader.tracer.export_chrome_trace(path)
        assert count == len(synthetic_dataset.data)
        events = _assert_valid_chrome_trace(
            path, expect_names=('serialize', 'deserialize', 'process_item',
                                'parquet_read', 'queue_wait'),
            min_pids=3)  # consumer + 2 worker interpreters
        consumer_pid = os.getpid()
        worker_span_pids = {e['pid'] for e in events
                            if e['name'] == 'process_item'}
        consumer_span_pids = {e['pid'] for e in events
                              if e['name'] in ('queue_wait', 'deserialize')}
        assert consumer_pid not in worker_span_pids
        assert consumer_span_pids == {consumer_pid}

    def test_readahead_spans_on_background_track(self, synthetic_dataset):
        with make_columnar_reader(synthetic_dataset.url,
                                  reader_pool_type='thread', workers_count=1,
                                  num_epochs=1, io_readahead=2,
                                  trace=True) as reader:
            sum(1 for _ in reader)
            spans = reader.tracer.spans()
        by_name = {}
        for span in spans:
            by_name.setdefault(span[0], []).append(span)
        assert by_name.get('readahead_read'), 'no readahead spans recorded'
        # the background reader thread is its own track, distinct from the
        # worker thread's process_item spans
        readahead_tids = {s[5] for s in by_name['readahead_read']}
        worker_tids = {s[5] for s in by_name['process_item']}
        assert readahead_tids.isdisjoint(worker_tids)

    def test_span_shipment_survives_worker_death(self, synthetic_dataset):
        """Spans shipped before a worker dies stay in the tracer, and the
        pool's death report does not corrupt the trace export."""
        with make_reader(synthetic_dataset.url, reader_pool_type='process',
                         workers_count=2, num_epochs=None, trace=True,
                         worker_recovery=False) as reader:
            it = iter(reader)
            for _ in range(5):
                next(it)
            while not reader.tracer.spans():
                next(it)   # accounting messages lag payloads; keep pulling
            # kill the worker interpreters mid-stream (death is detected on
            # the next empty poll, so every worker must stop producing)
            for proc in reader._pool._processes:
                proc.kill()
            with pytest.raises((RuntimeError, StopIteration)):
                for _ in range(100_000):
                    next(it)
            spans = reader.tracer.spans()
            events = reader.tracer.chrome_trace_events()
        assert spans, 'pre-death spans were lost'
        assert any(e['ph'] == 'X' for e in events)
        json.dumps(events)   # still serializable end to end

    def test_trace_env_var_auto_export(self, synthetic_dataset, tmp_path,
                                       monkeypatch):
        out = tmp_path / 'auto' / 'trace.json'
        out.parent.mkdir()
        monkeypatch.setenv('PETASTORM_TPU_TRACE', str(out))
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         num_epochs=1) as reader:
            assert reader.tracer is not None
            sum(1 for _ in reader)
        # the context exit (stop + join) wrote the chrome trace
        _assert_valid_chrome_trace(str(out), expect_names=('process_item',))


class TestLoaderTracing:
    def test_train_step_and_infeed_spans(self, synthetic_dataset):
        import threading
        with make_reader(synthetic_dataset.url, reader_pool_type='thread',
                         workers_count=2, num_epochs=1, trace=True,
                         schema_fields=['^id$', '^image_png$']) as reader:
            loader = JaxDataLoader(reader, batch_size=16)
            assert loader.tracer is reader.tracer
            batches = 0
            for _ in loader:
                time.sleep(0.002)   # the "train step"
                batches += 1
            spans = reader.tracer.spans()
            by_name = {}
            for span in spans:
                by_name.setdefault(span[0], []).append(span)
            assert len(by_name.get('infeed_wait', ())) >= batches
            # one train_step span per consumed batch except the last
            assert len(by_name.get('train_step', ())) >= batches - 1
            for span in by_name['train_step']:
                assert span[3] >= 0.002   # covers the consumer's sleep

            # second epoch (the loader auto-resets the reader): device
            # staging through the prefetch pipeline records device_stage
            # spans on the prefetch thread — its own track
            staged = list(prefetch_to_device(loader, stats=reader.stats,
                                             tracer=reader.tracer))
            stage_spans = [s for s in reader.tracer.spans()
                           if s[0] == 'device_stage']
        assert staged
        assert stage_spans, 'no device staging spans'
        assert threading.get_ident() not in {s[5] for s in stage_spans}


class TestMetricsEmitter:
    def test_jsonl_emission_and_reader_lifecycle(self, synthetic_dataset,
                                                 tmp_path):
        out = tmp_path / 'metrics.jsonl'
        with make_reader(synthetic_dataset.url, reader_pool_type='thread',
                         workers_count=2, num_epochs=1, metrics_interval=0.05,
                         metrics_out=str(out)) as reader:
            count = sum(1 for _ in reader)
            emitter = reader._metrics_emitter
        # Reader.stop()/join() (the context exit) stopped the emitter thread
        # and flushed a final snapshot
        assert emitter.emit_count >= 1
        assert emitter._thread is None
        lines = [json.loads(line) for line in out.read_text().splitlines()]
        assert len(lines) == emitter.emit_count
        final = lines[-1]
        assert final['items_out'] > 0
        assert count == len(synthetic_dataset.data)
        for key in ('ts', 'worker_io_s', 'worker_decode_s', 'items_per_s'):
            assert key in final

    def test_prometheus_format(self, tmp_path):
        from petastorm_tpu.workers.stats import ReaderStats
        stats = ReaderStats()
        stats.add('items_out', 7)
        stats.add_time('worker_io_s', 1.25)
        out = tmp_path / 'metrics.prom'
        emitter = MetricsEmitter(stats.snapshot, interval_s=60, path=str(out))
        emitter.emit_once()
        text = out.read_text()
        assert 'petastorm_tpu_items_out 7.0' in text
        assert 'petastorm_tpu_worker_io_s 1.25' in text
        assert '# TYPE petastorm_tpu_items_out gauge' in text
        # rewrites in place: a second emit replaces the exposition file
        # (same line count, fresh window-derived values) instead of appending
        emitter.emit_once()
        text2 = out.read_text()
        assert len(text2.splitlines()) == len(text.splitlines())
        assert 'petastorm_tpu_items_out 7.0' in text2

    def test_interval_requires_path(self, synthetic_dataset):
        with pytest.raises(ValueError, match='metrics_out'):
            make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                        metrics_interval=5)

    def test_background_thread_emits_periodically(self, tmp_path):
        from petastorm_tpu.workers.stats import ReaderStats
        stats = ReaderStats()
        out = tmp_path / 'm.jsonl'
        emitter = MetricsEmitter(stats.snapshot, interval_s=0.02,
                                 path=str(out))
        emitter.start()
        time.sleep(0.15)
        emitter.stop()
        assert emitter.emit_count >= 2   # periodic ticks + final flush
        lines = out.read_text().splitlines()
        assert len(lines) == emitter.emit_count


# promoted to petastorm_tpu.test_util.threads (and a conftest teardown
# fixture over every reader-lifecycle lane); the in-test assertions below
# stay because they check the state mid-test, right after join()
from petastorm_tpu.test_util.threads import petastorm_threads as _petastorm_threads  # noqa: E402,E501


class TestReaderShutdownLifecycle:
    """The daemon-thread shutdown contract shared by the metrics emitter,
    the readahead reader threads, the health watchdog and the debug HTTP
    server: Reader.stop()/join() is idempotent, joins everything with a
    timeout, and leaves no dangling petastorm threads behind."""

    def test_stop_join_idempotent_with_all_background_layers(
            self, synthetic_dataset, tmp_path):
        out = tmp_path / 'metrics.jsonl'
        reader = make_reader(synthetic_dataset.url, reader_pool_type='thread',
                             workers_count=2, num_epochs=1,
                             metrics_interval=0.05, metrics_out=str(out),
                             io_readahead=2, debug_port=0, stall_timeout=30)
        count = sum(1 for _ in reader)
        assert count == len(synthetic_dataset.data)
        reader.stop()
        reader.join()
        # a second (and third) stop/join must be clean no-ops — teardown
        # paths cannot always know whether an earlier join already ran
        reader.stop()
        reader.join()
        reader.join()
        assert reader._metrics_emitter._thread is None
        assert reader._watchdog._thread is None
        assert reader._debug_server._thread is None
        assert _petastorm_threads() == [], \
            'dangling petastorm threads after Reader.join()'

    def test_shutdown_clean_after_pool_died_uncleanly(self, synthetic_dataset,
                                                      tmp_path):
        """The health/metrics layers must come down even when the pool below
        is a corpse (killed worker interpreters mid-stream)."""
        out = tmp_path / 'metrics.jsonl'
        reader = make_reader(synthetic_dataset.url, reader_pool_type='process',
                             workers_count=2, num_epochs=None,
                             metrics_interval=0.05, metrics_out=str(out),
                             debug_port=0, stall_timeout=30,
                             worker_recovery=False)
        it = iter(reader)
        for _ in range(5):
            next(it)
        for proc in reader._pool._processes:
            proc.kill()
        with pytest.raises((RuntimeError, StopIteration)):
            for _ in range(100_000):
                next(it)
        reader.stop()
        reader.join()
        reader.join()   # idempotent even on this path
        assert reader._metrics_emitter._thread is None
        assert reader._watchdog._thread is None
        assert reader._debug_server._thread is None
        assert _petastorm_threads() == [], \
            'dangling petastorm threads after unclean pool death'


#: One Prometheus text-exposition sample line: metric name, single space,
#: then a float literal or the spec's NaN/+Inf/-Inf — what a scrape parser
#: accepts (anything else is a formatter bug).
_PROM_SAMPLE = __import__('re').compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]* '
    r'(?:[+-]?(?:[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)|NaN|\+Inf|-Inf)$')


class TestPrometheusText:
    def test_every_sample_line_parses(self):
        snapshot = {'worker_io_s': 1.25, 'items_out': 42, 'window_s': 0.0,
                    'tiny': 1e-07, 'huge': 3.5e18}
        lines = prometheus_text(snapshot).strip().splitlines()
        samples = [line for line in lines if not line.startswith('#')]
        assert len(samples) == len(snapshot)
        for line in samples:
            assert _PROM_SAMPLE.match(line), line

    def test_help_and_type_precede_each_sample(self):
        lines = prometheus_text({'a': 1, 'b': 2.5}).strip().splitlines()
        assert lines[0].startswith('# HELP petastorm_tpu_a ')
        assert lines[1] == '# TYPE petastorm_tpu_a gauge'
        assert lines[2].startswith('petastorm_tpu_a ')
        assert lines[3].startswith('# HELP petastorm_tpu_b ')

    def test_non_finite_values_use_spec_literals(self):
        text = prometheus_text({'nan_ratio': float('nan'),
                                'pos': float('inf'),
                                'neg': -float('inf')})
        samples = [line for line in text.strip().splitlines()
                   if not line.startswith('#')]
        values = dict(line.split(' ', 1) for line in samples)
        assert values['petastorm_tpu_nan_ratio'] == 'NaN'
        assert values['petastorm_tpu_pos'] == '+Inf'
        assert values['petastorm_tpu_neg'] == '-Inf'
        # none of the python reprs a scrape parser rejects
        assert 'nan' not in values.values() and 'inf' not in values.values()
        for line in samples:
            assert _PROM_SAMPLE.match(line), line

    def test_non_numeric_values_skipped(self):
        text = prometheus_text({'s': 'str', 'flag': True, 'ok': 1.0})
        assert 'petastorm_tpu_s' not in text
        assert 'petastorm_tpu_flag' not in text
        assert 'petastorm_tpu_ok' in text


#: One histogram bucket sample: ``name_bucket{le="<float or +Inf>"} <int>``
#: — the conformance shape `histogram_quantile()` queries depend on.
_PROM_BUCKET = __import__('re').compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)_bucket\{le="'
    r'((?:[0-9.]+(?:e-?[0-9]+)?)|\+Inf)"\} ([0-9]+)$')


class TestPrometheusHistogramConformance:
    """The latency plane's histogram rendering, held to the exposition
    format's histogram contract: ``# TYPE ... histogram``, cumulative
    ``_bucket`` samples with increasing ``le``, a terminal ``le="+Inf"``
    bucket equal to ``_count``, and ``_sum``/``_count`` lines."""

    def _text_with_histograms(self):
        from petastorm_tpu.latency import PipelineLatency
        from petastorm_tpu.workers.stats import LATENCY_HISTOGRAMS_KEY
        plane = PipelineLatency()
        for v in (1e-5, 4e-4, 4e-4, 0.03, 2.5):
            plane.record('queue_wait', v)
        plane.record('e2e_batch', 0.25)
        snapshot = {'items_out': 6, 'window_s': 1.0,
                    LATENCY_HISTOGRAMS_KEY: plane.export_state()}
        return prometheus_text(snapshot)

    def test_histogram_blocks_parse_and_are_cumulative(self):
        text = self._text_with_histograms()
        lines = text.strip().splitlines()
        assert ('# TYPE petastorm_tpu_latency_queue_wait_seconds histogram'
                in lines)
        for metric in ('queue_wait', 'e2e_batch'):
            name = 'petastorm_tpu_latency_{}_seconds'.format(metric)
            buckets = []
            for line in lines:
                match = _PROM_BUCKET.match(line)
                if match and match.group(1) == name:
                    buckets.append((match.group(2), int(match.group(3))))
            assert buckets, name
            counts = [count for _, count in buckets]
            assert counts == sorted(counts), 'cumulative le samples'
            les = [le for le, _ in buckets]
            assert les[-1] == '+Inf', 'terminal +Inf bucket is mandatory'
            finite = [float(le) for le in les[:-1]]
            assert finite == sorted(finite), 'le labels must increase'
            count_line = [ln for ln in lines
                          if ln.startswith(name + '_count ')]
            assert count_line and int(count_line[0].split()[1]) == counts[-1]
            assert any(ln.startswith(name + '_sum ') for ln in lines)

    def test_raw_state_key_never_leaks_as_gauge(self):
        text = self._text_with_histograms()
        assert '_latency_histograms' not in text
        # the plain gauges still render beside the histogram blocks
        assert 'petastorm_tpu_items_out 6' in text

    def test_reader_stats_snapshot_renders_histograms(self):
        from petastorm_tpu.workers.stats import ReaderStats
        stats = ReaderStats()
        if stats.latency is None:
            import pytest
            pytest.skip('latency plane disabled in this environment')
        stats.record_latency('queue_wait', 0.01)
        text = prometheus_text(stats.snapshot())
        assert ('petastorm_tpu_latency_queue_wait_seconds_bucket{le="+Inf"} 1'
                in text)
        # every non-histogram sample line still parses
        for line in text.strip().splitlines():
            if line.startswith('#') or '_bucket{' in line:
                continue
            assert _PROM_SAMPLE.match(line), line


class TestAtomicExports:
    def test_chrome_trace_export_is_atomic(self, tmp_path):
        tracer = Tracer()
        tracer.add_span('x', 'cat', 0.0, 1.0)
        path = tmp_path / 'trace.json'
        tracer.export_chrome_trace(str(path))
        # the tmp file never survives a completed export, and the artifact
        # is whole JSON
        leftovers = [p for p in tmp_path.iterdir() if '.tmp.' in p.name]
        assert leftovers == []
        with open(path) as f:
            assert json.load(f)['traceEvents']

    def test_failed_export_leaves_previous_file_intact(self, tmp_path,
                                                       monkeypatch):
        tracer = Tracer()
        tracer.add_span('x', 'cat', 0.0, 1.0)
        path = tmp_path / 'trace.json'
        tracer.export_chrome_trace(str(path))
        before = path.read_text()

        def boom(*_a, **_k):
            raise OSError('disk full mid-dump')

        monkeypatch.setattr(json, 'dump', boom)
        with pytest.raises(OSError):
            tracer.export_chrome_trace(str(path))
        # previous good export untouched; no truncated tmp file left behind
        assert path.read_text() == before
        assert [p for p in tmp_path.iterdir() if '.tmp.' in p.name] == []

    def test_flight_record_write_is_atomic(self, tmp_path, monkeypatch):
        from petastorm_tpu.health import write_flight_record
        path = tmp_path / 'flight.json'
        write_flight_record(str(path), {'ok': 1})
        with open(path) as f:
            assert json.load(f) == {'ok': 1}

        def boom(*_a, **_k):
            raise OSError('disk full mid-dump')

        monkeypatch.setattr(json, 'dump', boom)
        with pytest.raises(OSError):
            write_flight_record(str(path), {'ok': 2})
        with open(path) as f:
            assert json.load(f) == {'ok': 1}
        assert [p for p in tmp_path.iterdir() if '.tmp.' in p.name] == []
