"""Roofline profiler tests: interval-union attribution (overlaps union, not
sum), calibration-cache invalidation on dataset-digest change, advisor
monotonicity, the ``/profile`` debug route (schema + 404-when-off) and the
flight-record roofline section."""

import json
import os

import pytest

from petastorm_tpu import profiler
from petastorm_tpu.profiler import (advise, attribute, build_profile,
                                    dataset_digest, interval_union,
                                    predict_throughput, roofline_gauges,
                                    roofline_summary)


def _span(name, cat, start, dur, pid=1, tid=1):
    return (name, cat, start, dur, pid, tid, None)


def _http_get(port, route):
    from http.client import HTTPConnection
    conn = HTTPConnection('127.0.0.1', port, timeout=10)
    try:
        conn.request('GET', route)
        response = conn.getresponse()
        return response.status, response.read().decode('utf-8')
    finally:
        conn.close()


@pytest.fixture(scope='module')
def mnist_store(tmp_path_factory):
    """A small decode-bound (png) store for calibration/profile tests."""
    from petastorm_tpu.test_util.dataset_gen import \
        generate_mnist_images_dataset
    path = tmp_path_factory.mktemp('roofline') / 'mnist'
    url = 'file://' + str(path)
    # big enough that the io probe's timed window is several ms — a
    # sub-ms window mis-ranks io vs decode under a loaded CI host
    generate_mnist_images_dataset(url, rows=1024)
    return url


@pytest.fixture()
def calibration_dir(tmp_path, monkeypatch):
    """Tests must never touch the user's ~/.cache calibration store."""
    target = tmp_path / 'calibration'
    monkeypatch.setenv(profiler.CALIBRATION_DIR_ENV_VAR, str(target))
    return str(target)


class TestIntervalUnion:
    def test_overlapping_intervals_union_not_sum(self):
        # two fully-overlapped 1s spans are 1s of wall, not 2
        assert interval_union([(0.0, 1.0), (0.0, 1.0)]) == pytest.approx(1.0)
        # partial overlap merges
        assert interval_union([(0.0, 1.0), (0.5, 2.0)]) == pytest.approx(2.0)

    def test_disjoint_and_nested(self):
        assert interval_union([(0, 1), (2, 3)]) == pytest.approx(2.0)
        assert interval_union([(0, 10), (2, 3), (4, 5)]) == pytest.approx(10)
        assert interval_union([]) == 0.0

    def test_unsorted_and_inverted_input(self):
        assert interval_union([(5, 6), (0, 1), (3, 2)]) == pytest.approx(3.0)


class TestAttribution:
    def test_overlapped_stage_spans_attribute_by_union(self):
        # two worker threads decode concurrently over [0,1] and [0.5,1.5];
        # io runs [0,0.25]+[1.0,1.25]. Naive sums would say decode=2.0s.
        spans = [
            _span('decode_columns', 'decode', 0.0, 1.0, tid=1),
            _span('decode_columns', 'decode', 0.5, 1.0, tid=2),
            _span('parquet_read', 'io', 0.0, 0.25, tid=1),
            _span('readahead_read', 'io', 1.0, 0.25, tid=2),
        ]
        out = attribute(spans)
        assert out['source'] == 'spans'
        assert out['wall_s'] == pytest.approx(1.5)
        assert out['stages']['decode']['busy_s'] == pytest.approx(1.5)
        assert out['stages']['io']['busy_s'] == pytest.approx(0.5)
        assert out['critical_stage'] == 'decode'
        # decode(1.5) + io(0.5) ran inside a 1.5s union => 0.5s overlapped
        assert out['overlap_s'] == pytest.approx(0.5)

    def test_idle_stages_never_bind(self):
        spans = [
            _span('queue_wait', 'consumer', 0.0, 10.0),
            _span('decode_columns', 'decode', 0.0, 1.0),
        ]
        out = attribute(spans)
        assert out['critical_stage'] == 'decode'
        assert 'consumer_wait' in out['stages']

    def test_snapshot_fallback_without_spans(self):
        snapshot = {'window_s': 4.0, 'worker_io_s': 1.0,
                    'worker_decode_s': 3.0}
        out = attribute(None, snapshot=snapshot)
        assert out['source'] == 'snapshot'
        assert out['critical_stage'] == 'decode'
        # canonical stage names: stages[critical_stage] joins in BOTH modes
        assert out['stages'][out['critical_stage']]['busy_fraction'] == \
            pytest.approx(0.75)
        assert out['stages']['io']['busy_s'] == pytest.approx(1.0)

    def test_reversed_interval_normalized_before_sort(self):
        # (5,1) must behave as (1,5): union with (2,3) is 4.0, and the
        # reversed tuple must not sort AFTER (2,3) and break the merge
        assert interval_union([(5, 1), (2, 3)]) == pytest.approx(4.0)


class TestCalibration:
    def _parts(self, url):
        from petastorm_tpu.etl.dataset_metadata import (
            infer_or_load_unischema, load_row_groups)
        from petastorm_tpu.fs import get_filesystem_and_path_or_paths
        fs, path, _ = get_filesystem_and_path_or_paths(url)
        pieces = load_row_groups(fs, path)
        schema, _ = infer_or_load_unischema(fs, path)
        return fs, path, pieces, schema

    def test_calibrate_measures_real_codec_paths(self, mnist_store,
                                                 calibration_dir):
        fs, path, pieces, schema = self._parts(mnist_store)
        cal = profiler.calibrate(fs, path, pieces, schema)
        assert cal['dataset_digest'] == dataset_digest(pieces, schema)
        for stage in ('io', 'decode', 'serialize'):
            assert cal['ceilings'][stage] > 0
        per_codec = cal['probes']['decode']['per_codec']
        assert 'CompressedImageCodec(png)' in per_codec
        assert per_codec['CompressedImageCodec(png)']['rows_per_s'] > 0
        # the artifact landed in the (test-scoped) cache dir
        assert os.path.exists(
            profiler.calibration_path(cal['dataset_digest']))

    def test_cached_mode_loads_without_probing(self, mnist_store,
                                               calibration_dir,
                                               monkeypatch):
        fs, path, pieces, schema = self._parts(mnist_store)
        cal = profiler.calibrate(fs, path, pieces, schema)
        # any probe call after this is a cache-miss bug
        monkeypatch.setattr(profiler, '_probe_storage',
                            lambda *a, **k: pytest.fail('re-probed'))
        loaded = profiler.get_calibration(fs, path, pieces, schema,
                                          mode='cached')
        assert loaded is not None
        assert loaded['dataset_digest'] == cal['dataset_digest']
        auto = profiler.get_calibration(fs, path, pieces, schema,
                                        mode='auto')
        assert auto['dataset_digest'] == cal['dataset_digest']

    def test_digest_change_invalidates_cache(self, mnist_store,
                                             calibration_dir):
        import dataclasses
        fs, path, pieces, schema = self._parts(mnist_store)
        profiler.calibrate(fs, path, pieces, schema)
        # the same dataset regenerated with a different row-group layout:
        # every (path, row_group, num_rows) digest input shifts
        mutated = [dataclasses.replace(p, num_rows=p.num_rows + 1)
                   for p in pieces]
        assert dataset_digest(mutated) != dataset_digest(pieces)
        # ...and a narrower column view gets its own calibration identity
        view = schema.create_schema_view([schema.fields['idx']])
        assert dataset_digest(pieces, view) != dataset_digest(pieces, schema)
        assert profiler.load_calibration(
            dataset_digest(mutated, schema)) is None
        # 'cached' honestly reports the miss instead of serving stale data
        assert profiler.get_calibration(fs, path, mutated, schema,
                                        mode='cached') is None

    def test_corrupt_artifact_reads_as_miss(self, mnist_store,
                                            calibration_dir):
        fs, path, pieces, schema = self._parts(mnist_store)
        cal = profiler.calibrate(fs, path, pieces, schema)
        artifact = profiler.calibration_path(cal['dataset_digest'])
        with open(artifact, 'w') as f:
            f.write('{"truncated')
        assert profiler.load_calibration(cal['dataset_digest']) is None


class TestAdvisorModel:
    CEILINGS = {'io': 200.0, 'decode': 100.0, 'serialize': 5000.0,
                'device_stage': 2000.0}

    def test_more_workers_never_predicts_lower_ceiling(self):
        for cpu_count in (1, 2, 4, 16):
            curve = [predict_throughput(self.CEILINGS, workers=w,
                                        cpu_count=cpu_count,
                                        io_overlap=True)
                     for w in range(1, 33)]
            assert all(b >= a - 1e-9 for a, b in zip(curve, curve[1:])), \
                'non-monotone at cpu_count={}: {}'.format(cpu_count, curve)

    def test_workers_beyond_cores_add_nothing(self):
        one_core = predict_throughput(self.CEILINGS, workers=8, cpu_count=1,
                                      io_overlap=True)
        assert one_core == predict_throughput(self.CEILINGS, workers=1,
                                              cpu_count=1, io_overlap=True)

    def test_overlap_beats_serial_and_cached_beats_both(self):
        serial = predict_throughput(self.CEILINGS, io_overlap=False)
        overlapped = predict_throughput(self.CEILINGS, io_overlap=True)
        cached = predict_throughput(self.CEILINGS, io_overlap=True,
                                    cached=True)
        assert serial < overlapped <= cached
        # 1:2 io:decode serial harmonic = 1/(1/200 + 1/100) = 66.7
        assert serial == pytest.approx(66.67, rel=1e-3)
        assert overlapped == pytest.approx(100.0)

    def test_process_pool_caps_at_serializer(self):
        ceilings = dict(self.CEILINGS, serialize=50.0)
        assert predict_throughput(ceilings, io_overlap=True,
                                  in_process=False) == pytest.approx(50.0)
        assert predict_throughput(ceilings, io_overlap=True,
                                  in_process=True) == pytest.approx(100.0)

    def _decode_bound_profile(self):
        calibration = {'ceilings': dict(self.CEILINGS), 'cpu_count': 4,
                       'host': 'h', 'dataset_digest': 'x',
                       'rows_per_group': 10.0}
        snapshot = {'items_per_s': 5.0, 'window_s': 2.0,
                    'io_overlap_fraction': 0.0, 'items_out': 10}
        return build_profile(snapshot, calibration, workers_count=1,
                             pool_type='thread', cache_type='null')

    def test_advisor_ranked_positive_deltas(self):
        profile = self._decode_bound_profile()
        recs = profile['advisor']
        assert recs, 'a 1-worker decode-bound profile must yield advice'
        knobs = [r['knob'] for r in recs]
        assert 'workers_count' in knobs
        assert "cache_type='shared'" in knobs
        deltas = [r['predicted_delta_pct'] for r in recs]
        assert deltas == sorted(deltas, reverse=True)
        assert all(d > 0 for d in deltas)
        # the advisor replays the same model the verdict uses: no
        # recommendation may exceed the best ceiling in the calibration
        for rec in recs:
            assert rec['predicted_samples_per_s'] <= max(
                self.CEILINGS.values())

    def test_profile_names_binding_stage_and_fraction(self):
        profile = self._decode_bound_profile()
        assert profile['binding_stage'] == 'decode'
        # measured 5 items/s * 10 rows/group = 50 rows/s of a 100 ceiling
        assert profile['measured_samples_per_s'] == pytest.approx(50.0)
        assert profile['roofline_fraction'] == pytest.approx(0.5)
        gauges = roofline_gauges(profile)
        assert gauges['binding_stage'] == 'decode'
        assert gauges['roofline_fraction'] == pytest.approx(0.5)
        assert gauges['stage_ceiling_decode'] == pytest.approx(100.0)
        summary = roofline_summary(profile)
        assert summary['binding_stage'] == 'decode'

    def test_above_ceiling_measurement_warns(self):
        # a short measured window draining pre-decoded buffers can read far
        # above the ceiling; the profile must flag it as a measurement
        # problem, not report a 900% roofline with a straight face
        calibration = {'ceilings': dict(self.CEILINGS), 'cpu_count': 1,
                       'host': 'h', 'dataset_digest': 'x',
                       'rows_per_group': 10.0}
        profile = build_profile({'items_per_s': 1.0}, calibration,
                                samples_per_sec=900.0, workers_count=1)
        assert profile['roofline_fraction'] > profiler.SANE_FRACTION_LIMIT
        assert 'drained pre-decoded buffers' in profile['warning']
        assert 'WARNING' in profiler.explain(profile)
        # a sane fraction carries no warning
        ok = build_profile({'items_per_s': 1.0}, calibration,
                           samples_per_sec=50.0, workers_count=1)
        assert 'warning' not in ok

    def test_warm_shared_cache_judged_against_post_cache_stages(self):
        # a proven-warm shared cache skips io+decode: no false "broken
        # measurement" warning, binding moves to the post-cache stages
        calibration = {'ceilings': dict(self.CEILINGS), 'cpu_count': 1,
                       'host': 'h', 'dataset_digest': 'x',
                       'rows_per_group': 10.0}
        snapshot = {'items_per_s': 1.0, 'shared_hits': 90,
                    'shared_misses': 10}
        profile = build_profile(snapshot, calibration,
                                samples_per_sec=1500.0, workers_count=1,
                                cache_type='shared')
        assert profile['cache_warm'] is True
        assert profile['binding_stage'] == 'device_stage'
        assert 'io' not in profile['effective_ceilings']
        assert 'warning' not in profile
        assert profile['roofline_fraction'] == pytest.approx(0.75)
        # an unproven (cold) shared cache keeps the io+decode verdict but
        # an above-ceiling rate names cache replay, not a broken probe
        cold = build_profile({'items_per_s': 1.0, 'shared_hits': 0,
                              'shared_misses': 10}, calibration,
                             samples_per_sec=1500.0, workers_count=1,
                             cache_type='shared')
        assert cold['cache_warm'] is False
        assert 'cache-replay' in cold['warning']

    def test_uncalibrated_profile_degrades(self):
        profile = build_profile({'items_per_s': 3.0}, None)
        assert profile['calibrated'] is False
        assert profile['binding_stage'] is None
        assert advise(profile) == []


class TestReaderProfileSurfaces:
    def test_profile_reports_roofline_and_gauges(self, mnist_store,
                                                 calibration_dir):
        from petastorm_tpu import make_columnar_reader
        from petastorm_tpu.tracing import prometheus_text
        with make_columnar_reader(mnist_store, num_epochs=1,
                                  workers_count=2, trace=True) as reader:
            for _ in reader:
                pass
            profile = reader.profile()
            assert profile['calibrated']
            assert profile['binding_stage'] == 'decode'
            assert profile['attribution']['source'] == 'spans'
            assert 'decode' in profile['attribution']['stages']
            snapshot = reader._stats_snapshot()
            assert snapshot['binding_stage'] == 'decode'
            assert 'stage_ceiling_decode' in snapshot
            text = prometheus_text(snapshot)
            assert 'petastorm_tpu_binding_stage{stage="decode"} 1' in text
            assert 'petastorm_tpu_roofline_fraction' in text

    def test_explain_throughput_sentence(self, mnist_store,
                                         calibration_dir):
        from petastorm_tpu import make_columnar_reader
        with make_columnar_reader(mnist_store, num_epochs=1,
                                  workers_count=2) as reader:
            for _ in reader:
                pass
            sentence = reader.explain_throughput()
            assert 'binding stage' in sentence
            assert 'decode' in sentence

    def test_profile_route_schema_and_404_when_off(self, mnist_store,
                                                   calibration_dir,
                                                   monkeypatch):
        from petastorm_tpu import make_columnar_reader
        with make_columnar_reader(mnist_store, num_epochs=1,
                                  workers_count=2, debug_port=0) as reader:
            # before any calibration exists the route still answers (an
            # HTTP probe must stay cheap: cached-mode, no probes)
            status, body = _http_get(reader.debug_port, '/profile')
            assert status == 200
            assert json.loads(body)['calibrated'] is False
            for _ in reader:
                pass
            reader.profile()      # creates the calibration artifact
            status, body = _http_get(reader.debug_port, '/profile')
            assert status == 200
            blob = json.loads(body)
            assert blob['calibrated'] is True
            assert blob['binding_stage'] == 'decode'
            assert 'advisor' in blob and 'attribution' in blob

        # kill switch: the route must 404, the method must refuse
        monkeypatch.setenv(profiler.PROFILER_ENV_VAR, '0')
        with make_columnar_reader(mnist_store, num_epochs=1,
                                  workers_count=2, debug_port=0) as reader:
            status, body = _http_get(reader.debug_port, '/profile')
            assert status == 404
            assert 'disabled' in body
            with pytest.raises(RuntimeError, match='disabled'):
                reader.profile()

    def test_flight_record_gains_roofline_section(self, mnist_store,
                                                  calibration_dir,
                                                  tmp_path):
        from petastorm_tpu import make_columnar_reader
        with make_columnar_reader(mnist_store, num_epochs=1,
                                  workers_count=2) as reader:
            for _ in reader:
                pass
            before = reader.dump_flight_record(
                path=str(tmp_path / 'before.json'))
            assert 'roofline' not in json.load(open(before))
            reader.profile()
            after = reader.dump_flight_record(
                path=str(tmp_path / 'after.json'))
            record = json.load(open(after))
            assert record['roofline']['binding_stage'] == 'decode'
            assert record['roofline']['roofline_fraction'] is not None

    def test_infeed_diagnosis_roofline_section(self):
        from petastorm_tpu.jax_utils import infeed_diagnosis
        snapshot = {'worker_io_s': 1.0, 'worker_decode_s': 5.0,
                    'worker_publish_wait_s': 0.0}
        profile = {'kind': 'petastorm_tpu_roofline_profile',
                   'measured_samples_per_s': 50.0,
                   'binding_stage': 'decode',
                   'binding_ceiling_samples_per_s': 100.0,
                   'roofline_fraction': 0.5,
                   'attribution': {'critical_stage': 'decode'}}
        out = infeed_diagnosis(snapshot, roofline=profile)
        assert out['roofline']['binding_stage'] == 'decode'
        assert out['roofline']['roofline_fraction'] == 0.5
        assert 'kind' not in out['roofline']
