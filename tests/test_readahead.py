"""Row-group readahead tests: the prefetch unit (sync/take/cancel/errors),
the bounded file-handle LRU, reader integration across pool types and worker
paths (row/columnar/batch/ngram), order preservation, the stats-driven auto
depth, and slow storage under readahead."""

import threading
import time

import numpy as np
import pytest

from petastorm_tpu.readers.piece_worker import FileHandleCache
from petastorm_tpu.readers.readahead import (AUTO_MAX_DEPTH,
                                             RowGroupReadahead)
from petastorm_tpu.reader import (make_batch_reader, make_columnar_reader,
                                  make_reader)


class _FakeHandle:
    def __init__(self, path):
        self.path = path
        self.closed = False

    def close(self):
        self.closed = True


class TestFileHandleCache:
    def test_caches_and_reuses(self):
        opened = []

        def open_fn(path):
            handle = _FakeHandle(path)
            opened.append(handle)
            return handle

        cache = FileHandleCache(open_fn, max_size=4)
        a1 = cache.get('a')
        a2 = cache.get('a')
        assert a1 is a2
        assert len(opened) == 1

    def test_evicts_lru_and_closes(self):
        cache = FileHandleCache(_FakeHandle, max_size=2)
        a = cache.get('a')
        b = cache.get('b')
        cache.get('a')             # refresh 'a': 'b' is now the LRU entry
        c = cache.get('c')         # evicts 'b'
        assert b.closed
        assert not a.closed and not c.closed
        assert len(cache) == 2
        assert 'b' not in cache and 'a' in cache and 'c' in cache

    def test_close_all(self):
        cache = FileHandleCache(_FakeHandle, max_size=4)
        handles = [cache.get(p) for p in 'abc']
        cache.close_all()
        assert all(h.closed for h in handles)
        assert len(cache) == 0

    def test_rejects_zero_size(self):
        with pytest.raises(ValueError):
            FileHandleCache(_FakeHandle, max_size=0)


class _Recorder:
    """Minimal WorkerBase-shaped stats sink for drain_stats_into."""

    def __init__(self):
        self.times = {}
        self.counts = {}
        self.gauges = {}

    def record_time(self, stage, seconds):
        self.times[stage] = self.times.get(stage, 0.0) + seconds

    def record_count(self, name, n=1):
        self.counts[name] = self.counts.get(name, 0) + n

    def record_gauge(self, name, value):
        self.gauges[name] = value


def _plan(key):
    return (key, 'piece-' + str(key), ['col'])


class TestRowGroupReadahead:
    def test_prefetched_read_hits(self):
        reads = []

        def read_fn(piece, columns):
            reads.append(piece)
            return ('table', piece)

        ra = RowGroupReadahead(read_fn, depth=2)
        try:
            ra.sync([_plan(1), _plan(2), _plan(3)])   # depth 2: schedules 1, 2
            assert ra.take(1) == ('table', 'piece-1')
            ra.sync([_plan(2), _plan(3)])
            assert ra.take(2) == ('table', 'piece-2')
            assert ra.take(3) == ('table', 'piece-3')
            recorder = _Recorder()
            ra.drain_stats_into(recorder)
            assert recorder.counts['readahead_hits'] == 3
            assert 'readahead_misses' not in recorder.counts
            assert recorder.times['readahead_io_s'] > 0
        finally:
            ra.stop()

    def test_unplanned_key_is_a_miss(self):
        ra = RowGroupReadahead(lambda piece, columns: 'x', depth=2)
        try:
            assert ra.take(99) is None
            recorder = _Recorder()
            ra.drain_stats_into(recorder)
            assert recorder.counts['readahead_misses'] == 1
        finally:
            ra.stop()

    def test_desync_cancels_and_self_heals(self):
        ra = RowGroupReadahead(lambda piece, columns: piece, depth=2)
        try:
            ra.sync([_plan(1), _plan(2)])
            # the pool re-ordered work: outstanding [1, 2] is not a prefix
            ra.sync([_plan(5), _plan(6)])
            assert ra.take(5) == 'piece-5'
            assert ra.take(1) is None     # cancelled, falls back inline
        finally:
            ra.stop()

    def test_read_errors_surface_on_take(self):
        def read_fn(piece, columns):
            raise OSError('storage gone')

        ra = RowGroupReadahead(read_fn, depth=1)
        try:
            ra.sync([_plan(1)])
            with pytest.raises(OSError, match='storage gone'):
                ra.take(1)
        finally:
            ra.stop()

    def test_duplicate_keys_fifo(self):
        # shuffle_row_drop_partitions ventilates the same piece repeatedly:
        # duplicate keys must serve FIFO, one entry per occurrence
        served = []
        ra = RowGroupReadahead(lambda piece, columns: served.append(piece) or len(served),
                               depth=3)
        try:
            plans = [_plan(7), _plan(7), _plan(7)]
            ra.sync(plans)
            assert ra.take(7) == 1
            assert ra.take(7) == 2
            assert ra.take(7) == 3
        finally:
            ra.stop()

    def test_auto_depth_tracks_io_decode_ratio(self):
        # reads take ~4x the inter-take gap: auto depth should rise above its
        # initial value (and stay bounded)
        def slow_read(piece, columns):
            time.sleep(0.02)
            return piece

        ra = RowGroupReadahead(slow_read, depth='auto')
        try:
            keys = list(range(12))
            for i in keys:
                ra.sync([_plan(k) for k in keys[i:i + AUTO_MAX_DEPTH]])
                ra.take(i)
                time.sleep(0.005)   # "decode"
            assert 1 <= ra.depth <= AUTO_MAX_DEPTH
            assert ra.depth >= 3
        finally:
            ra.stop()

    def test_validates_depth(self):
        with pytest.raises(ValueError):
            RowGroupReadahead(lambda p, c: None, depth=-1)
        with pytest.raises(ValueError):
            RowGroupReadahead(lambda p, c: None, depth='warp')
        # 0 is legal since the autotune controller: dormant machinery that
        # set_depth() can activate live (docs/autotune.md)
        dormant = RowGroupReadahead(lambda p, c: None, depth=0)
        assert dormant.depth == 0
        dormant.stop()


def _reader_ids(url, **kwargs):
    with make_reader(url, shuffle_row_groups=False, num_epochs=1,
                     **kwargs) as reader:
        ids = [row.id for row in reader]
        diag = reader.diagnostics
    return ids, diag


class TestReaderIntegration:
    def test_results_and_order_match_serial(self, synthetic_dataset):
        """With one worker and shuffle off, readahead must preserve the exact
        ventilated-piece order the serial reader produces."""
        base_ids, _ = _reader_ids(synthetic_dataset.url,
                                  reader_pool_type='thread', workers_count=1)
        ra_ids, diag = _reader_ids(synthetic_dataset.url,
                                   reader_pool_type='thread', workers_count=1,
                                   io_readahead=3)
        assert ra_ids == base_ids
        assert diag['readahead_hits'] > 0
        assert diag['readahead_misses'] == 0
        assert diag['readahead_io_s'] > 0

    def test_thread_pool_multiworker_same_rows(self, synthetic_dataset):
        base_ids, _ = _reader_ids(synthetic_dataset.url,
                                  reader_pool_type='thread', workers_count=3)
        ra_ids, diag = _reader_ids(synthetic_dataset.url,
                                   reader_pool_type='thread', workers_count=3,
                                   io_readahead=2)
        assert sorted(ra_ids) == sorted(base_ids)
        assert diag['readahead_hits'] > 0

    def test_auto_depth_reader(self, synthetic_dataset):
        ra_ids, diag = _reader_ids(synthetic_dataset.url,
                                   reader_pool_type='thread', workers_count=2,
                                   io_readahead='auto')
        assert len(ra_ids) == len(synthetic_dataset.data)
        assert diag['readahead_hits'] > 0
        assert 0.0 <= diag['io_overlap_fraction'] <= 1.0

    def test_process_pool_counters_ship_back(self, synthetic_dataset):
        with make_columnar_reader(synthetic_dataset.url,
                                  reader_pool_type='process', workers_count=2,
                                  num_epochs=1, io_readahead=2) as reader:
            count = sum(1 for _ in reader)
            diag = reader.diagnostics
        assert count > 0
        # the counters were accumulated in worker interpreters and shipped
        # back via the accounting control messages
        assert diag['readahead_hits'] > 0
        assert diag['readahead_io_s'] > 0

    def test_batch_reader_readahead(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, reader_pool_type='thread',
                               workers_count=1, shuffle_row_groups=False,
                               num_epochs=1, io_readahead=2) as reader:
            ids = np.concatenate([batch.id for batch in reader])
            diag = reader.diagnostics
        assert len(ids) == len(scalar_dataset.data)
        assert diag['readahead_hits'] > 0

    def test_predicate_items_bypass_prefetch(self, synthetic_dataset):
        from petastorm_tpu.predicates import in_lambda
        predicate = in_lambda(['id'], lambda v: v['id'] % 2 == 0)
        with make_reader(synthetic_dataset.url, reader_pool_type='thread',
                         workers_count=1, shuffle_row_groups=False,
                         num_epochs=1, predicate=predicate,
                         io_readahead=2) as reader:
            ids = sorted(row.id for row in reader)
            diag = reader.diagnostics
        expected = sorted(r['id'] for r in synthetic_dataset.data
                          if r['id'] % 2 == 0)
        assert ids == expected
        # predicate reads are multi-phase and unplannable: nothing prefetched
        assert diag['readahead_hits'] == 0

    def test_ngram_chunk_path_readahead(self, tmp_path):
        # the synthetic fixture's row groups hold ~1 row (no windows fit);
        # write a store with multi-row groups so the chunk path emits windows
        from petastorm_tpu.ngram import NGram
        from petastorm_tpu.test_util.dataset_gen import create_test_dataset
        url = 'file://' + str(tmp_path / 'ngram_ra')
        create_test_dataset(url, range(24), num_files=2,
                            row_group_size_mb=0.5)
        fields = {
            0: ['id', 'id2'],
            1: ['id'],
        }
        ngram = NGram(fields, delta_threshold=10, timestamp_field='id')
        with make_reader(url, schema_fields=ngram,
                         reader_pool_type='thread', workers_count=1,
                         shuffle_row_groups=False, num_epochs=1,
                         io_readahead=2) as reader:
            windows = list(reader)
            diag = reader.diagnostics
        assert windows
        assert diag['readahead_hits'] > 0
        assert diag['readahead_misses'] == 0

    def test_shuffle_row_drop_partitions_readahead(self, synthetic_dataset):
        base_ids, _ = _reader_ids(synthetic_dataset.url,
                                  reader_pool_type='thread', workers_count=1,
                                  shuffle_row_drop_partitions=2)
        ra_ids, diag = _reader_ids(synthetic_dataset.url,
                                   reader_pool_type='thread', workers_count=1,
                                   shuffle_row_drop_partitions=2,
                                   io_readahead=2)
        assert sorted(ra_ids) == sorted(base_ids)
        assert diag['readahead_hits'] > 0

    def test_dummy_pool_disables_readahead(self, synthetic_dataset):
        """DummyPool never hints workers: the reader must force readahead off
        rather than record every read as a misleading miss."""
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         num_epochs=1, io_readahead=4) as reader:
            count = sum(1 for _ in reader)
            diag = reader.diagnostics
        assert count == len(synthetic_dataset.data)
        assert diag['readahead_hits'] == 0
        assert diag['readahead_misses'] == 0
        assert diag['readahead_io_s'] == 0.0

    def test_readahead_rejects_bad_values(self, synthetic_dataset):
        with pytest.raises(ValueError, match='io_readahead'):
            make_reader(synthetic_dataset.url, io_readahead=-1)
        with pytest.raises(ValueError, match='io_readahead'):
            make_reader(synthetic_dataset.url, io_readahead='fast')


class TestCacheKeyMemoization:
    def test_cache_key_format_and_reuse(self, synthetic_dataset, monkeypatch):
        """The dataset-path md5 and decode-hints hash are per-worker
        constants: computed in __init__, never per piece."""
        import hashlib

        from petastorm_tpu.cache import NullCache
        from petastorm_tpu.etl.dataset_metadata import (infer_or_load_unischema,
                                                        load_row_groups)
        from petastorm_tpu.fs import get_filesystem_and_path_or_paths
        from petastorm_tpu.readers.columnar_worker import ColumnarWorker

        fs, path, factory = get_filesystem_and_path_or_paths(
            synthetic_dataset.url)
        schema, _ = infer_or_load_unischema(fs, path)
        pieces = load_row_groups(fs, path)
        worker_args = {
            'filesystem_factory': factory, 'dataset_path': path,
            'schema': schema, 'full_schema': schema, 'ngram': None,
            'split_pieces': pieces, 'local_cache': NullCache(),
            'transform_spec': None, 'transformed_schema': schema,
            'decode_hints': {'image_png': {'scale': 2}},
        }
        worker = ColumnarWorker(0, lambda item: None, worker_args)
        try:
            expected_path_digest = hashlib.md5(str(path).encode()).hexdigest()
            key = worker._cache_key('columnar', pieces[0])
            assert key.startswith('columnar:' + expected_path_digest + ':')
            assert key == worker._cache_key('columnar', pieces[0])
            assert worker._decode_hints_digest in key

            # per-piece keying must not re-hash: md5 is forbidden after init
            def boom(*a, **k):
                raise AssertionError('md5 recomputed per piece')
            monkeypatch.setattr(hashlib, 'md5', boom)
            worker._cache_key('columnar', pieces[-1])
        finally:
            worker.shutdown()


class TestInfeedDiagnosis:
    def test_io_bound_signature(self):
        from petastorm_tpu.jax_utils import infeed_diagnosis
        diag = infeed_diagnosis({'worker_io_s': 9.0, 'worker_decode_s': 3.0})
        assert diag['bottleneck'] == 'io'
        assert diag['recommended_io_readahead'] == 3

    def test_decode_bound_signature(self):
        from petastorm_tpu.jax_utils import infeed_diagnosis
        diag = infeed_diagnosis({'worker_io_s': 1.0, 'worker_decode_s': 8.0})
        assert diag['bottleneck'] == 'decode'
        assert diag['recommended_io_readahead'] == 1

    def test_readahead_aware_io_accounting(self):
        from petastorm_tpu.jax_utils import infeed_diagnosis
        # hidden background reads count as io; the double-counted blocked
        # wait is removed from the stall side
        diag = infeed_diagnosis({'worker_io_s': 2.0, 'readahead_io_s': 6.0,
                                 'readahead_wait_s': 2.0,
                                 'worker_decode_s': 6.0})
        assert diag['io_s'] == pytest.approx(6.0)
        assert diag['bottleneck'] == 'balanced'

    def test_consumer_bound_signature(self):
        from petastorm_tpu.jax_utils import infeed_diagnosis
        diag = infeed_diagnosis({'worker_io_s': 0.5, 'worker_decode_s': 0.5,
                                 'worker_publish_wait_s': 9.0})
        assert diag['bottleneck'] == 'consumer'


def test_recommend_io_readahead_bounds():
    from petastorm_tpu.workers.stats import recommend_io_readahead
    assert recommend_io_readahead({}) == 1
    assert recommend_io_readahead({'worker_io_s': 100.0,
                                   'worker_decode_s': 1.0}) == 8
    assert recommend_io_readahead(
        {'worker_io_s': 3.1, 'worker_decode_s': 1.0}) == 4


def test_fixed_latency_storage_keeps_rows_and_counts_reads(
        synthetic_dataset):
    """Slow storage (faultfs' ``fixed-latency`` scenario: every ``read()``
    sleeps, nothing fails) under readahead yields the plain reader's rows in
    the plain reader's order, and the wrapper counts the reads it slowed."""
    import fsspec

    from petastorm_tpu.cache import NullCache
    from petastorm_tpu.faultfs import FaultInjector, FaultyFilesystem
    from petastorm_tpu.reader import Reader
    from petastorm_tpu.readers.columnar_worker import (ColumnarResultsReader,
                                                       ColumnarWorker)
    from petastorm_tpu.workers.thread_pool import ThreadPool

    with make_columnar_reader(synthetic_dataset.url, schema_fields=['id'],
                              reader_pool_type='thread', workers_count=1,
                              shuffle_row_groups=False,
                              num_epochs=1) as reader:
        expected = [list(batch.id) for batch in reader]
    slow = FaultyFilesystem(fsspec.filesystem('file'), FaultInjector(
        'fixed-latency', seconds_per_read=0.002))
    reader = Reader(lambda: slow, synthetic_dataset.path,
                    schema_fields=['id'], worker_class=ColumnarWorker,
                    results_reader_factory=ColumnarResultsReader,
                    shuffle_row_groups=False, num_epochs=1,
                    cache=NullCache(), pool=ThreadPool(1, 50),
                    is_batched_reader=True, io_readahead=2)
    reads_at_open = slow.read_calls
    try:
        got = [list(batch.id) for batch in reader]
        diag = reader.diagnostics
    finally:
        reader.stop()
        reader.join()
    assert got == expected
    assert slow.read_calls > reads_at_open
    assert slow.bytes_read > 0
    assert diag['readahead_hits'] > 0
