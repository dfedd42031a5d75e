"""ReaderStats telemetry tests: every pool type must expose the full
per-stage key set through ``Reader.diagnostics``, with non-zero timings for
the stages its pipeline actually exercises, and the stages must sum sanely
against wall time."""

import time

import numpy as np
import pytest

from petastorm_tpu.jax_utils import JaxDataLoader, prefetch_to_device
from petastorm_tpu.reader import make_batch_reader, make_columnar_reader, make_reader
from petastorm_tpu.workers.stats import ReaderStats, stage_keys


class TestReaderStatsUnit:
    def test_snapshot_has_stable_key_set(self):
        snap = ReaderStats().snapshot()
        assert set(stage_keys()) <= set(snap)
        # window_s ticks from construction; every accumulated key starts at 0
        assert snap['window_s'] > 0
        assert all(v == 0 for k, v in snap.items() if k != 'window_s')

    def test_reset_zeroes_and_restarts_window(self):
        stats = ReaderStats()
        stats.add_time('worker_io_s', 2.0)
        stats.add('items_out', 10)
        stats.gauge('queue_depth', 5)
        time.sleep(0.02)
        before = stats.snapshot()
        assert before['items_per_s'] > 0
        stats.reset()
        snap = stats.snapshot()
        assert all(v == 0 for k, v in snap.items() if k != 'window_s')
        assert snap['window_s'] < before['window_s']
        assert snap['queue_depth_max'] == 0

    def test_snapshot_window_rates(self):
        """items_per_s / mb_per_s are rates over the window since
        construction/reset — the one derivation the metrics emitter and the
        CLI diagnostics output share."""
        stats = ReaderStats()
        stats.add('items_out', 100)
        stats.add('bytes_moved', 50 * 1024 * 1024)
        snap = stats.snapshot()
        # both rates divide by the same window captured in this snapshot
        assert snap['items_per_s'] == pytest.approx(100 / snap['window_s'])
        assert snap['mb_per_s'] == pytest.approx(50 / snap['window_s'])

    def test_accumulation_and_gauges(self):
        stats = ReaderStats()
        stats.add_time('worker_decode_s', 0.25)
        stats.add_time('worker_decode_s', 0.25)
        stats.add('bytes_moved', 100)
        stats.gauge('queue_depth', 7)
        stats.gauge('queue_depth', 3)
        snap = stats.snapshot()
        assert snap['worker_decode_s'] == pytest.approx(0.5)
        assert snap['bytes_moved'] == 100
        assert snap['queue_depth'] == 3          # last sample
        assert snap['queue_depth_max'] == 7      # high-water mark

    def test_batch_out_sums_the_wait_and_carries_the_ring_gauge(self):
        """The training loop's one stats update per batch: its wait, the
        batch count and, from a prefetcher, the ring's occupancy."""
        stats = ReaderStats()
        stats.note_batch_out(0.5, 3)
        stats.note_batch_out(0.25, 1)
        stats.note_batch_out(0.25)
        snap = stats.snapshot()
        assert snap['infeed_wait_s'] == pytest.approx(1.0)
        assert snap['batches_out'] == 3
        assert snap['prefetch_occupancy'] == 1   # last sample; None keeps it
        assert snap['prefetch_occupancy_max'] == 3

    def test_timed_context_and_merge(self):
        stats = ReaderStats()
        with stats.timed('deserialize_s'):
            time.sleep(0.01)
        stats.merge_times({'worker_io_s': 1.5, 'serialize_s': 0.5})
        snap = stats.snapshot()
        assert snap['deserialize_s'] > 0
        assert snap['worker_io_s'] == 1.5
        assert snap['serialize_s'] == 0.5

    def test_merge_counts_and_gauges(self):
        stats = ReaderStats()
        stats.merge_counts({'readahead_hits': 3, 'readahead_misses': 1})
        stats.merge_counts({'readahead_hits': 2})
        stats.merge_gauges({'readahead_depth': 4})
        stats.merge_gauges({'readahead_depth': 1})
        snap = stats.snapshot()
        assert snap['readahead_hits'] == 5
        assert snap['readahead_misses'] == 1
        assert snap['readahead_depth'] == 1
        assert snap['readahead_depth_max'] == 4

    def test_io_overlap_fraction_derivation(self):
        stats = ReaderStats()
        assert stats.snapshot()['io_overlap_fraction'] == 0.0
        stats.add_time('readahead_io_s', 4.0)
        stats.add_time('readahead_wait_s', 1.0)
        assert stats.snapshot()['io_overlap_fraction'] == pytest.approx(0.75)

    def test_snapshot_consistency_under_concurrent_updates(self):
        """Writers from many threads (the thread-pool shape: workers merging
        per-item times, the consumer adding counters, pools sampling gauges)
        must never corrupt a concurrent snapshot: every snapshot sees
        non-decreasing counters and the stable key set, and the final totals
        are exact — no update lost."""
        import threading

        stats = ReaderStats()
        writers = 6
        iterations = 300
        start_barrier = threading.Barrier(writers + 1)

        def writer(worker_id):
            start_barrier.wait()
            for i in range(iterations):
                stats.merge_times({'worker_io_s': 0.001,
                                   'worker_decode_s': 0.002})
                stats.add('items_out')
                stats.merge_counts({'readahead_hits': 1})
                stats.gauge('queue_depth', (worker_id * iterations + i) % 17)

        threads = [threading.Thread(target=writer, args=(w,))
                   for w in range(writers)]
        for t in threads:
            t.start()
        start_barrier.wait()
        last_items = 0
        snapshots = []
        while any(t.is_alive() for t in threads):
            snap = stats.snapshot()
            snapshots.append(snap)
            assert set(stage_keys()) <= set(snap)
            assert snap['items_out'] >= last_items       # monotonic counter
            last_items = snap['items_out']
            # a torn read would break the 1:2 io:decode invariant wildly;
            # both sides accumulate under one lock, but each merge applies
            # both stages atomically so the ratio can lag at most one update
            assert snap['worker_decode_s'] >= snap['worker_io_s']
        for t in threads:
            t.join()
        final = stats.snapshot()
        total = writers * iterations
        assert final['items_out'] == total
        assert final['readahead_hits'] == total
        assert final['worker_io_s'] == pytest.approx(0.001 * total)
        assert final['worker_decode_s'] == pytest.approx(0.002 * total)
        assert final['queue_depth_max'] == 16
        assert snapshots, 'no concurrent snapshot was taken'


def _consume_and_snapshot(reader):
    start = time.perf_counter()
    count = sum(1 for _ in reader)
    wall = time.perf_counter() - start
    return count, wall, reader.diagnostics


def _assert_sane(diag, wall, workers, expect_transport):
    """Keys exist, the exercised stages are non-zero, and no stage exceeds
    what ``workers`` parallel workers plus the consumer could have spent."""
    assert set(stage_keys()) <= set(diag)
    assert diag['worker_io_s'] > 0
    assert diag['worker_decode_s'] > 0
    assert diag['items_out'] > 0
    if expect_transport:
        assert diag['serialize_s'] > 0
        assert diag['deserialize_s'] > 0
        assert diag['bytes_moved'] > 0
    else:
        assert diag['serialize_s'] == 0
        assert diag['deserialize_s'] == 0
    budget = wall * (workers + 2)
    for stage in ('worker_io_s', 'worker_decode_s', 'serialize_s',
                  'deserialize_s', 'queue_wait_s', 'device_stage_s'):
        assert 0 <= diag[stage] <= budget, (stage, diag[stage], budget)


class TestPoolDiagnostics:
    def test_thread_pool_stages(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, reader_pool_type='thread',
                         workers_count=3, num_epochs=1) as reader:
            count, wall, diag = _consume_and_snapshot(reader)
        assert count == len(synthetic_dataset.data)
        _assert_sane(diag, wall, workers=3, expect_transport=False)
        assert diag['queue_wait_s'] > 0       # consumer polled the queue

    def test_process_pool_stages(self, synthetic_dataset):
        with make_columnar_reader(synthetic_dataset.url,
                                  reader_pool_type='process',
                                  workers_count=2, num_epochs=1) as reader:
            count, wall, diag = _consume_and_snapshot(reader)
        assert count > 0
        _assert_sane(diag, wall, workers=2, expect_transport=True)
        # zero-copy transport: decoded image columns ship as out-of-band
        # frames, so no full-payload memcpys anywhere on the path
        assert diag['payload_copies'] == 0
        assert diag['payload_frames'] > 0

    def test_batch_reader_process_pool_arrow_transport(self, scalar_dataset):
        with make_batch_reader(scalar_dataset.url, reader_pool_type='process',
                               workers_count=2, num_epochs=1) as reader:
            count, wall, diag = _consume_and_snapshot(reader)
        assert count > 0
        _assert_sane(diag, wall, workers=2, expect_transport=True)

    @pytest.mark.parametrize('pool_type,workers', [('thread', 3),
                                                   ('process', 2)])
    def test_snapshot_consistent_while_pool_runs(self, synthetic_dataset,
                                                 pool_type, workers):
        """Snapshots taken concurrently with live pool updates (worker
        threads / accounting messages from worker processes) must always
        carry the stable key set and monotonic counters."""
        import threading

        seen = {'count': 0}
        failures = []

        def sampler(reader, stop_event):
            last_items = 0
            while not stop_event.is_set():
                snap = reader.stats.snapshot()
                seen['count'] += 1
                if not set(stage_keys()) <= set(snap):
                    failures.append('missing keys: {}'.format(
                        set(stage_keys()) - set(snap)))
                    return
                if snap['items_out'] < last_items:
                    failures.append('items_out went backwards')
                    return
                last_items = snap['items_out']

        with make_columnar_reader(synthetic_dataset.url,
                                  reader_pool_type=pool_type,
                                  workers_count=workers, num_epochs=2,
                                  io_readahead=2) as reader:
            stop_event = threading.Event()
            thread = threading.Thread(target=sampler,
                                      args=(reader, stop_event))
            thread.start()
            count = sum(1 for _ in reader)
            stop_event.set()
            thread.join(timeout=10)
        assert count > 0
        assert seen['count'] > 0
        assert not failures, failures

    def test_dummy_pool_stages(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         num_epochs=1) as reader:
            count, wall, diag = _consume_and_snapshot(reader)
        assert count == len(synthetic_dataset.data)
        assert set(stage_keys()) <= set(diag)
        assert diag['worker_io_s'] > 0
        assert diag['worker_decode_s'] > 0


class TestLoaderTelemetry:
    def test_device_staging_time_recorded(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, reader_pool_type='thread',
                         workers_count=2, num_epochs=1,
                         schema_fields=['^id$', '^image_png$']) as reader:
            loader = JaxDataLoader(reader, batch_size=16,
                                   shuffling_queue_capacity=32)
            batches = list(prefetch_to_device(loader, stats=reader.stats))
            diag = reader.diagnostics
        assert batches
        assert diag['device_stage_s'] > 0
        assert diag['shuffle_buffer_depth_max'] > 0

    def test_loader_exposes_reader_stats(self, synthetic_dataset):
        with make_reader(synthetic_dataset.url, reader_pool_type='dummy',
                         num_epochs=1) as reader:
            loader = JaxDataLoader(reader, batch_size=8)
            assert loader.stats is reader.stats
            for batch in loader:
                assert isinstance(batch['id'], np.ndarray)
