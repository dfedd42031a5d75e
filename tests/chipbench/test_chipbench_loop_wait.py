"""``loader.loop_wait_ms``, the per-layer metric that reads the program's
own record of the training loop's wait: its reader on hand-built runs, and
a tiny ``dp4`` run on the CPU whose loop boundary the program records where
the harness's loop waits."""

import json
import os

import pytest

import chipbench_tiny
from chipbench import harness

METRIC = 'loader.loop_wait_ms'


def _run(**attrs):
    run = harness.Run()
    run.__dict__.update(dict(stats_delta={}, fetched=0, global_batch=16,
                             trace=None), **attrs)
    return run


def _reader():
    return harness.Lookup([harness.BENCH_DIR]).module('metrics', METRIC)


@pytest.mark.parametrize('delta,expected', [
    ({'infeed_wait_s': 0.02, 'batches_out': 40}, 0.5),
    ({'infeed_wait_s': 0.0, 'batches_out': 7}, 0.0),
    ({'infeed_wait_s': 3.0, 'batches_out': 1000, 'items_out': 9}, 3.0),
], ids=['mean', 'no_wait', 'other_keys'])
def test_reader_reads_its_source(delta, expected):
    assert _reader().read(_run(stats_delta=delta)) == pytest.approx(expected)


@pytest.mark.parametrize('delta', [
    {},
    {'worker_decode_s': 1.0, 'items_out': 9},
    {'infeed_wait_s': 0.0, 'batches_out': 0},
], ids=['no_stats', 'parent_keys', 'no_batches'])
def test_reader_is_silent_without_its_source(delta):
    """A program without these counters (the parent of the change that
    brought them) yields nothing, and no error."""
    assert _reader().read(_run(stats_delta=delta, fetched=50)) is None


def test_tiny_dp4_records_the_loop_boundary_where_the_harness_waits(
        tmp_path, monkeypatch):
    """The harness's own feed (``prefetch_to_device(iter(loader))``): the
    program counts every batch the window drew, on the loop's side, and its
    wait lies inside the benchmark's span around ``next``."""
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    search = chipbench_tiny.write(tmp_path, harness.BENCH_DIR)
    captured = {}
    per_layer = harness._per_layer

    def capture(entries, workload, lookup, run_):
        captured['run'] = run_
        return per_layer(entries, workload, lookup, run_)

    monkeypatch.setattr(harness, '_per_layer', capture)
    result = harness.run(bench, 'pile_pythia160m.dp4', 2 ** 31 + 23, 0.5,
                         True, search, str(tmp_path / 'stores'))
    assert result['correct'], result['checks']
    run = captured['run']
    assert run.stats_delta['batches_out'] == run.fetched > 0
    lookup = harness.Lookup(search)
    loop_ms = lookup.module('metrics', METRIC).read(run)
    bench_ms = lookup.module('metrics', 'loader.infeed_wait_ms').read(run)
    assert 0 <= loop_ms <= bench_ms
