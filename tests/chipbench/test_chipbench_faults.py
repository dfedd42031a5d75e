"""``correct`` can fail: each fault a cell can have, planted under the
timed path of a whole run, and the cell's control in the program's place,
come out not correct. Tiny sizes on the CPU, with the tiny limits of
``chipbench_tiny`` (the cells' own limits are set from chip readings)."""

import json
import os

import pytest

import chipbench_tiny
from chipbench import calibrate, harness, precision

SEED = 2 ** 31 + 29
CELL_FAULTS = [
    ('imagenet_jpeg_resnet18.decode', 'state_unchanged'),
    ('imagenet_jpeg_resnet18.decode', 'half_batch'),
    ('imagenet_jpeg_resnet18.decode', 'altered_value'),
    ('imagenet_jpeg_resnet18.decode', 'control'),
    ('pile_pythia160m.dp4', 'state_unchanged'),
    ('pile_pythia160m.dp4', 'half_batch'),
    ('pile_pythia160m.dp4', 'no_exchange'),
    ('pile_pythia160m.dp4', 'altered_value'),
    ('pile_pythia160m.dp4', 'control'),
]
#: Which numbers each fault has to trip (at least one of them).
TRIPS = {'state_unchanged': {'change_gap', 'grad_gap'},
         'half_batch': {'loss_gap', 'grad_gap', 'change_gap'},
         'no_exchange': {'loss_gap', 'grad_gap', 'change_gap'},
         'altered_value': {'rows_mismatched'},
         'control': {'loss_gap', 'grad_gap', 'change_gap'}}


@pytest.fixture(scope='module')
def bench():
    with open(os.path.join(harness.ROOT, 'BENCHMARK.json')) as f:
        return chipbench_tiny.manifest(json.load(f))


@pytest.fixture(scope='module')
def tiny(tmp_path_factory):
    base = tmp_path_factory.mktemp('tiny')
    return chipbench_tiny.write(base, harness.BENCH_DIR), str(base / 'stores')


@pytest.mark.parametrize('workload, fault', CELL_FAULTS)
def test_planted_fault_is_not_correct(bench, tiny, workload, fault):
    search, stores = tiny
    result = harness.run(bench, workload, SEED, 0.3, False, search, stores,
                         fault=fault)
    assert result['correct'] is False
    tripped = {k for k, v in result['checks'].items()
               if v['value'] > v['limit']}
    assert tripped & TRIPS[fault], result['checks']


@pytest.mark.parametrize('workload', ['imagenet_jpeg_resnet18.decode',
                                      'pile_pythia160m.dp4'])
def test_control_misses_the_limits(bench, tiny, workload):
    search, stores = tiny
    readings = calibrate.control_numbers(bench, workload, SEED, search, stores,
                                         ['half_batch'])
    limits = chipbench_tiny.LIMITS[workload]
    for kind in ('control_' + precision.CONTROL, 'reference_half_batch'):
        assert any(readings[kind][k] > limits[k] for k in limits), readings


def test_reference_against_itself_reads_zero(bench, tiny):
    """The comparison's arithmetic: identical training reads 0 gaps."""
    from chipbench import checks
    search, stores = tiny
    lookup = harness.Lookup(search)
    cfg = lookup.json('configs', 'pile_pythia160m')
    ref = lookup.module('drivers', 'transformer_lm_ref')
    driver = lookup.module('drivers', 'transformer_lm')
    path = harness.store_path(stores, 'pile_pythia160m', cfg, SEED, driver,
                              ref)
    harness.ensure_store(path, cfg, SEED, driver)
    source = ref.RowSource(cfg, path, SEED)
    batches = [{'row_id': list(range(i * 8, i * 8 + 8))} for i in range(3)]
    run = checks.reference_run(cfg, ref, source, batches, SEED)
    assert checks.training_numbers(run, run) == {
        'loss_gap': 0.0, 'grad_gap': 0.0, 'grad_gap_median': 0.0,
        'change_gap': 0.0, 'change_gap_median': 0.0}


@pytest.mark.parametrize('workload', ['imagenet_jpeg_resnet18.decode',
                                      'pile_pythia160m.dp4'])
def test_the_reference_in_the_programs_place_is_correct(bench, tiny,
                                                        monkeypatch,
                                                        workload):
    """The control is the reference's own step: at the reference's own
    precision in the program's place, every gap is round-off, so the
    control fails by its precision alone."""
    search, stores = tiny
    monkeypatch.setattr(precision, 'CONTROL', None)
    result = harness.run(bench, workload, SEED, 0.3, False, search, stores,
                         fault='control')
    assert result['correct'], result['checks']
    # SGD's gradient is read back as (p0 - p1) / lr from float32 weights,
    # so it keeps about 1e-4 of its size
    reads = {'loss_gap': 1e-5, 'grad_gap': 1e-3, 'change_gap': 1e-4}
    for name, most in reads.items():
        assert result['checks'][name]['value'] < most, result['checks']


def test_the_control_needs_a_reference_step():
    from chipbench import faults
    from chipbench.drivers import image_cnn
    with pytest.raises(ValueError, match='control_step'):
        faults.broken(image_cnn, 'control', object())
