"""BENCHMARK.json against the contract, the peak table, and the FLOP
counters against hand counts."""

import json
import os

import pytest

import chipbench_tiny
from chipbench import harness, manifest

ROOT = harness.ROOT


@pytest.fixture(scope='module')
def bench():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def test_manifest_keeps_the_contract(bench):
    assert manifest.errors(bench, ROOT) == []


@pytest.mark.parametrize('bad, field', [
    ({'name': 'has space'}, 'name'), ({'name': 'a/b'}, 'name'),
    ({'name': 'µs_metric'}, 'name'), ({'unit': 'tokens per s'}, 'unit'),
    ({'unit': 'µs'}, 'unit'), ({'better': 'up'}, 'better'),
    ({'source': 'guess'}, 'source'), ({'bound': 0.5}, 'bound'),
    ({'why': 'x'}, 'keys')])
def test_manifest_refuses_bad_names_and_units(bench, bad, field):
    broken = json.loads(json.dumps(bench))
    broken['end_to_end'][0].update(bad)
    found = manifest.errors(broken, ROOT)
    assert found and any(field in e or 'name' in e for e in found)


def test_manifest_refuses_a_metric_moving_what_its_cell_lacks(bench):
    broken = chipbench_tiny.manifest(bench)
    metric = next(m for m in broken['per_layer'] if m['name'] == 'step_mfu')
    metric['workloads'].append('imagenet_jpeg_resnet18.decode')
    assert any('does not report samples_per_s' in e
               for e in manifest.errors(broken, ROOT))


def test_every_part_a_cell_names_exists(bench):
    lookup = harness.Lookup([harness.BENCH_DIR])
    for cell in bench['workloads']:
        cfg = lookup.json('configs', cell['config'])
        lookup.json('traffic', cell['traffic'])
        limits = lookup.json('limits', cell['name'])['limits']
        assert set(limits) >= {'rows_mismatched', 'row_ids_invalid',
                               'losses_nonfinite'}
        lookup.module('drivers', cfg['driver'])
        lookup.module('drivers', cfg['driver'] + '_ref')
    for metric in bench['per_layer']:
        assert callable(lookup.module('metrics', metric['name']).read)


def test_run_seconds_fits_a_check_of_24_cells(bench):
    assert manifest.check_budget(bench['run_seconds'])
    assert not manifest.check_budget(52)


def test_peak_table_lookup_and_unknown_kind():
    v5e = harness.load_peaks('TPU v5 lite')
    assert v5e['bf16_flop_per_s'] == 197e12
    assert v5e['hbm_bytes_per_s'] == 819e9
    with pytest.raises(KeyError, match='no device_kind'):
        harness.load_peaks('TPU v9 imaginary')


def test_resnet18_flops_hand_count():
    lookup = harness.Lookup([harness.BENCH_DIR])
    cfg = lookup.json('configs', 'imagenet_jpeg_resnet18')
    ref = lookup.module('drivers', 'image_cnn_ref')
    # multiply-adds at 224 px: stem 112^2*7*7*3*64; stage 1 four 3x3 64->64
    # at 56^2; stages 2-4 a strided 3x3, three 3x3 and a 1x1 projection
    # each (411.0 M); head 512*1000
    stem = 112 * 112 * 7 * 7 * 3 * 64
    stage1 = 4 * 56 * 56 * 9 * 64 * 64
    later = sum(hw * hw * (9 * c * 2 * c + 3 * 9 * 2 * c * 2 * c + c * 2 * c)
                for hw, c in ((28, 64), (14, 128), (7, 256)))
    macs = stem + stage1 + later + 512 * 1000
    assert macs == 1_814_073_344          # ResNet-18's 1.8 GMAC
    forward = 2 * macs
    assert ref.train_flops(cfg, 1) == 3 * forward - 2 * stem
    assert round(ref.train_flops(cfg, 128) / 1e12, 2) == 1.36


def test_pythia160m_flops_hand_count():
    lookup = harness.Lookup([harness.BENCH_DIR])
    cfg = lookup.json('configs', 'pile_pythia160m')
    ref = lookup.module('drivers', 'transformer_lm_ref')
    block = 12 * (4 * 768 * 768 + 3 * 768 * 3072)       # 113.2 M
    head = 768 * 50304                                  # 38.6 M
    attention = 12 * 6 * 768 * 2049
    per_token = 6 * (block + head) + attention
    assert per_token == 1_024_579_584                   # ~1.02 GFLOP
    assert ref.train_flops(cfg, 4) == 4 * 2048 * per_token
    assert round(ref.train_flops(cfg, 4) / 1e12, 2) == 8.39


def test_workers_follow_the_example_rule():
    rule = {'min': 2, 'max': 8}
    assert [harness.workers_for(rule, n) for n in (1, 4, 13, 30)] == \
        [2, 4, 8, 8]
