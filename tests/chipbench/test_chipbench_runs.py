"""Whole runs of the benchmark's cells at tiny sizes on the CPU: the same
harness, drivers, references and checks as on the chip, so a rehearsal
finds wrong paths without chip time. No number here is a measurement."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny
from chipbench import harness
from chipbench.drivers import seeded

ROOT = harness.ROOT
SEED = 2 ** 31 + 17       # above 32 signed bits, as the driver's seeds are


@pytest.fixture(scope='module')
def bench():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return chipbench_tiny.manifest(json.load(f))


@pytest.fixture(scope='module')
def tiny(tmp_path_factory):
    base = tmp_path_factory.mktemp('tiny')
    return chipbench_tiny.write(base, harness.BENCH_DIR), str(base / 'stores')


@pytest.mark.parametrize('workload', ['imagenet_jpeg_resnet18.decode',
                                      'pile_pythia160m.dp4'])
def test_cell_runs_correct_and_writes_no_device_metric(bench, tiny, workload):
    search, stores = tiny
    result = harness.run(bench, workload, SEED, 0.5, False, search, stores)
    assert result['correct'], result['checks']
    assert list(result)[-1] == 'checks'
    assert result['metrics'] == {}                 # a CPU rehearsal
    assert result['rehearsal']['platform'] == 'cpu'
    assert result['device']['count'] == (4 if 'dp4' in workload else 1)
    assert result['attempted'] > 0 and result['failed'] == 0
    assert all(set(v) == {'value', 'limit'} for v in result['checks'].values())


def test_same_seed_same_store_and_weights(bench, tiny):
    search, stores = tiny
    lookup = harness.Lookup(search)
    cfg = lookup.json('configs', 'imagenet_jpeg_resnet18')
    driver = lookup.module('drivers', 'image_cnn')
    ref = lookup.module('drivers', 'image_cnn_ref')
    a = driver.synthetic_row(cfg, SEED, 5)
    b = driver.synthetic_row(cfg, SEED, 5)
    c = driver.synthetic_row(cfg, SEED + 1, 5)
    assert (a['image'] == b['image']).all()
    assert a['image'].shape != c['image'].shape or \
        (a['image'] != c['image']).any()
    path = harness.store_path(stores, 'imagenet_jpeg_resnet18', cfg, SEED,
                              driver, ref)
    assert path == harness.store_path(stores, 'imagenet_jpeg_resnet18', cfg,
                                      SEED, driver, ref)
    assert seeded.crop_box(SEED, 3, 300, 400) == \
        seeded.crop_box(SEED, 3, 300, 400)
    # the seeded helpers both drivers draw from are part of the store's key
    assert os.path.samefile(harness.SEEDED, seeded.__file__)


def test_a_new_traffic_mix_and_metric_need_only_new_files(bench, tiny,
                                                          tmp_path):
    """A later PR adds a cell and a per-layer metric with data files, a
    reader file and BENCHMARK.json entries: no edit of what is there."""
    search, stores = tiny
    extra = tmp_path / 'later_pr'
    (extra / 'traffic').mkdir(parents=True)
    (extra / 'metrics').mkdir()
    (extra / 'limits').mkdir()
    with open(os.path.join(search[0], 'traffic', 'decode.json')) as f:
        mix = json.load(f)
    mix['prefetch'] = 1
    (extra / 'traffic' / 'shallow.json').write_text(json.dumps(mix))
    with open(os.path.join(search[0], 'limits',
                           'imagenet_jpeg_resnet18.decode.json')) as f:
        (extra / 'limits' / 'imagenet_jpeg_resnet18.shallow.json').write_text(
            f.read())
    (extra / 'metrics' / 'throwaway.steps.py').write_text(
        'def read(run):\n    return float(run.steps)\n')
    later = json.loads(json.dumps(bench))
    later['workloads'].append({
        'name': 'imagenet_jpeg_resnet18.shallow',
        'config': 'imagenet_jpeg_resnet18', 'traffic': 'shallow', 'chips': 1,
        'why': 'a throw-away cell of a test'})
    next(m for m in later['end_to_end'] if m['name'] == 'samples_per_s')[
        'workloads'].append('imagenet_jpeg_resnet18.shallow')
    later['per_layer'].append({
        'name': 'throwaway.steps', 'unit': 'steps', 'better': 'higher',
        'source': 'host_clock', 'layer': 'loader and staging',
        'moves': 'samples_per_s',
        'workloads': ['imagenet_jpeg_resnet18.shallow']})
    result = harness.run(later, 'imagenet_jpeg_resnet18.shallow', SEED, 0.3,
                         False, [str(extra)] + search, stores)
    assert result['correct'], result['checks']
    lookup = harness.Lookup([str(extra)] + search)
    run = harness.Run()
    run.steps = 7
    assert lookup.module('metrics', 'throwaway.steps').read(run) == 7.0


def test_refuses_without_a_tpu_unless_cpu_is_explicit(monkeypatch, capsys):
    spec = importlib.util.spec_from_file_location(
        'chipbench_cli', os.path.join(ROOT, 'chipbench', 'run.py'))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    monkeypatch.setenv('JAX_PLATFORMS', '')
    code = cli.main(['--workload', 'pile_pythia160m.dp4',
                     '--seed', '1', '--seconds', '1', '--trace', '0'])
    out = capsys.readouterr()
    assert code != 0
    assert out.out == ''
    assert 'no TPU' in out.err


def test_fails_in_a_checkout_of_only_the_benchmark(tmp_path):
    """Where only BENCHMARK.json and the files under paths exist, the
    program is missing: a non-zero exit and no result."""
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        paths = json.load(f)['paths']
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns('.stores', '.trace',
                                                      '__pycache__'))
    env = {k: v for k, v in os.environ.items() if k != 'PYTHONPATH'}
    env['JAX_PLATFORMS'] = 'cpu'
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    proc = subprocess.run(
        [sys.executable, 'chipbench/run.py', '--workload',
         'pile_pythia160m.dp4', '--seed', '1', '--seconds', '1',
         '--trace', '0'], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ''
    assert "No module named 'petastorm_tpu'" in proc.stderr
