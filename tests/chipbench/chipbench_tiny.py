"""Tiny stand-ins for the benchmark's configurations, traffic and limits,
written into a search directory that the harness reads before
``chipbench/``: the same drivers, references, checks and cells at sizes a
CPU test can run (not a measurement of anything)."""

import json
import os

CONFIGS = {
    'imagenet_jpeg_resnet18': {
        'driver': 'image_cnn', 'rows': 96, 'source_hw': [72, 96],
        'row_group_mb': 0.05, 'image_size': 64, 'num_classes': 10,
        'widths': [8, 16, 16, 16], 'blocks_per_stage': 2,
        'compute_dtype': 'bfloat16', 'batch_per_chip': 16, 'lr': 0.001,
        'ref_block_rows': 4, 'check_batches': 2},
    'pile_pythia160m': {
        'driver': 'transformer_lm', 'rows': 64, 'row_group_mb': 0.01,
        'vocab_size': 256, 'd_model': 64, 'n_heads': 4, 'n_layers': 2,
        'd_ff': 128, 'seq_len': 64, 'attention': 'flash',
        'compute_dtype': 'bfloat16', 'batch_per_chip': 2, 'lr': 0.0006,
        'adam_b1': 0.9, 'adam_b2': 0.95, 'adam_eps': 1e-8,
        'weight_decay': 0.01, 'ref_block_rows': 4, 'check_batches': 0},
}

#: Limits for these sizes on the CPU, set between the sound readings and
#: the fp8 control over seeds 100-103 and the tests' seed, as the cells'
#: own limits are on the chip.
LIMITS = {
    'imagenet_jpeg_resnet18.decode': {'loss_gap': 0.01, 'grad_gap': 0.1,
                                      'change_gap': 0.3},
    'pile_pythia160m.dp4': {'loss_gap': 0.0004, 'grad_gap': 0.005,
                            'change_gap': 0.004},
}
#: The decode cell, out of BENCHMARK.json until its check can fail its
#: control on the chip (PERF.md); its driver and reference are still run
#: here.
STANDBY = {
    'config': {"name": "imagenet_jpeg_resnet18", "source": "https://arxiv.org/abs/1512.03385", "file": "chipbench/configs/imagenet_jpeg_resnet18.json", "reduced": ["rows"], "why": "ILSVRC-2012-shaped JPEGs decoded and cropped on the host into ResNet-18: the decode-bound input path"},
    'workload': {"name": "imagenet_jpeg_resnet18.decode", "config": "imagenet_jpeg_resnet18", "traffic": "decode", "chips": 1, "why": "batch 128 at 224px, closed loop; every sample pays host JPEG decode and crop on a thread pool, no cache: reader and worker pool bound"},
}
EXACT = {'rows_mismatched': 0, 'row_ids_invalid': 0, 'losses_nonfinite': 0}


def manifest(bench):
    """``bench`` with the standby decode cell added."""
    bench = json.loads(json.dumps(bench))
    bench['configs'].append(dict(STANDBY['config']))
    bench['workloads'].append(dict(STANDBY['workload']))
    return bench


def write(base, bench_dir):
    """Fills ``base`` with the tiny configurations, the cells' traffic with
    4 warm-up steps, and the tiny limits; returns the search path."""
    for kind in ('configs', 'traffic', 'limits'):
        os.makedirs(os.path.join(base, kind), exist_ok=True)
    for name, cfg in CONFIGS.items():
        with open(os.path.join(base, 'configs', name + '.json'), 'w') as f:
            json.dump(cfg, f)
    for mix in ('decode', 'dp4'):
        with open(os.path.join(bench_dir, 'traffic', mix + '.json')) as f:
            traffic = json.load(f)
        traffic['warmup_steps'] = 4
        with open(os.path.join(base, 'traffic', mix + '.json'), 'w') as f:
            json.dump(traffic, f)
    for cell, limits in LIMITS.items():
        with open(os.path.join(base, 'limits', cell + '.json'), 'w') as f:
            json.dump({'limits': dict(EXACT, **limits)}, f)
    return [str(base), bench_dir]
