#!/usr/bin/env python3
"""Readings that the limits in ``limits/<workload>.json`` are set from, in
one process on the chips of this machine.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,... \
        [--control-seeds ...] [--faults state_unchanged,...] \
        [--fault-seeds ...] [--seconds 2] --out <file.jsonl>

- each ``--seeds`` seed: one run of the cell as the benchmark runs it, with
  a short window; every number its check computes, compared or not, is a
  sound reading of the program;
- each ``--fault-seeds`` seed and each of ``--faults``: one run with that
  fault planted in the timed path (``chipbench/faults.py``); the fault
  ``control`` is the control, the reference's step computed one precision
  below the configuration's bfloat16 (``chipbench/precision.py``) and put
  in the program's place;
- each ``--control-seeds`` seed: the control's readings without a run, the
  reference in that precision against the float32 one over three batches
  of rows drawn from the seed;
- each ``--control-seeds`` seed and each of ``--reference-faults``
  (``half_batch``, ``no_exchange``): the fault planted in the reference put
  in the program's place, against the sound reference, on the control's
  rows.

Every reading is one JSON line on stdout and in ``--out``.
"""

import argparse
import json
import os
import sys
import time

STARTED = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text):
    return [int(s) for s in text.split(',') if s]


def control_numbers(manifest, workload, seed, search, store_root,
                    faults=()):
    """The control's readings, the reference in the cell's lower precision
    against the f32 one, and each of ``faults`` planted in the f32
    reference, by name."""
    import numpy as np

    from chipbench import checks, harness, precision
    lookup = harness.Lookup(search)
    cell = harness.find(manifest['workloads'], workload, 'workload')
    cfg = lookup.json('configs', cell['config'])
    driver = lookup.module('drivers', cfg['driver'])
    ref = lookup.module('drivers', cfg['driver'] + '_ref')
    path = harness.store_path(store_root, cell['config'], cfg, seed, driver,
                              ref)
    facts, _ = harness.ensure_store(path, cfg, seed, driver)
    batch = cfg['batch_per_chip'] * cell['chips']
    order = np.random.default_rng([seed % (1 << 63), 11]).permutation(
        facts['rows'])
    batches = [{'row_id': order[i * batch:(i + 1) * batch]}
               for i in range(harness.CHECKED_STEPS)]
    source = ref.RowSource(cfg, path, seed)
    want = checks.reference_run(cfg, ref, source, batches, seed)
    out = {'control_' + precision.CONTROL: checks.training_numbers(
        checks.reference_run(cfg, ref, source, batches, seed,
                             precision.CONTROL), want)}
    local = batch // cell['chips']
    planted = {
        'half_batch': lambda ids: ids[:batch // 2],
        'no_exchange': lambda ids: np.concatenate([ids[:local]]
                                                  * cell['chips']),
    }
    for fault in faults:
        faulty = [{'row_id': planted[fault](b['row_id'])} for b in batches]
        out['reference_' + fault] = checks.training_numbers(
            checks.reference_run(cfg, ref, source, faulty, seed), want)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seeds', type=_seeds, default=[])
    parser.add_argument('--control-seeds', type=_seeds, default=[])
    parser.add_argument('--faults', default='')
    parser.add_argument('--fault-seeds', type=_seeds, default=[])
    parser.add_argument('--reference-faults', default='')
    parser.add_argument('--seconds', type=float, default=2.0)
    parser.add_argument('--out', required=True)
    args = parser.parse_args(argv)

    import jax

    from chipbench import harness
    from petastorm_tpu.utils import configure_compile_cache
    if jax.devices()[0].platform != 'tpu' and \
            os.environ.get('JAX_PLATFORMS') != 'cpu':
        print('calibrate: no TPU', file=sys.stderr)
        return 2
    configure_compile_cache(ROOT)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        manifest = json.load(f)
    search = [harness.BENCH_DIR]
    store_root = os.path.join(harness.BENCH_DIR, '.stores')
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    with open(args.out, 'a') as out:
        def emit(kind, seed, numbers, **extra):
            line = json.dumps(dict(workload=args.workload, kind=kind,
                                   seed=seed, numbers=numbers, **extra))
            print(line, flush=True)
            out.write(line + '\n')
            out.flush()

        runs = [(seed, None) for seed in args.seeds]
        runs += [(seed, fault) for fault in args.faults.split(',') if fault
                 for seed in args.fault_seeds]
        for seed, fault in runs:
            t = time.perf_counter()
            readings = {}
            result = harness.run(
                manifest, args.workload, seed, args.seconds, False, search,
                store_root, fault=fault, readings=readings)
            emit(fault or 'program', seed, readings,
                 correct=result['correct'], seconds=time.perf_counter() - t)
        for seed in args.control_seeds:
            t = time.perf_counter()
            readings = control_numbers(
                manifest, args.workload, seed, search, store_root,
                [f for f in args.reference_faults.split(',') if f])
            for kind, numbers in readings.items():
                emit(kind, seed, numbers, seconds=time.perf_counter() - t)
    return 0


if __name__ == '__main__':
    # run as a script, sys.path[0] is chipbench/, whose trace.py would
    # shadow the standard library's; the checkout root holds both packages
    sys.path[0] = ROOT
    sys.exit(main())
