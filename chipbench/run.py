#!/usr/bin/env python3
"""Run one cell of the chip benchmark on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: every number ``correct`` was decided on
beside its limit, which are also the last lines of standard error.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for. A CPU rehearsal needs an explicit
``JAX_PLATFORMS=cpu``; its line is labelled ``cpu`` under ``rehearsal`` and
holds no device metric.
"""

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        manifest = json.load(f)
    from chipbench import harness
    chips = harness.find(manifest['workloads'], args.workload,
                         'workload')['chips']

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != 'tpu' and os.environ.get('JAX_PLATFORMS') != 'cpu':
        print('chipbench: no TPU found (JAX reports {!r}); a CPU rehearsal '
              'needs JAX_PLATFORMS=cpu'.format(platform), file=sys.stderr)
        return 2
    if len(devices) < chips:
        print('chipbench: {} needs {} chips, JAX has {}'.format(
            args.workload, chips, len(devices)), file=sys.stderr)
        return 2

    from petastorm_tpu.utils import configure_compile_cache
    harness.log('compile cache: {}'.format(configure_compile_cache(ROOT)))
    # every program of the cell, however quick to compile, comes back from
    # the cache in the next run
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)

    result = harness.run(
        manifest, args.workload, args.seed, args.seconds, bool(args.trace),
        search=[harness.BENCH_DIR],
        store_root=os.path.join(harness.BENCH_DIR, '.stores'),
        trace_dir=os.path.join(harness.BENCH_DIR, '.trace', args.workload),
        started=STARTED)
    print(json.dumps(result), flush=True)
    for name, check in result['checks'].items():
        print('{} {!r} limit {!r}'.format(name, check['value'],
                                          check['limit']),
              file=sys.stderr, flush=True)
    return 0


if __name__ == '__main__':
    # run as a script, sys.path[0] is chipbench/, whose trace.py would
    # shadow the standard library's; the checkout root holds both packages
    sys.path[0] = ROOT
    sys.exit(main())
