#!/usr/bin/env python3
"""Record a small chip trace with the benchmark's spans, for the test of
``chipbench/trace.py`` (``tests/chipbench/``), and print its planes.

    python3 chipbench/record_fixture.py --out <path.xplane.pb> [--chips n]

A few steps of a tiny ``chipbench_train_step`` (a matmul, and across
``n`` chips an all-reduce), each behind an ``infeed_wait`` of about 2 ms of
host sleep, so the device idles in known gaps; the host record the
reduction reads is written beside it as ``<out>.host.json``.
"""

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument('--out', required=True)
    parser.add_argument('--chips', type=int, default=1)
    parser.add_argument('--steps', type=int, default=6)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from chipbench import trace

    devices = jax.devices()[:args.chips]
    mesh = Mesh(devices, ('data',))
    rows = NamedSharding(mesh, P('data'))

    def chipbench_train_step(w, x):
        y = jnp.tanh(x @ w)
        return w - 1e-3 * (x.T @ y) / x.shape[0], jnp.mean(y)

    step = jax.jit(chipbench_train_step, in_shardings=(NamedSharding(mesh, P()), rows),
                   out_shardings=(NamedSharding(mesh, P()), NamedSharding(mesh, P())))
    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16) / 512,
                       NamedSharding(mesh, P()))
    x = jax.device_put(jnp.ones((256 * args.chips, 512), jnp.bfloat16), rows)
    w, loss = step(w, x)
    loss.block_until_ready()

    tmp = os.path.join(ROOT, 'chipbench', '.trace', 'fixture')
    shutil.rmtree(tmp, ignore_errors=True)
    recorder = trace.Recorder(devices[0])
    recorder.start(tmp)
    clock = time.perf_counter_ns
    spans, pending = [], None
    t0 = clock()
    for _ in range(args.steps):
        a = clock()
        time.sleep(0.002)
        b = clock()
        w, loss = step(w, x)
        c = clock()
        spans += [['infeed_wait', a, b], ['dispatch', b, c]]
        if pending is not None:
            pending.block_until_ready()
            spans.append(['block', c, clock()])
        pending = loss
    pending.block_until_ready()
    t1 = clock()
    found = recorder.stop(tmp)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copy(found, args.out)
    shutil.rmtree(tmp, ignore_errors=True)
    host = {'window': [t0, t1], 'marks': recorder.marks, 'spans': spans}
    with open(args.out + '.host.json', 'w') as f:
        json.dump(host, f)
    print(trace.describe(args.out))
    print(trace.reduce(args.out, 'chipbench_train_step', host))
    return 0


if __name__ == '__main__':
    # run as a script, sys.path[0] is chipbench/, whose trace.py would
    # shadow the standard library's; the checkout root holds both packages
    sys.path[0] = ROOT
    sys.exit(main())
