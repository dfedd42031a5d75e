#!/usr/bin/env python3
"""The packed cells' own fault, beside ``chipbench/faults.py``'s:
``ignored_mask``, the program's step handed segment ids of all 1 (positions
kept), so attention crosses document boundaries and the loss weights lose
the boundaries and the padding.

    python3 chipbench/mask_fault.py <calibrate.py arguments>

runs ``calibrate.py`` with the fault known to the harness
(``--faults ignored_mask`` and any of ``faults.FAULTS``).
"""

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULT = 'ignored_mask'


def broken(driver, fault, ref=None, base=None):
    """``faults.broken`` with ``ignored_mask`` added: a copy of ``driver``
    whose step sees segment ids of all 1. Other faults go to ``base``
    (``faults.broken`` when not given)."""
    if fault != FAULT:
        if base is None:
            from chipbench import faults
            base = faults.broken
        return base(driver, fault, ref)
    copy = types.SimpleNamespace(**{k: getattr(driver, k) for k in dir(driver)
                                    if not k.startswith('__')})
    copy.__file__ = driver.__file__

    def make_step(cfg, mesh, batch):
        import jax
        import jax.numpy as jnp
        step, shapes = driver.make_step(cfg, mesh, batch)

        def chipbench_train_step(state, tokens, segment_ids, positions):
            return step(state, tokens, jnp.ones_like(segment_ids), positions)

        return jax.jit(chipbench_train_step), shapes

    copy.make_step = make_step
    return copy


def install():
    """Puts :func:`broken` in ``faults.broken``'s place for this process."""
    from chipbench import faults
    base = faults.broken
    faults.broken = lambda driver, fault, ref=None: broken(driver, fault, ref,
                                                           base)


if __name__ == '__main__':
    # run as a script, sys.path[0] is chipbench/, whose trace.py would
    # shadow the standard library's; the checkout root holds both packages
    sys.path[0] = ROOT
    from chipbench import calibrate
    install()
    sys.exit(calibrate.main())
