"""Faults planted in the timed path, to show that ``correct`` can fail.

Used by ``calibrate.py`` on the chip and by the tests on the CPU; the
benchmark's own runs never plant one. Each wraps a driver (or the batch
stream) and leaves everything else of the run as it is:

- ``state_unchanged``: the step returns the state it was given;
- ``half_batch``: the step sees the first half of the batch, its mean over
  that half;
- ``no_exchange``: every chip steps on the first chip's rows, as a chip
  whose gradient is never averaged with the others' would (many chips);
- ``altered_value``: one value of every batch is changed where the loader
  hands it over (its lowest bit flipped);
- ``control``: the cell's control in the program's place, the plain
  reference's step computed one precision below the configuration's
  (``precision.CONTROL``), where the reference has a ``control_step``.
"""

import types

FAULTS = ('state_unchanged', 'half_batch', 'no_exchange', 'altered_value',
          'control')


def broken(driver, fault, ref=None):
    """A copy of ``driver`` whose ``make_step`` builds the broken step
    (``ref``, the driver's reference, for the ``control``)."""
    if fault not in FAULTS:
        raise ValueError('unknown fault {!r}'.format(fault))
    if fault == 'control' and not hasattr(ref, 'control_step'):
        raise ValueError('the reference has no control_step to put in the '
                         "program's place")
    copy = types.SimpleNamespace(**{k: getattr(driver, k) for k in dir(driver)
                                    if not k.startswith('__')})
    copy.__file__ = driver.__file__
    if fault == 'altered_value':
        return copy

    def make_step(cfg, mesh, batch):
        import jax
        import jax.numpy as jnp

        from chipbench import precision
        step, shapes = driver.make_step(cfg, mesh, batch)
        chips = mesh.devices.size
        if fault == 'control':
            control = ref.control_step(cfg, precision.CONTROL)

            def chipbench_train_step(state, *args):
                return control(state, *args)

            # the state comes back placed as it went in, as the program's
            # step leaves it, so the step compiled for it runs again
            placed = jax.sharding.NamedSharding(mesh,
                                                jax.sharding.PartitionSpec())
            return jax.jit(chipbench_train_step, donate_argnums=0,
                           out_shardings=placed), shapes

        def chipbench_train_step(state, *args):
            if fault == 'state_unchanged':
                return state, step(state, *args)[1]
            if fault == 'half_batch':
                return step(state, *(a[:len(a) // 2] for a in args))
            local = len(args[0]) // chips
            return step(state, *(jnp.concatenate([a[:local]] * chips)
                                 for a in args))

        return jax.jit(chipbench_train_step), shapes

    copy.make_step = make_step
    return copy


def broken_feed(batches, fault):
    """The batch stream, with one value of each batch's checked columns
    altered under ``altered_value``."""
    if fault != 'altered_value':
        yield from batches
        return
    import jax

    @jax.jit
    def alter(x):
        first = (0,) * x.ndim
        return x.at[first].set(x[first] ^ 1)

    for batch in batches:
        batch = dict(batch)
        for name in ('image', 'tokens'):
            if name in batch:
                batch[name] = alter(batch[name])
        yield batch
