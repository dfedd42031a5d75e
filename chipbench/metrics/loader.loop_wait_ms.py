"""Mean wait of the training loop for its next batch, as the program
records it where the loop waits: ``ReaderStats.infeed_wait_s`` over
``batches_out`` in the window (``jax_utils.LoopBoundary``; under the
prefetcher, on the ring's consumer side). ``None`` from a program that
keeps no such counter."""


def read(run):
    batches = run.stats_delta.get('batches_out', 0)
    if batches <= 0:
        return None
    return 1e3 * run.stats_delta.get('infeed_wait_s', 0.0) / batches
