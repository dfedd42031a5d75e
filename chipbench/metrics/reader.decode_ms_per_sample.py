"""Host CPU milliseconds of codec decode and transform per delivered sample:
``ReaderStats.worker_decode_s`` over the window, summed over the workers,
divided by the samples the loader delivered in it."""


def read(run):
    decode_s = run.stats_delta.get('worker_decode_s', 0.0)
    samples = run.fetched * run.global_batch
    if decode_s <= 0 or samples <= 0:
        return None
    return 1e3 * decode_s / samples
