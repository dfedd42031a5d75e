"""Host milliseconds of the row-group packing transform per delivered
packed row: ``ReaderStats.worker_transform_s`` over the window, summed over
the workers, divided by the rows the loader delivered in it. ``None`` from
a program that keeps no such stage. Also logs the window's padding share
(``pack_pad_tokens`` over the packed slots), which is not a metric."""

import sys


def read(run):
    transform_s = run.stats_delta.get('worker_transform_s', 0.0)
    rows = run.fetched * run.global_batch
    pad = run.stats_delta.get('pack_pad_tokens', 0)
    slots = pad + run.stats_delta.get('pack_tokens', 0)
    if slots > 0:
        print('packing: padding share {!r} of {} slots packed in the '
              'window'.format(pad / slots, slots), file=sys.stderr, flush=True)
    if transform_s <= 0 or rows <= 0:
        return None
    return 1e3 * transform_s / rows
