"""Percent of the traced window in which no op ran on the device: 1 minus
the union of ``XLA Ops`` intervals over the window, per device; the
largest over the cell's chips."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * max(run.trace.idle_share)
