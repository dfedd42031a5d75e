"""Mean host-clock wait per step for the next staged batch: the benchmark's
``infeed_wait`` span around ``next(batches)``, over the window."""


def read(run):
    if not run.infeed_waits:
        return None
    return 1e3 * sum(run.infeed_waits) / len(run.infeed_waits)
