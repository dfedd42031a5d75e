"""Device time of collective ops (all-reduce, all-gather, reduce-scatter,
collective-permute, all-to-all) per train step in the traced window,
averaged over the chips. Nothing to read on one chip."""


def read(run):
    if (run.trace is None or run.trace.collective_s <= 0
            or run.trace.program_runs <= 0):
        return None
    return 1e3 * run.trace.collective_s / run.trace.program_runs
