"""The part of ``step.collective_ms`` during which no other op runs on the
device: collective time that compute does not hide, per train step."""


def read(run):
    if (run.trace is None or run.trace.collective_s <= 0
            or run.trace.program_runs <= 0):
        return None
    return 1e3 * run.trace.collective_exposed_s / run.trace.program_runs
