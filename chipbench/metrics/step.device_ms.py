"""Device time per run of the train-step program (``chipbench_train_step``)
in the traced window, from the ``XLA Modules`` line, averaged over the
cell's chips."""


def read(run):
    if run.trace is None or run.trace.program_runs <= 0:
        return None
    return 1e3 * run.trace.program_s / run.trace.program_runs
