"""Model FLOPs of the steps completed in the traced window over (window
seconds x chips x the chip's bf16 peak), in percent. The FLOPs come from the
configuration's counter (``drivers/<driver>_ref.train_flops``), computed from
the shapes run; recomputed FLOPs do not count."""


def read(run):
    if run.peak is None or run.steps <= 0:
        return None
    return 100.0 * run.steps * run.flops_per_step / (
        run.window_s * run.chips * run.peak['bf16_flop_per_s'])
