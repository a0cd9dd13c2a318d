"""The whole step's share of the chip's peak: the least time the
window's necessary work could take (the larger of its FLOPs over the bf16
peak and its bytes over HBM bandwidth; ``work.py``) over the window's
length.  A step is a round (sync) or a fold (async)."""
import work


def read(run):
    if not run.peaks or run.window_s <= 0 or not run.steps:
        return None
    least = work.least_seconds(run.work, run.peaks, "bf16_flops_per_s")
    return 100.0 * least / run.window_s
