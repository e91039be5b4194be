"""Model FLOPs of the images answered in the window (``flops.py``) over
the window's length times the chips' peak bf16 FLOP/s (``peaks.json``),
in %."""


def read(run):
    w = run.window
    if not w.done or run.peak_flops <= 0:
        return None
    return 100.0 * run.flops_per_image * len(w.done) / (
        w.seconds * run.cell.chips * run.peak_flops)
