"""The whole window's share of the chip's peak: AE training FLOPs of every
run completed in the window (``bench/flops.py``: forward + backward of
every client's local steps, eval and gate forwards excluded) over the
traced window's length times the peak FLOP/s of the chip
(``bench/peaks.json``)."""


def read(ctx):
    red = ctx["trace"]
    if red.window_ns <= 0 or red.n_runs <= 0:
        return None
    flops = ctx["run_train_flops"] * red.n_runs
    return 100.0 * flops / (red.window_ns / 1e9 * ctx["peaks"]["flops_per_s"])
