"""The histogram kernels' share of their roofline: the least time the chip
could take for the rows the window's own trees needed (HBM-bound: a bin code
is a byte, three additions a cell are nothing beside the peak), over the
kernels' device time in the traced window."""

from benchmark.harness import device, work


def read(run):
    s = work.kernel_seconds_per_iter(run, "hist")
    if not s or not run.get("window_trees"):
        return None
    feats = run["ctx"]["config"]["features"]
    rows = work.mean_hist_rows(run["window_trees"])
    least = work.least_seconds(work.ADDS_PER_CELL * feats * rows,
                               rows * work.hist_row_bytes(feats),
                               device.peaks(run["device"]["kind"]))
    return 100.0 * least / s
