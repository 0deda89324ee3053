"""``hist_roofline`` for a job over several chips: the least time ONE chip
could take for its share of the histogram rows of the window's own trees
(the rows are sharded evenly), over one chip's histogram-kernel time (the
trace's mean over the device planes)."""

from benchmark.harness import device, work


def read(run):
    t = work.traced(run)
    s = work.kernel_seconds_per_iter(run, "hist")
    if not s or not run.get("window_trees") or t["devices"] < 2:
        return None
    feats = run["ctx"]["config"]["features"]
    rows = work.mean_hist_rows(run["window_trees"]) / t["devices"]
    least = work.least_seconds(work.ADDS_PER_CELL * feats * rows,
                               rows * work.hist_row_bytes(feats),
                               device.peaks(run["device"]["kind"]))
    return 100.0 * least / s
