"""The whole iteration's share of the chip's peak: the least time the chip
could take for what one boosting iteration needs (the histogram rows of the
window's own trees, and one pass over score and label), over the traced
window's seconds per iteration.  The bound is HBM bandwidth, not FLOP/s: a
tree learner adds, it does not multiply."""

from benchmark.harness import device, work


def read(run):
    t = work.traced(run)
    if t is None or not run.get("window_trees"):
        return None
    feats = run["ctx"]["config"]["features"]
    rows = work.mean_hist_rows(run["window_trees"])
    least = work.least_seconds(
        work.ADDS_PER_CELL * feats * rows,
        rows * work.hist_row_bytes(feats) + run["rows"] * work.SCORE_PASS_BYTES,
        device.peaks(run["device"]["kind"]))
    return 100.0 * least / (t["window_s"] / t["iterations"])
