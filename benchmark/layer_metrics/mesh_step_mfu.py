"""``step_mfu`` for a job over several chips: the least time ONE chip could
take for its share of what a boosting iteration needs (the histogram rows of
the window's own trees and one pass over score and label, each divided by the
devices of the trace: the rows are sharded evenly), over the traced seconds
per iteration.  HBM-bound, as ``step_mfu``."""

from benchmark.harness import device, work


def read(run):
    t = work.traced(run)
    if t is None or not run.get("window_trees") or t["devices"] < 2:
        return None
    feats = run["ctx"]["config"]["features"]
    rows = work.mean_hist_rows(run["window_trees"]) / t["devices"]
    least = work.least_seconds(
        work.ADDS_PER_CELL * feats * rows,
        rows * work.hist_row_bytes(feats)
        + run["rows"] / t["devices"] * work.SCORE_PASS_BYTES,
        device.peaks(run["device"]["kind"]))
    return 100.0 * least / (t["window_s"] / t["iterations"])
