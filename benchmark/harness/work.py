"""The work the algorithm needs, whatever implements it."""

import glob
import os

import numpy as np

from benchmark.harness import trace_reduce
from benchmark.harness.paths import BENCH_DIR, load_json

GRAD_HESS_BYTES = 8      # one float32 gradient and one float32 hessian a row
SCORE_PASS_BYTES = 12    # read score and label, write score: float32 each
ADDS_PER_CELL = 3        # gradient, hessian and count of one (row, feature)


def hist_rows(tree):
    """Rows a tree's histograms must visit with histogram subtraction: the
    root's rows, then for every split the smaller child's."""
    if tree["num_leaves"] <= 1:
        return 0
    both = np.concatenate([tree["internal_count"], tree["leaf_count"]])
    n_inner = tree["internal_count"].size

    def count(child):
        return both[np.where(child < 0, n_inner + ~child, child)]

    smaller = np.minimum(count(tree["left_child"]), count(tree["right_child"]))
    return int(tree["internal_count"][0] + smaller.sum())


def hist_row_bytes(features, bin_bytes=1):
    return features * bin_bytes + GRAD_HESS_BYTES


def hist_bytes(tree, features, bin_bytes=1):
    return hist_rows(tree) * hist_row_bytes(features, bin_bytes)


def least_seconds(flops, nbytes, peaks):
    """Roofline: the larger of operations over peak and bytes over peak."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def kernel_needles(group=None):
    """Name fragments of the Pallas kernels, from ``benchmark/kernels/``."""
    if group is not None:
        return load_json(BENCH_DIR, "kernels", group + ".json")["needles"]
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "kernels", "*.json"))):
        out += load_json(path)["needles"]
    return out


def traced(run):
    """The reduced trace where it holds whole iterations, else None."""
    t = run.get("trace")
    return t if t and t["iterations"] else None


def kernel_seconds_per_iter(run, group=None):
    """Device seconds per traced iteration of one kernel group (all groups
    where ``group`` is None); None where none of its kernels ran."""
    t = traced(run)
    if t is None:
        return None
    s = trace_reduce.seconds_matching(t["op_seconds"], kernel_needles(group))
    return None if s is None else s / t["iterations"]


def mean_hist_rows(trees):
    return sum(hist_rows(tr) for tr in trees) / len(trees)
