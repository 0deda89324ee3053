"""What decides ``correct`` for a ranking job: the plain references judge,
from the raw rows and their queries, the trees that the timed path itself
built at the timed size.  The numbers are ``check_train``'s (PERF.md
section 2), with lambdarank gradients (``reference/lambdarank_plain.py``) in
place of the binary ones; every number compared is listed beside its limit
in ``limits/rank_job.json``."""

import numpy as np

from benchmark.harness import check_train
from benchmark.reference import gbdt_plain as ref
from benchmark.reference import lambdarank_plain as rank_ref


def _bounds(sizes):
    return np.concatenate([[0], np.cumsum(np.asarray(sizes, np.int64))])


def audit(produced, n_check, eval_at, control=None):
    """The numbers compared, and what is printed beside them.  ``control``
    is ``check_train.audit``'s: ``"bf16"`` puts in the program's place the
    leaf values and gains that gradients and hessians rounded to bfloat16
    and summed wide give on the same trees."""
    X, y = produced["X"], produced["y"].astype(np.float64)
    params = produced["params"]
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    k = int(params.get("max_position", 20))
    sigma = float(params.get("sigmoid", 1.0))
    bounds = _bounds(produced["group"])
    n = X.shape[0]
    trees = ref.parse_model(produced["model_text"])["trees"]

    out = {"count_mismatch": 0, "leaf_value_gap": 0.0,
           "leaf_value_gap_weighted": 0.0, "root_gain_gap": 0.0}
    # read in every run and printed, but not compared
    watched = {"gain_gap": 0.0, "worst_leaf_rows": 0}
    # lambdarank starts from 0 (no boost from average)
    score = np.zeros(n)
    for tree in trees[:n_check]:
        g, h = rank_ref.grad_hess(score, y, bounds, k, sigma)
        li = ref.leaf_index(tree, X)
        leaf, inner = ref.node_sums(tree, li, g, h)
        want_leaf = -leaf[0] / (leaf[1] + l2) * lr
        want_gain = ref.split_gains(tree, leaf, inner, l2)
        got_leaf, got_gain = tree["leaf_value"], tree["split_gain"]
        if control == "bf16":
            lb, ib = ref.node_sums(tree, li, ref.round_bf16(g),
                                   ref.round_bf16(h))
            got_leaf = -lb[0] / (lb[1] + l2) * lr
            got_gain = ref.split_gains(tree, lb, ib, l2)
        elif control:
            raise ValueError(f"unknown control {control!r}")
        out["count_mismatch"] += int(
            np.sum(tree["leaf_count"] != leaf[2])
            + np.sum(tree["internal_count"] != inner[2]))
        worst = check_train._worst_gap(got_leaf, want_leaf)
        if worst > out["leaf_value_gap"]:
            out["leaf_value_gap"] = worst
            floor = np.median(np.abs(want_leaf))
            watched["worst_leaf_rows"] = int(leaf[2][np.argmax(
                np.abs(got_leaf - want_leaf)
                / np.maximum(np.abs(want_leaf), floor))])
        out["leaf_value_gap_weighted"] = max(
            out["leaf_value_gap_weighted"],
            float(np.sum(leaf[2] * np.abs(got_leaf - want_leaf))
                  / np.sum(leaf[2] * np.abs(want_leaf))))
        out["root_gain_gap"] = max(
            out["root_gain_gap"],
            float(abs(got_gain[0] - want_gain[0]) / want_gain[0]))
        watched["gain_gap"] = max(watched["gain_gap"],
                                  check_train._worst_gap(got_gain, want_gain))
        score += tree["leaf_value"][li]

    # the whole model the window left: the device's train score and the
    # program's predictions (raw scores) against the reference's walk
    idx = produced["sample_idx"]
    want = ref.predict_raw(trees, X[idx])
    out["score_gap"] = float(np.max(np.abs(produced["score_sample"] - want)
                                    / np.maximum(1.0, np.abs(want))))
    p_ref = ref.predict_raw(trees, produced["Xh"])
    out["predict_gap"] = float(np.max(
        np.abs(produced["p_holdout"] - p_ref) / np.maximum(1.0, np.abs(p_ref))))
    hb = _bounds(produced["group_holdout"])
    info = {f"ndcg@{eval_at}_holdout": rank_ref.ndcg_at(
                produced["p_holdout"], produced["yh"], hb, eval_at),
            "trees": len(trees), **watched}
    return out, info


def decide(numbers):
    return check_train.decide(numbers, kind="rank_job")
