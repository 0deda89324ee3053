"""What decides ``correct`` for a training job: the plain reference judges,
from the raw rows, the trees that the timed path itself built at the timed
size.  Every number compared is listed beside its limit."""

import numpy as np

from benchmark.harness import data
from benchmark.harness.paths import BENCH_DIR, load_json
from benchmark.reference import gbdt_plain as ref

CANDIDATE_SAMPLE = 200000
# internal nodes whose best split is searched again: the root of the first
# tree, and a seeded pair from each class of size (the learner treats
# windows of different sizes by different code)
SIZE_CLASSES = ((0, 8192), (8192, 262144), (262144, None))
PER_CLASS = 2


def _worst_gap(got, want):
    """Worst entry of |got - want| against the entry's own size or the
    median entry's, whichever is larger."""
    if want.size == 0:
        return 0.0
    floor = np.median(np.abs(want))
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), floor)))


def _sampled_nodes(tree_no, counts, n, rng):
    nodes = [0] if tree_no == 0 else []
    for lo, hi in SIZE_CLASSES:
        hi = n // 4 if hi is None else hi
        pool = np.flatnonzero((counts > lo) & (counts <= hi))
        pool = pool[pool != 0]
        nodes += list(rng.choice(pool, size=min(PER_CLASS, pool.size),
                                 replace=False))
    return nodes


def audit(produced, seed, n_check, control=None):
    """The numbers compared.  A control puts in the program's place the leaf
    values and gains that a lower precision gives on the same trees:
    ``"bf16"`` rounds gradients and hessians to bfloat16 and sums them wide,
    as one bfloat16 pass of a matrix unit would (the nearest step below the
    stated three passes); ``"bf16_all"`` is the reference computed in
    bfloat16 throughout, values and sums."""
    X, y = produced["X"], produced["y"].astype(np.float64)
    params = produced["params"]
    lr, l2 = float(params["learning_rate"]), float(params.get("lambda_l2", 0.0))
    min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    min_data = int(params.get("min_data_in_leaf", 20))
    n = X.shape[0]
    trees = ref.parse_model(produced["model_text"])["trees"]
    rng = data.rng_for(seed, data.NODE_STREAM)
    pick = np.sort(rng.choice(n, size=min(n, CANDIDATE_SAMPLE), replace=False))
    cands = ref.quantile_candidates(X[pick], int(params["max_bin"]))

    out = {"count_mismatch": 0, "leaf_value_gap": 0.0,
           "leaf_value_gap_weighted": 0.0, "root_gain_gap": 0.0}
    # read in every run and printed, but not compared (PERF.md section 2)
    watched = {"gain_gap": 0.0, "split_shortfall": 0.0, "worst_leaf_rows": 0}
    # the first tree's leaves carry the average the boosting starts from,
    # so the trees' sum is the whole score
    init = ref.binary_init_score(y)
    score = np.zeros(n)
    for k, tree in enumerate(trees[:n_check]):
        g, h = ref.binary_grad_hess(score if k else np.full(n, init), y)
        li = ref.leaf_index(tree, X)
        leaf, inner = ref.node_sums(tree, li, g, h)
        want_leaf = -leaf[0] / (leaf[1] + l2) * lr
        want_gain = ref.split_gains(tree, leaf, inner, l2)
        got_leaf = tree["leaf_value"] - (init if k == 0 else 0.0)
        got_gain = tree["split_gain"]
        if control:
            if control == "bf16_all":
                import ml_dtypes
                lb, ib = ref.node_sums(tree, li, g, h, dtype=ml_dtypes.bfloat16)
            elif control == "bf16":
                lb, ib = ref.node_sums(tree, li, ref.round_bf16(g),
                                       ref.round_bf16(h))
            else:
                raise ValueError(f"unknown control {control!r}")
            got_leaf = -lb[0] / (lb[1] + l2) * lr
            got_gain = ref.split_gains(tree, lb, ib, l2)
        out["count_mismatch"] += int(
            np.sum(tree["leaf_count"] != leaf[2])
            + np.sum(tree["internal_count"] != inner[2]))
        worst = _worst_gap(got_leaf, want_leaf)
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
                                  _worst_gap(got_gain, want_gain))
        for node in _sampled_nodes(k, inner[2], n, rng):
            if node == 0:
                Xn, gn, hn = X, g, h
            else:
                rows = np.flatnonzero(ref.subtree_leaves(tree, node)[li])
                Xn, gn, hn = X[rows], g[rows], h[rows]
            best = ref.best_gain(Xn, gn, hn, cands, l2, min_hess, min_data)
            if np.isfinite(best) and best > 0:
                watched["split_shortfall"] = max(
                    watched["split_shortfall"],
                    float((best - want_gain[node]) / best))
        score += tree["leaf_value"][li]

    # the whole model the window left: the device's train score and the
    # program's predictions against the reference's walk of the same text
    idx = produced["sample_idx"]
    want = ref.predict_raw(trees, X[idx])
    out["score_gap"] = float(np.max(
        np.abs(produced["score_sample"] - want) / np.maximum(1.0, np.abs(want))))
    p_ref = ref.sigmoid(ref.predict_raw(trees, produced["Xh"]))
    out["predict_gap"] = float(np.max(np.abs(produced["p_holdout"] - p_ref)))
    info = {"auc_holdout": ref.auc(produced["yh"], produced["p_holdout"]),
            "trees": len(trees), **watched}
    if produced.get("valid_auc"):
        out["valid_auc_gap"] = abs(produced["valid_auc"][-1]
                                   - ref.auc(produced["yh"], p_ref))
    return out, info


def decide(numbers, kind="train_job"):
    """(correct, {name: {"value", "limit"}})."""
    limits = load_json(BENCH_DIR, "limits", kind + ".json")
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
