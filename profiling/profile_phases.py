"""Per-phase timing breakdown of the compact learner at bench scale.

Times each phase of a `num_leaves`-leaf tree on the bench workload
(1M x 28, 255 bins) in isolation, so the per-split cost model

    split = partition-sort(parent window) + histogram(smaller child)
          + split-scan + bookkeeping

can be attributed.  Run on the real TPU chip:

    python profiling/profile_phases.py [rows]

Writes profiling/PROFILE.json with the breakdown (committed as the round's
profiling artifact) and prints a human table.
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ONE timing implementation repo-wide: best-of, synced per call, shared
# with the runtime attribution probes — no hand-rolled timing loops here
from lightgbm_tpu.observability.attribution import (  # noqa: E402
    force_sync, timeit)


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    import jax
    import jax.numpy as jnp
    from jax import lax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.hist_pallas import build_histogram_packed

    rng = np.random.RandomState(7)
    f = 28
    X = rng.randn(rows, f).astype(np.float64)
    logit = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2] * 0.5 + np.sin(X[:, 3])
             + 0.5 * rng.randn(rows))
    y = (logit > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "none"}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    out = {"rows": rows, "device": str(jax.devices()[0])}

    # -- full iteration & tree ------------------------------------------------
    t_iter = timeit(lambda: bst.update() or 0,
                    sync=lambda _: force_sync(bst.gbdt.train_score.score))
    out["full_iteration_s"] = t_iter

    lrn = bst.gbdt.learner
    n = lrn.n_pad
    grad = jnp.zeros(n, jnp.float32).at[:rows].set(
        jnp.asarray(rng.randn(rows), jnp.float32))
    hess = jnp.ones(n, jnp.float32) * 0.25
    bag = jnp.zeros(n, jnp.float32).at[:rows].set(1.0)
    fmask = jnp.ones(lrn.num_features, bool)
    t_tree = timeit(lambda: lrn._jit_tree_c(grad, hess, bag, fmask))
    out["tree_train_s"] = t_tree
    out["boost_overhead_s"] = t_iter - t_tree

    # -- phase microbenches at each window bucket -----------------------------
    lrn._hist_branches = [lrn._make_hist_branch(S) for S in lrn._win_sizes]
    lrn._partition_branches = [lrn._make_partition_branch(S)
                               for S in lrn._win_sizes]
    bins_p = lrn.bins_packed()
    w = jnp.stack([grad * bag, hess * bag, bag], axis=0)
    rid = jnp.arange(n, dtype=jnp.int32)
    lid = jnp.zeros(n, jnp.int32)

    hist_t, part_t = {}, {}
    for i, S in enumerate(lrn._win_sizes):
        hb = jax.jit(lrn._hist_branches[i])
        t = timeit(hb, bins_p, w, jnp.int32(0), jnp.int32(S))
        hist_t[S] = t
        pb = jax.jit(lrn._partition_branches[i])
        t = timeit(pb, bins_p, w, rid, lid, jnp.int32(0), jnp.int32(S),
                   jnp.int32(3), jnp.int32(100), jnp.asarray(True),
                   jnp.asarray(False), jnp.zeros(lrn.cat_W, jnp.uint32),
                   jnp.int32(1), jnp.asarray(True))
        part_t[S] = t
    out["hist_by_window_s"] = {str(k): v for k, v in hist_t.items()}
    out["partition_by_window_s"] = {str(k): v for k, v in part_t.items()}

    # -- split scan (pair of children) ---------------------------------------
    hist = jnp.abs(jnp.asarray(
        rng.randn(lrn.num_features, lrn.num_bins_padded, 3), jnp.float32))
    crow = jnp.asarray([1.0, 0.0, rows / 8, rows / 2, 0.0, rows / 8,
                        rows / 2, 0.0, 0.0], jnp.float32)
    pair = jax.jit(lambda hl, hr, cr: lrn._cand_rows_pair(
        hl, hr, cr, fmask, jnp.asarray([True, True])))
    t = timeit(pair, hist, hist * 0.5, crow)
    out["split_scan_pair_s"] = t

    # -- model: expected per-tree totals --------------------------------------
    # leaf-wise tree: sum of parent windows ~ N log2(L); every split pays one
    # partition at the parent bucket + one hist at the smaller-child bucket.
    L = 255
    est_part = 0.0
    est_hist = 0.0
    lvl_windows = [n]
    splits_left = L - 1
    while splits_left > 0 and lvl_windows:
        nxt = []
        for wnd in lvl_windows:
            if splits_left <= 0:
                break
            splits_left -= 1
            bidx = int(np.searchsorted(lrn._win_sizes, wnd))
            bidx = min(bidx, len(lrn._win_sizes) - 1)
            est_part += part_t[lrn._win_sizes[bidx]]
            half = wnd // 2
            hidx = int(np.searchsorted(lrn._win_sizes, half))
            hidx = min(hidx, len(lrn._win_sizes) - 1)
            est_hist += hist_t[lrn._win_sizes[hidx]]
            nxt += [half, wnd - half]
        lvl_windows = nxt
    out["model_partition_total_s"] = est_part
    out["model_hist_total_s"] = est_hist
    out["model_split_scan_total_s"] = out["split_scan_pair_s"] * (L - 1)
    acc = est_part + est_hist + out["model_split_scan_total_s"]
    out["model_accounted_s"] = acc
    out["model_unaccounted_s"] = t_tree - acc

    os.makedirs(os.path.dirname(os.path.abspath(__file__)), exist_ok=True)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "PROFILE.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)

    print(f"\n=== phase breakdown ({rows} rows) ===")
    print(f"full iteration      {out['full_iteration_s']*1e3:9.1f} ms")
    print(f"  tree train        {out['tree_train_s']*1e3:9.1f} ms")
    print(f"  boost overhead    {out['boost_overhead_s']*1e3:9.1f} ms")
    print(f"model accounting of tree train:")
    print(f"  partition sorts   {est_part*1e3:9.1f} ms")
    print(f"  histograms        {est_hist*1e3:9.1f} ms")
    print(f"  split scans       {out['model_split_scan_total_s']*1e3:9.1f} ms")
    print(f"  unaccounted       {out['model_unaccounted_s']*1e3:9.1f} ms")
    print("\nper-window costs (ms):")
    print(f"{'window':>10} {'hist':>8} {'partition':>10}")
    for S in lrn._win_sizes:
        print(f"{S:>10} {hist_t[S]*1e3:8.2f} {part_t[S]*1e3:10.2f}")


if __name__ == "__main__":
    main()
