"""Attribute the compact learner's per-tree device time by ablation.

Compiles stubbed variants of the fused tree build (partition sort skipped /
histogram skipped / split-scan skipped) and differences their steady-state
times, which holds up where a sub-100 ms microbench of one op is lost in
per-dispatch overhead.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(r):
    import jax
    jax.block_until_ready(r)


def timed(fn, args, iters=8):
    r = fn(*args)
    _sync(r)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*args)
        _sync(r)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    import jax
    import jax.numpy as jnp
    from jax import lax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner_compact import CompactTPUTreeLearner

    rng = np.random.RandomState(7)
    f = 28
    X = rng.randn(rows, f).astype(np.float64)
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "none"}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    data = ds.constructed
    from lightgbm_tpu.config import Config
    cfg = Config.from_params(params)

    class NoPartition(CompactTPUTreeLearner):
        def _make_partition_branch(self, S):
            def branch(bins_p, w_p, rid_p, lid_p, s, c, feat, thr, dleft,
                       is_cat, cat_bits, new_leaf, do):
                lc_w = c // 2
                return bins_p, w_p, rid_p, lid_p, lc_w, lc_w, c
            return branch

    class NoHist(CompactTPUTreeLearner):
        def _make_hist_branch(self, S):
            fshape = (self.num_features, self.num_bins_padded, 3)

            def branch(bins_p, w_p, start, cnt):
                # depend on inputs so nothing is constant-folded
                seed = (w_p[0, 0] + bins_p[0, 0].astype(jnp.float32)
                        + start.astype(jnp.float32) + cnt.astype(jnp.float32))
                return jnp.full(fshape, 1e-6, jnp.float32) * (1.0 + 0.0 * seed)
            return branch

    class NoScan(CompactTPUTreeLearner):
        def _cand_rows_pair(self, hist_l, hist_r, crow_f, feature_mask,
                            depth_ok, constraints=None):
            z = hist_l[0, 0, 0] * 0.0
            cf = jnp.tile(jnp.asarray(
                [1.0, 0.0, 100.0, 50.0, 0.0, 100.0, 50.0, 0.0, 0.0],
                self._acc), (2, 1)) + z.astype(self._acc)
            ci = jnp.tile(jnp.asarray([1, 10, 0], jnp.int32), (2, 1))
            cb = jnp.zeros((2, self.cat_W), jnp.uint32)
            return cf, ci, cb

    n_pad = data.num_data_padded
    grad = jnp.asarray(rng.randn(n_pad).astype(np.float32))
    hess = jnp.ones(n_pad, jnp.float32) * 0.25
    bag = jnp.zeros(n_pad, jnp.float32).at[:rows].set(1.0)
    fmask = jnp.ones(data.num_used_features, dtype=bool)
    args = (grad, hess, bag, fmask)

    class HistOnly(NoPartition, NoScan):
        """Realistic window halving + real histograms; no sort, no scan."""

    class Skeleton(NoPartition, NoHist, NoScan):
        """Pure per-step bookkeeping: window halving, constant hists,
        constant candidates — the fixed overhead floor."""

    class SkeletonNoSwitch(Skeleton):
        """Skeleton with the lax.switch replaced by a direct call to one
        branch — isolates conditional carry-copy cost."""

        def _split_step_compact(self, state, feature_mask, step_idx):
            import types
            real_switch = lax.switch

            def fake_switch(idx, branches, *args):
                return branches[0](*args)
            lax_mod = sys.modules["lightgbm_tpu.learner_compact"].lax
            orig = lax_mod.switch
            lax_mod.switch = fake_switch
            try:
                return super()._split_step_compact(state, feature_mask,
                                                   step_idx)
            finally:
                lax_mod.switch = orig

    out = {"rows": rows}
    variants = [("full", CompactTPUTreeLearner), ("no_partition", NoPartition),
                ("no_scan", NoScan), ("hist_only", HistOnly),
                ("skeleton", Skeleton),
                ("skeleton_noswitch", SkeletonNoSwitch)]
    for name, cls in variants:
        lrn = cls(cfg, data)
        t = timed(lrn._jit_tree_c, args)
        out[name + "_s"] = t
        print(f"{name:14s} {t*1e3:9.1f} ms")
        del lrn

    full = out["full_s"]
    print(f"\npartition cost ~ {1e3*(full - out['no_partition_s']):8.1f} ms")
    print(f"splitscan cost ~ {1e3*(full - out['no_scan_s']):8.1f} ms")

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "PROFILE_TREE.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=2)


if __name__ == "__main__":
    main()
