"""TPU tree learner: leaf-wise (best-first) tree growth on device.

TPU-native re-design of ``SerialTreeLearner`` (`src/treelearner/serial_tree_learner.cpp:157-860`)
slotting in where ``GPUTreeLearner`` does (`src/treelearner/gpu_tree_learner.cpp`).
The reference's per-split control flow is preserved — keep a best split per
leaf, split the globally best leaf, build the smaller child's histogram and
subtract for the sibling (`serial_tree_learner.cpp:371-385`) — but the data
structures are re-designed for static-shape XLA:

  * ``DataPartition``'s permuted index array (`data_partition.hpp`) becomes a
    flat ``(rows,) int32 leaf_id`` updated with ``where`` on the split
    predicate; histogram masking on ``leaf_id == leaf`` replaces row slicing.
  * The ``HistogramPool`` LRU (`feature_histogram.hpp:646-818`) becomes a
    dense ``(num_leaves, F, B, 3)`` pool in HBM — no eviction, sized up front.
  * The entire split becomes ONE jitted ``split_step`` with no data-dependent
    Python control flow; a step whose best gain is <= 0 is an exact no-op, so
    a tree is always ``num_leaves - 1`` dispatches and only the tiny per-split
    record array crosses back to host, once per tree.

Numerics: histograms and gains are f32 (the reference GPU path's documented
regime, `docs/GPU-Performance.rst:137-141`); per-leaf totals come from f32
reductions over the bagged mask.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from .config import Config
from .dataset import _ConstructedDataset
from .ops.histogram import build_histogram
from .ops.split import SplitCandidates, find_best_splits
from .tree import Tree

# per-split record layout fetched to host once per tree
REC_VALID, REC_LEAF, REC_FEATURE, REC_THRESHOLD, REC_DEFAULT_LEFT, REC_GAIN, \
    REC_LEFT_OUT, REC_RIGHT_OUT, REC_LEFT_CNT, REC_RIGHT_CNT, \
    REC_INTERNAL_VALUE, REC_INTERNAL_CNT, REC_LEFT_SUM_H, REC_RIGHT_SUM_H, \
    REC_LEFT_SUM_G, REC_RIGHT_SUM_G, REC_IS_CAT = range(17)
NUM_REC_FIELDS = 17


class TreeState(NamedTuple):
    leaf_id: jax.Array       # (N,) int32
    hist_pool: jax.Array     # (L, F, B, 3) f32
    leaf_sum_g: jax.Array    # (L,) f32
    leaf_sum_h: jax.Array    # (L,) f32
    leaf_cnt: jax.Array      # (L,) f32
    leaf_output: jax.Array   # (L,) f32
    leaf_depth: jax.Array    # (L,) int32
    cand: "_LeafCand"        # per-leaf best splits, arrays (L,)
    num_leaves: jax.Array    # () int32
    records: jax.Array       # (L-1, NUM_REC_FIELDS) f32
    rec_cat: jax.Array       # (L-1, W) uint32 — bin bitset of cat splits
    rec_i: jax.Array         # (L-1, 2) int32 — exact bagged left/right counts
    leaf_min_c: jax.Array    # (L,) monotone value constraints per leaf
    leaf_max_c: jax.Array


class _FeatCand(NamedTuple):
    """Merged numerical+categorical best split PER FEATURE (fields (F,);
    cat_bits (F, W))."""
    gain: jax.Array
    threshold: jax.Array
    default_left: jax.Array
    is_cat: jax.Array
    cat_bits: jax.Array
    left_sum_g: jax.Array
    left_sum_h: jax.Array
    left_cnt: jax.Array
    right_sum_g: jax.Array
    right_sum_h: jax.Array
    right_cnt: jax.Array
    left_output: jax.Array
    right_output: jax.Array


class _LeafCand(NamedTuple):
    """Best split per LEAF, reduced over features (fields shape (L,);
    cat_bits (L, W))."""
    gain: jax.Array
    feature: jax.Array
    threshold: jax.Array
    default_left: jax.Array
    is_cat: jax.Array
    cat_bits: jax.Array
    left_sum_g: jax.Array
    left_sum_h: jax.Array
    left_cnt: jax.Array
    right_sum_g: jax.Array
    right_sum_h: jax.Array
    right_cnt: jax.Array
    left_output: jax.Array
    right_output: jax.Array


def _reduce_over_features(cand: _FeatCand) -> _LeafCand:
    """argmax over features; lowest feature index wins ties
    (`serial_tree_learner.cpp:505-520`)."""
    best_f = jnp.argmax(cand.gain).astype(jnp.int32)
    g = lambda a: a[best_f]
    return _LeafCand(gain=g(cand.gain), feature=best_f,
                     threshold=g(cand.threshold),
                     default_left=g(cand.default_left),
                     is_cat=g(cand.is_cat), cat_bits=g(cand.cat_bits),
                     left_sum_g=g(cand.left_sum_g), left_sum_h=g(cand.left_sum_h),
                     left_cnt=g(cand.left_cnt), right_sum_g=g(cand.right_sum_g),
                     right_sum_h=g(cand.right_sum_h), right_cnt=g(cand.right_cnt),
                     left_output=g(cand.left_output),
                     right_output=g(cand.right_output))


class TPUTreeLearner:
    """Leaf-wise growth driven from host: one jitted no-op-able step per
    split, single host sync per tree (factory slot:
    `src/treelearner/tree_learner.cpp:9-33`, device_type=tpu)."""

    def __init__(self, cfg: Config, data: _ConstructedDataset,
                 hist_backend: str = "auto"):
        self.cfg = cfg
        self.data = data
        self.num_leaves = max(int(cfg.num_leaves), 2)
        self.hist_backend = hist_backend
        num_bin, missing, default_bin, is_cat = data.feature_meta_arrays()
        self.f_num_bin = jnp.asarray(num_bin)
        self.f_missing = jnp.asarray(missing)
        self.f_default_bin = jnp.asarray(default_bin)
        self.np_num_bin = num_bin
        self.np_missing = missing
        self.np_default_bin = default_bin
        self.is_categorical = is_cat
        self.num_bins_padded = int(data.max_num_bin)
        self.num_features = data.num_used_features
        # double-precision histogram accumulation — the reference's
        # ``gpu_use_dp`` (`config.h:872-876`): training decisions then match
        # the f64 CPU implementation exactly (needs jax_enable_x64)
        self.hist_dp = bool(cfg.gpu_use_dp or cfg.tpu_double_precision)
        if self.hist_dp:
            import jax as _jax
            if not _jax.config.jax_enable_x64:
                import warnings
                warnings.warn("gpu_use_dp/tpu_double_precision requested but "
                              "jax_enable_x64 is off; falling back to f32 "
                              "histogram accumulation")
                self.hist_dp = False
        self._bins = None   # the device table: ``place_table``, ``bins``
        self._split_kwargs = dict(
            lambda_l1=float(cfg.lambda_l1), lambda_l2=float(cfg.lambda_l2),
            max_delta_step=float(cfg.max_delta_step),
            min_data_in_leaf=int(cfg.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(cfg.min_sum_hessian_in_leaf),
            min_gain_to_split=float(cfg.min_gain_to_split),
            # all-MISSING_NONE datasets statically skip the whole
            # missing-right scan (exact: it can contribute nothing)
            skip_missing_scan=not bool((missing != MISSING_NONE).any()))
        self._cat_split_kwargs = dict(
            {k: v for k, v in self._split_kwargs.items()
             if k != "skip_missing_scan"},
            cat_l2=float(cfg.cat_l2), cat_smooth=float(cfg.cat_smooth),
            max_cat_threshold=int(cfg.max_cat_threshold),
            max_cat_to_onehot=int(cfg.max_cat_to_onehot),
            min_data_per_group=int(cfg.min_data_per_group))
        # numerical features go to the two-scan finder, categoricals to the
        # one-hot / sorted-CTR finder; masks combine with the per-tree
        # feature_fraction mask
        self._cat_mask = jnp.asarray(~is_cat)      # numerical features
        self._is_cat_mask = jnp.asarray(is_cat)    # categorical features
        self.has_categorical = bool(is_cat.any())
        self.cat_W = (self.num_bins_padded + 31) // 32
        # monotone constraints / per-feature gain penalty, mapped from real
        # feature index to used-feature slots (`config.h:355-368`)
        used_map = data.used_feature_map
        mono = np.zeros(self.num_features, np.int8)
        if cfg.monotone_constraints:
            mc = list(cfg.monotone_constraints)
            for k, j in enumerate(used_map):
                if int(j) < len(mc):
                    mono[k] = int(mc[int(j)])
        self.has_monotone = bool(mono.any())
        self.f_monotone = jnp.asarray(mono) if self.has_monotone else None
        pen = np.ones(self.num_features, np.float32)
        if cfg.feature_contri:
            fc = list(cfg.feature_contri)
            for k, j in enumerate(used_map):
                if int(j) < len(fc):
                    pen[k] = float(fc[int(j)])
        self.has_penalty = bool((pen != 1.0).any())
        self.f_penalty = jnp.asarray(pen) if self.has_penalty else None
        # observability: telemetry is a STATIC trace-time flag — when off,
        # every learner traces the exact jaxpr it traced before the
        # telemetry layer existed (the device counter lane stays None)
        from .observability import CollectiveLedger
        self._telemetry = bool(getattr(cfg, "telemetry", False))
        self._ledger = CollectiveLedger(enabled=self._telemetry)
        self._coll_ctx = ("tree", "tree")   # (phase, cadence) for _rec_coll
        self._last_telem = None
        self._jit_init = jax.jit(self._init_root)
        self._jit_step = jax.jit(self._split_step, donate_argnums=(0,))
        self._jit_tree = jax.jit(self._train_tree_fused)

    @property
    def bins(self) -> jax.Array:
        """The (F, N) table of bin codes on the default device.  Not placed
        by ``__init__``: the sharded learners (``parallel/``) never read it
        (they place per-device shards from the host table), nor does the
        serial learner that ``GBDT.init`` builds before it routes a job to
        one of them, so a job over a mesh never stages the whole table on
        one chip.  ``GBDT.init`` places it (:meth:`place_table`); a learner
        built by hand gets it at its first read."""
        if self._bins is None:
            # a learner built by hand may first read it under a trace
            with jax.ensure_compile_time_eval():
                self._bins = self.data.device_bins()
        return self._bins

    @bins.setter
    def bins(self, value) -> None:
        self._bins = value

    def place_table(self) -> None:
        """Put the table of bin codes where this learner's programs read it
        (here the default device; a sharded learner, its shards).  Called
        once by ``GBDT.init`` on the learner the job was routed to, before
        anything is traced."""
        self.bins

    # -- observability seams --------------------------------------------------

    def _rec_coll(self, op: str, payload) -> None:
        """Trace-time collective accounting hook: the sharded seams call
        this next to each lax collective they issue (no-op when telemetry
        is off; never emits device ops)."""
        if self._ledger.enabled:
            phase, cadence = self._coll_ctx
            self._ledger.record(op, payload, phase, cadence)

    def take_telemetry(self):
        """Pop the last tree's device counter vector (None for learners
        without a device counter lane)."""
        t, self._last_telem = self._last_telem, None
        return t

    def exchange_probe(self):
        """Standalone jitted program over this learner's cross-device
        exchange seam, as ``(fn, args)`` for the sampled-sync attribution
        probe (`observability/attribution.py`), or None when the learner
        has no exchange (the serial paths)."""
        return None

    # -- device functions ----------------------------------------------------

    def _hist(self, w):
        h = build_histogram(self.bins, w, num_bins=self.num_bins_padded,
                            backend=self.hist_backend, dp=self.hist_dp)
        return h[:self.num_features]  # drop feature-tile padding rows

    def _fix_histogram(self, hist, sum_g, sum_h, cnt):
        """``Dataset::FixHistogram`` (`src/io/dataset.cpp:923-941`): every
        feature with ``default_bin > 0`` gets its default-bin entry REBUILT
        as leaf totals minus the other bins before any scan — the
        reference's histogram construction skips default-bin rows, so this
        is load-bearing there; here it is an exact no-op on consistent
        paths but reproduces the reference's behavior on forced-split
        chains, whose GatherInfo sums disagree with the actual partition
        (the delta lands in the default bin exactly like the reference)."""
        dt = hist.dtype
        db = self.f_default_bin
        dbm = (jnp.arange(hist.shape[1])[None, :] == db[:, None]) & \
            (db[:, None] > 0)                                    # (F, B)
        totals = jnp.stack([sum_g, sum_h, cnt]).astype(dt)       # (3,)
        others = jnp.sum(jnp.where(dbm[..., None], 0.0, hist), axis=1)
        fixed = totals[None, :] - others                         # (F, 3)
        return jnp.where(dbm[..., None], fixed[:, None, :], hist)

    def _feature_cands(self, hist, sum_g, sum_h, cnt, feature_mask,
                       min_c=None, max_c=None) -> _FeatCand:
        """Merged per-feature candidates: each feature scanned by its own
        finder (`FeatureHistogram::FuncForNumrical/FuncForCategorical`,
        `feature_histogram.hpp:256-270`).  min_c/max_c are this leaf's
        monotone value constraints."""
        hist = self._fix_histogram(hist, sum_g, sum_h, cnt)
        f = self.num_features
        w = self.cat_W
        if not self.has_monotone:
            min_c = max_c = None
        elif min_c is None:
            min_c = jnp.asarray(-jnp.inf, hist.dtype)
            max_c = jnp.asarray(jnp.inf, hist.dtype)
        num = find_best_splits(
            hist, sum_g, sum_h, cnt, self.f_num_bin, self.f_missing,
            self.f_default_bin, feature_mask & self._cat_mask,
            self.f_monotone, min_c, max_c,
            **self._split_kwargs)
        if self.has_penalty:
            # `FindBestThreshold` gain penalty (`feature_histogram.hpp:81`)
            num = num._replace(gain=jnp.where(
                jnp.isneginf(num.gain), num.gain, num.gain * self.f_penalty))
        if not self.has_categorical:
            return _FeatCand(
                gain=num.gain, threshold=num.threshold,
                default_left=num.default_left,
                is_cat=jnp.zeros(f, bool),
                cat_bits=jnp.zeros((f, w), jnp.uint32),
                left_sum_g=num.left_sum_g, left_sum_h=num.left_sum_h,
                left_cnt=num.left_cnt, right_sum_g=num.right_sum_g,
                right_sum_h=num.right_sum_h, right_cnt=num.right_cnt,
                left_output=num.left_output, right_output=num.right_output)
        from .ops.split_cat import find_best_splits_categorical
        cat = find_best_splits_categorical(
            hist, sum_g, sum_h, cnt, self.f_num_bin, self.f_missing,
            feature_mask & self._is_cat_mask, min_c, max_c,
            **self._cat_split_kwargs)
        if self.has_penalty:
            cat = cat._replace(gain=jnp.where(
                jnp.isneginf(cat.gain), cat.gain, cat.gain * self.f_penalty))
        ic = self._is_cat_mask
        pick = lambda c, n: jnp.where(ic, c, n)
        return _FeatCand(
            gain=pick(cat.gain, num.gain),
            threshold=jnp.where(ic, 0, num.threshold),
            default_left=jnp.where(ic, False, num.default_left),
            is_cat=ic,
            cat_bits=jnp.where(ic[:, None], cat.bits,
                               jnp.zeros((f, w), jnp.uint32)),
            left_sum_g=pick(cat.left_sum_g, num.left_sum_g),
            left_sum_h=pick(cat.left_sum_h, num.left_sum_h),
            left_cnt=pick(cat.left_cnt, num.left_cnt),
            right_sum_g=pick(cat.right_sum_g, num.right_sum_g),
            right_sum_h=pick(cat.right_sum_h, num.right_sum_h),
            right_cnt=pick(cat.right_cnt, num.right_cnt),
            left_output=pick(cat.left_output, num.left_output),
            right_output=pick(cat.right_output, num.right_output))

    def _leaf_cand(self, hist, sum_g, sum_h, cnt, feature_mask, depth_ok,
                   min_c=None, max_c=None) -> _LeafCand:
        cand = self._feature_cands(hist, sum_g, sum_h, cnt, feature_mask,
                                   min_c, max_c)
        lc = _reduce_over_features(cand)
        return lc._replace(gain=jnp.where(depth_ok, lc.gain, -jnp.inf))

    def _child_constraints(self, info, pmin, pmax):
        """Constraint propagation on split (`serial_tree_learner.cpp:765-776`):
        children inherit the parent's range; a monotone numerical split pins
        the shared boundary at the output midpoint."""
        mono_t = self.f_monotone[info.feature]
        mono_t = jnp.where(info.is_cat, 0, mono_t)
        mid = (info.left_output + info.right_output) / 2.0
        lmin = jnp.where(mono_t < 0, mid, pmin)
        lmax = jnp.where(mono_t > 0, mid, pmax)
        rmin = jnp.where(mono_t > 0, mid, pmin)
        rmax = jnp.where(mono_t < 0, mid, pmax)
        return lmin, lmax, rmin, rmax

    def _init_root(self, grad, hess, bag, feature_mask) -> TreeState:
        n = self.bins.shape[1]
        f = self.num_features
        b = self.num_bins_padded
        L = self.num_leaves
        w = jnp.stack([grad * bag, hess * bag, bag], axis=0)
        root_hist = self._hist(w)
        acc = jnp.float64 if self.hist_dp else jnp.float32
        sum_g = jnp.sum((grad * bag).astype(acc))
        sum_h = jnp.sum((hess * bag).astype(acc))
        cnt = jnp.sum(bag.astype(acc))
        md = int(self.cfg.max_depth)
        depth_ok = jnp.asarray(True if md <= 0 else md > 0)
        root = self._leaf_cand(root_hist, sum_g, sum_h, cnt, feature_mask, depth_ok)

        def expand(x):
            x = jnp.asarray(x)
            return jnp.concatenate(
                [x[None], jnp.zeros((L - 1,) + x.shape, x.dtype)], axis=0)

        cand_L = jax.tree_util.tree_map(expand, root)
        cand_L = cand_L._replace(gain=cand_L.gain.at[1:].set(-jnp.inf))
        hist_pool = jnp.zeros((L, f, b, 3), root_hist.dtype).at[0].set(root_hist)
        return TreeState(
            leaf_id=jnp.zeros(n, jnp.int32),
            hist_pool=hist_pool,
            leaf_sum_g=jnp.zeros(L, acc).at[0].set(sum_g),
            leaf_sum_h=jnp.zeros(L, acc).at[0].set(sum_h),
            leaf_cnt=jnp.zeros(L, acc).at[0].set(cnt),
            leaf_output=jnp.zeros(L, jnp.float32),
            leaf_depth=jnp.zeros(L, jnp.int32),
            cand=cand_L,
            num_leaves=jnp.asarray(1, jnp.int32),
            records=jnp.zeros((L - 1, NUM_REC_FIELDS), jnp.float32),
            rec_cat=jnp.zeros((L - 1, self.cat_W), jnp.uint32),
            rec_i=jnp.zeros((L - 1, 2), jnp.int32),
            leaf_min_c=jnp.full(L, -jnp.inf, jnp.float32),
            leaf_max_c=jnp.full(L, jnp.inf, jnp.float32))

    def _split_step(self, state: TreeState, grad, hess, bag, feature_mask,
                    step_idx, forced=None) -> TreeState:
        """One split; ``forced=(leaf, info, do)`` replaces best-gain
        selection with a forced split (`serial_tree_learner.cpp:543-663`)."""
        cfg = self.cfg
        cand = state.cand
        if forced is None:
            best_leaf = jnp.argmax(cand.gain).astype(jnp.int32)
            info = jax.tree_util.tree_map(lambda a: a[best_leaf], cand)
            do = info.gain > 0.0
        else:
            best_leaf, info, do = forced
            best_leaf = jnp.asarray(best_leaf, jnp.int32)
        best_gain = info.gain
        dof = do.astype(jnp.float32)
        new_leaf = state.num_leaves

        # ---- partition rows (`data_partition.hpp` Split → `tree.h:233-249`
        # NumericalDecisionInner / `tree.h:270-277` CategoricalDecisionInner)
        frow = self.bins[info.feature]                      # (N,) bin codes
        frow = frow.astype(jnp.int32)
        mt = self.f_missing[info.feature]
        db = self.f_default_bin[info.feature]
        nb = self.f_num_bin[info.feature]
        is_missing = ((mt == MISSING_ZERO) & (frow == db)) | \
                     ((mt == MISSING_NAN) & (frow == nb - 1))
        go_left = jnp.where(is_missing, info.default_left,
                            frow <= info.threshold)
        if self.has_categorical:
            cat_left = (info.cat_bits[frow >> 5]
                        >> (frow & 31).astype(jnp.uint32)) & 1
            go_left = jnp.where(info.is_cat, cat_left.astype(bool), go_left)
        at_leaf = state.leaf_id == best_leaf
        leaf_id = jnp.where(do & at_leaf & ~go_left, new_leaf, state.leaf_id)
        # exact integer bagged counts — the f32 histogram count channel
        # loses integer exactness past 2^24 rows (round-1 advisor hazard)
        bag_b = bag > 0.5
        lc_bag = jnp.sum((at_leaf & go_left & bag_b).astype(jnp.int32)) \
                    .astype(jnp.int32)
        c_bag = jnp.sum((at_leaf & bag_b).astype(jnp.int32)).astype(jnp.int32)

        # ---- smaller-child histogram + sibling subtraction
        # (`serial_tree_learner.cpp:371-385`)
        left_smaller = info.left_cnt <= info.right_cnt
        small_leaf = jnp.where(left_smaller, best_leaf, new_leaf)
        m_small = (leaf_id == small_leaf) & at_leaf & do
        msf = m_small.astype(jnp.float32)
        w = jnp.stack([grad * bag * msf, hess * bag * msf, bag * msf], axis=0)
        hist_small = self._hist(w)
        hist_parent = state.hist_pool[best_leaf]
        hist_large = hist_parent - hist_small
        hist_left = jnp.where(left_smaller, hist_small, hist_large)
        hist_right = jnp.where(left_smaller, hist_large, hist_small)
        hist_pool = state.hist_pool
        hist_pool = hist_pool.at[best_leaf].set(
            jnp.where(do, hist_left, hist_parent))
        hist_pool = hist_pool.at[new_leaf].set(
            jnp.where(do, hist_right, hist_pool[new_leaf]))

        # ---- leaf bookkeeping.  Forced splits mirror the reference's
        # convention: child SUMS from GatherInfoForThreshold, child COUNTS
        # from the actual partition (`leaf_splits.hpp:40-52` reads
        # ``leaf_count`` off the data partition) — see learner_compact.py.
        if forced is not None:
            info = info._replace(left_cnt=lc_bag.astype(info.left_cnt.dtype),
                                 right_cnt=(c_bag - lc_bag)
                                 .astype(info.right_cnt.dtype))
        upd = lambda arr, l_val, r_val: (
            arr.at[best_leaf].set(jnp.where(do, l_val, arr[best_leaf]))
               .at[new_leaf].set(jnp.where(do, r_val, arr[new_leaf])))
        leaf_sum_g = upd(state.leaf_sum_g, info.left_sum_g, info.right_sum_g)
        leaf_sum_h = upd(state.leaf_sum_h, info.left_sum_h, info.right_sum_h)
        leaf_cnt = upd(state.leaf_cnt, info.left_cnt, info.right_cnt)
        prev_output = state.leaf_output[best_leaf]
        leaf_output = upd(state.leaf_output, info.left_output, info.right_output)
        child_depth = state.leaf_depth[best_leaf] + 1
        leaf_depth = upd(state.leaf_depth, child_depth, child_depth)

        # ---- children's best splits (with monotone constraint propagation)
        md = int(cfg.max_depth)
        depth_ok = jnp.asarray(True) if md <= 0 else (child_depth < md)
        if self.has_monotone:
            pmin = state.leaf_min_c[best_leaf]
            pmax = state.leaf_max_c[best_leaf]
            lmin, lmax, rmin, rmax = self._child_constraints(info, pmin, pmax)
            leaf_min_c = upd(state.leaf_min_c, lmin, rmin)
            leaf_max_c = upd(state.leaf_max_c, lmax, rmax)
        else:
            lmin = lmax = rmin = rmax = None
            leaf_min_c = state.leaf_min_c
            leaf_max_c = state.leaf_max_c
        cand_left = self._leaf_cand(hist_left, info.left_sum_g, info.left_sum_h,
                                    info.left_cnt, feature_mask, depth_ok,
                                    lmin, lmax)
        cand_right = self._leaf_cand(hist_right, info.right_sum_g,
                                     info.right_sum_h, info.right_cnt,
                                     feature_mask, depth_ok, rmin, rmax)

        def upd_cand(arr, l_val, r_val):
            return (arr.at[best_leaf].set(
                        jnp.where(do, l_val, arr[best_leaf]))
                       .at[new_leaf].set(
                        jnp.where(do, r_val, arr[new_leaf])))

        new_cand = jax.tree_util.tree_map(upd_cand, state.cand,
                                          cand_left, cand_right)

        # ---- record for host-side tree assembly
        rec = jnp.zeros(NUM_REC_FIELDS, jnp.float32)
        rec = rec.at[REC_VALID].set(dof)
        rec = rec.at[REC_LEAF].set(best_leaf.astype(jnp.float32))
        rec = rec.at[REC_FEATURE].set(info.feature.astype(jnp.float32))
        rec = rec.at[REC_THRESHOLD].set(info.threshold.astype(jnp.float32))
        rec = rec.at[REC_DEFAULT_LEFT].set(info.default_left.astype(jnp.float32))
        rec = rec.at[REC_GAIN].set(best_gain)
        rec = rec.at[REC_LEFT_OUT].set(info.left_output)
        rec = rec.at[REC_RIGHT_OUT].set(info.right_output)
        rec = rec.at[REC_LEFT_CNT].set(info.left_cnt)
        rec = rec.at[REC_RIGHT_CNT].set(info.right_cnt)
        rec = rec.at[REC_INTERNAL_VALUE].set(prev_output)
        rec = rec.at[REC_INTERNAL_CNT].set(state.leaf_cnt[best_leaf])
        rec = rec.at[REC_LEFT_SUM_H].set(info.left_sum_h)
        rec = rec.at[REC_RIGHT_SUM_H].set(info.right_sum_h)
        rec = rec.at[REC_LEFT_SUM_G].set(info.left_sum_g)
        rec = rec.at[REC_RIGHT_SUM_G].set(info.right_sum_g)
        rec = rec.at[REC_IS_CAT].set(info.is_cat.astype(jnp.float32))
        records = state.records.at[step_idx].set(rec)
        rec_cat = state.rec_cat.at[step_idx].set(info.cat_bits)
        rec_i = state.rec_i.at[step_idx].set(
            jnp.stack([lc_bag, c_bag - lc_bag]).astype(jnp.int32))

        return TreeState(
            leaf_id=leaf_id, hist_pool=hist_pool, leaf_sum_g=leaf_sum_g,
            leaf_sum_h=leaf_sum_h, leaf_cnt=leaf_cnt, leaf_output=leaf_output,
            leaf_depth=leaf_depth, cand=new_cand,
            num_leaves=state.num_leaves + do.astype(jnp.int32),
            records=records, rec_cat=rec_cat, rec_i=rec_i,
            leaf_min_c=leaf_min_c, leaf_max_c=leaf_max_c)

    def set_forced_splits(self, forced) -> None:
        """Install the static BFS forced-split list (``forced.py``); must
        be called before the first train (re-wraps the jitted program)."""
        self._forced = list(forced) if forced else None
        self._jit_tree = jax.jit(self._train_tree_fused)

    def _forced_info(self, state: TreeState, fs) -> tuple:
        """_LeafCand row for one forced split (GatherInfoForThreshold)."""
        from .ops.split import K_EPSILON, forced_split_info
        cfg = self.cfg
        leaf = fs.leaf
        sum_g = state.leaf_sum_g[leaf]
        sum_h = state.leaf_sum_h[leaf]
        cnt = state.leaf_cnt[leaf]
        # FixHistogram before the gather, like the scans (see
        # learner_compact.py _forced_candidate_compact)
        hist = self._fix_histogram(state.hist_pool[leaf], sum_g, sum_h, cnt)
        hrow = hist[fs.feature_inner]                       # (B, 3)
        gain, lg, lh, lc, rg, rh, rc, lo, ro, valid = forced_split_info(
            hrow, sum_g, sum_h, cnt,
            threshold=fs.threshold_bin,
            num_bin=int(self.np_num_bin[fs.feature_inner]),
            missing_type=int(self.np_missing[fs.feature_inner]),
            default_bin=int(self.np_default_bin[fs.feature_inner]),
            is_cat=fs.is_cat,
            lambda_l1=float(cfg.lambda_l1), lambda_l2=float(cfg.lambda_l2),
            max_delta_step=float(cfg.max_delta_step),
            min_gain_to_split=float(cfg.min_gain_to_split))
        cb = np.zeros(self.cat_W, np.uint32)
        if fs.is_cat:
            cb[fs.threshold_bin // 32] |= np.uint32(
                1 << (fs.threshold_bin % 32))
        info = _LeafCand(
            gain=gain, feature=jnp.asarray(fs.feature_inner, jnp.int32),
            threshold=jnp.asarray(fs.threshold_bin, jnp.int32),
            default_left=jnp.asarray(not fs.is_cat),
            is_cat=jnp.asarray(fs.is_cat), cat_bits=jnp.asarray(cb),
            left_sum_g=lg, left_sum_h=lh - K_EPSILON, left_cnt=lc,
            right_sum_g=rg, right_sum_h=rh - K_EPSILON, right_cnt=rc,
            left_output=lo, right_output=ro)
        return info, valid

    def _train_tree_fused(self, grad, hess, bag, feature_mask) -> TreeState:
        """The whole leaf-wise growth loop as ONE XLA computation — the
        fusion the reference can't have (its loop is host control flow,
        `serial_tree_learner.cpp:185-218`); on TPU it removes per-split
        dispatch latency entirely.  Records are written at cursor
        ``num_leaves - 1`` so an aborted forced phase leaves no gap."""
        state = self._init_root(grad, hess, bag, feature_mask)
        forced = getattr(self, "_forced", None)
        if forced:
            aborted = jnp.asarray(False)
            for fs in forced:
                info, valid = self._forced_info(state, fs)
                do = valid & ~aborted
                state = self._split_step(state, grad, hess, bag,
                                         feature_mask,
                                         state.num_leaves - 1,
                                         forced=(fs.leaf, info, do))
                aborted = aborted | ~valid

        def cond(st):
            return (st.num_leaves < self.num_leaves) & \
                (jnp.max(st.cand.gain) > 0.0)

        return jax.lax.while_loop(
            cond,
            lambda st: self._split_step(st, grad, hess, bag, feature_mask,
                                        st.num_leaves - 1),
            state)

    # -- host orchestration --------------------------------------------------

    def train_async(self, grad: jax.Array, hess: jax.Array, bag: jax.Array,
                    feature_mask: Optional[jax.Array] = None):
        """Dispatch one tree build; returns device arrays with NO host sync:
        (rec_f, rec_i, rec_cat, leaf_id, leaf_output)."""
        if feature_mask is None:
            feature_mask = jnp.ones(self.num_features, dtype=bool)
        state = self._jit_tree(grad, hess, bag, feature_mask)
        return (state.records, state.rec_i, state.rec_cat, state.leaf_id,
                state.leaf_output)

    def assemble_host(self, rec_f, rec_i, rec_cat=None) -> Tree:
        rec_f = np.asarray(rec_f)
        rec_i = None if rec_i is None else np.asarray(rec_i)
        rec_cat = None if rec_cat is None else np.asarray(rec_cat)
        if bool(getattr(self.cfg, "tpu_vec_assemble", True)) \
                and rec_i is not None:
            tree = self._assemble_vec(rec_f, rec_cat, rec_i)
            if tree is not None:
                return tree
        return self._assemble(rec_f, rec_cat, rec_i)

    def train(self, grad: jax.Array, hess: jax.Array, bag: jax.Array,
              feature_mask: Optional[jax.Array] = None, fused: bool = True
              ) -> Tuple[Tree, jax.Array]:
        """Build one tree; returns (host Tree with unit shrinkage, device
        leaf_id for the score updater)."""
        f = self.num_features
        if feature_mask is None:
            feature_mask = jnp.ones(f, dtype=bool)
        if fused:
            state = self._jit_tree(grad, hess, bag, feature_mask)
        else:
            state = self._jit_init(grad, hess, bag, feature_mask)
            for i in range(self.num_leaves - 1):
                state = self._jit_step(state, grad, hess, bag, feature_mask,
                                       jnp.asarray(i, jnp.int32))
        records = np.asarray(state.records)  # single host sync per tree
        tree = self._assemble(records, np.asarray(state.rec_cat),
                              np.asarray(state.rec_i))
        return tree, state.leaf_id

    def _split_host_tree(self, tree: Tree, r: np.ndarray,
                         cat_bits: Optional[np.ndarray], left_cnt: int,
                         right_cnt: int) -> None:
        """Apply one recorded split to the host tree — numerical via
        ``Tree.split``, categorical via ``Tree.split_categorical`` with the
        bin bitset converted to category values
        (`serial_tree_learner.cpp:727-748`)."""
        fi = int(r[REC_FEATURE])
        mapper = self.data.bin_mappers[fi]
        used_map = self.data.used_feature_map
        common = dict(
            leaf=int(r[REC_LEAF]), feature_inner=fi,
            real_feature=int(used_map[fi]),
            left_value=float(r[REC_LEFT_OUT]),
            right_value=float(r[REC_RIGHT_OUT]),
            left_cnt=left_cnt, right_cnt=right_cnt,
            gain=float(r[REC_GAIN]),
            missing_type=int(self.np_missing[fi]))
        if r[REC_IS_CAT] > 0.5:
            bits = cat_bits
            bins = [bi for bi in range(int(self.np_num_bin[fi]))
                    if (int(bits[bi // 32]) >> (bi % 32)) & 1]
            cats = [int(mapper.bin_2_categorical[bi]) for bi in bins
                    if bi < len(mapper.bin_2_categorical)
                    and int(mapper.bin_2_categorical[bi]) >= 0]
            tree.split_categorical(threshold_bins=bins, threshold_cats=cats,
                                   **common)
        else:
            thr_bin = int(r[REC_THRESHOLD])
            tree.split(threshold_bin=thr_bin,
                       threshold_double=mapper.bin_to_value(thr_bin),
                       default_left=bool(r[REC_DEFAULT_LEFT] > 0.5),
                       **common)
        tree.internal_value[tree.num_leaves - 2] = float(r[REC_INTERNAL_VALUE])

    def _assemble(self, records: np.ndarray,
                  rec_cat: Optional[np.ndarray] = None,
                  rec_i: Optional[np.ndarray] = None) -> Tree:
        tree = Tree(self.num_leaves)
        for i in range(records.shape[0]):
            r = records[i]
            if r[REC_VALID] < 0.5:
                break
            if rec_i is not None:
                lc, rc = int(rec_i[i, 0]), int(rec_i[i, 1])
            else:
                lc = int(round(float(r[REC_LEFT_CNT])))
                rc = int(round(float(r[REC_RIGHT_CNT])))
            self._split_host_tree(
                tree, r, None if rec_cat is None else rec_cat[i],
                left_cnt=lc, right_cnt=rc)
        return tree

    def _thr_value_table(self) -> np.ndarray:
        """(F, B) f64 table of ``mapper.bin_to_value`` for numerical
        features (model-text thresholds), built once per learner."""
        tab = getattr(self, "_np_thr_val", None)
        if tab is None:
            b = max(int(self.np_num_bin.max()), 1)
            tab = np.zeros((self.num_features, b), dtype=np.float64)
            for k, m in enumerate(self.data.bin_mappers):
                if getattr(m, "bin_type", 0) == 0:  # numerical
                    ub = np.asarray(m.bin_upper_bound, dtype=np.float64)
                    tab[k, :min(len(ub), b)] = ub[:b]
            self._np_thr_val = tab
        return tab

    def _assemble_vec(self, records: np.ndarray, rec_cat, rec_i
                      ) -> Optional[Tree]:
        """One numpy pass over the record batch — semantically identical
        to replaying ``Tree.split`` record by record (the sequential
        ``_assemble`` costs ~20 scalar numpy ops per split, 15-25 ms per
        255-leaf tree inside every pipeline flush — round-5 trace).  The
        per-split recurrences vectorize because the record stream is in
        pop order: the node a record creates is its own index, the left
        child keeps the parent's leaf number and the right child gets
        ``num_leaves``; parent/child links reduce to "previous/next
        record touching the same leaf number".  Returns None for trees
        with categorical splits (their bitset bookkeeping is
        order-dependent) — the caller falls back to the sequential path.
        """
        from .tree import K_DEFAULT_LEFT_MASK, Tree as _Tree

        valid = records[:, REC_VALID] > 0.5
        nv = int(np.argmin(valid)) if not valid.all() else len(valid)
        tree = _Tree(self.num_leaves)
        if nv == 0:
            return tree
        r = records[:nv]
        if (r[:, REC_IS_CAT] > 0.5).any():
            return None
        leaves = r[:, REC_LEAF].astype(np.int64)
        iota = np.arange(nv, dtype=np.int64)
        fi = r[:, REC_FEATURE].astype(np.int64)
        thr_bin = r[:, REC_THRESHOLD].astype(np.int64)
        tree.num_leaves = nv + 1
        tree.split_feature_inner[:nv] = fi
        tree.split_feature[:nv] = np.asarray(
            self.data.used_feature_map)[fi]
        gains = r[:, REC_GAIN].astype(np.float64)
        tree.split_gain[:nv] = np.clip(np.nan_to_num(gains, nan=0.0),
                                       -1e300, 1e300)   # Common::AvoidInf
        tree.threshold_in_bin[:nv] = thr_bin
        tree.threshold[:nv] = self._thr_value_table()[fi, thr_bin]
        tree.decision_type[:nv] = (
            (r[:, REC_DEFAULT_LEFT] > 0.5) * K_DEFAULT_LEFT_MASK
            | ((self.np_missing[fi].astype(np.int64) & 3) << 2)
        ).astype(np.int8)
        tree.internal_value[:nv] = r[:, REC_INTERNAL_VALUE]
        lc = rec_i[:nv, 0].astype(np.int64)
        rc = rec_i[:nv, 1].astype(np.int64)
        tree.internal_count[:nv] = lc + rc
        # previous/next record splitting the same leaf number (stable
        # grouping by leaf): the "next" one is where the child pointer
        # lands; the "previous" one (or the right-child creator, record
        # leaf-1) is the parent node
        ordx = np.argsort(leaves, kind="stable")
        lv = leaves[ordx]
        same = lv[1:] == lv[:-1]
        nxt = np.full(nv, -1, np.int64)
        nxt[ordx[:-1][same]] = ordx[1:][same]
        prv = np.full(nv, -1, np.int64)
        prv[ordx[1:][same]] = ordx[:-1][same]
        mask_first = np.r_[True, ~same]
        firsts = np.full(nv + 2, -1, np.int64)
        firsts[lv[mask_first]] = ordx[mask_first]
        # children: the next splitter of the child's leaf number, else
        # the leaf itself (~leaf encoding)
        tree.left_child[:nv] = np.where(nxt >= 0, nxt, ~leaves)
        nxt_r = firsts[iota + 1]
        tree.right_child[:nv] = np.where(nxt_r >= 0, nxt_r, ~(iota + 1))
        # last record touching each leaf number owns its final value/count
        lp = np.full(nv + 1, -1, np.int64)
        np.maximum.at(lp, leaves, iota)
        np.maximum.at(lp, iota + 1, iota)
        tree.leaf_parent[:nv + 1] = lp
        own_left = leaves[lp] == np.arange(nv + 1)
        lval = np.where(own_left, r[lp, REC_LEFT_OUT],
                        r[lp, REC_RIGHT_OUT])
        tree.leaf_value[:nv + 1] = np.nan_to_num(lval, nan=0.0)
        tree.leaf_count[:nv + 1] = np.where(own_left, lc[lp], rc[lp])
        # depths: child depth of record i = 1 + child depth of its parent
        # record (the previous same-leaf splitter, or the right-creator
        # record leaf-1); a ~254-step int loop, not 254 numpy scalar ops
        creator = np.where(leaves > 0, leaves - 1, -1)
        parent_rec = np.maximum(creator, prv).tolist()
        cd = [0] * nv
        for i in range(nv):
            p = parent_rec[i]
            cd[i] = 1 + (cd[p] if p >= 0 else 0)
        cd_np = np.asarray(cd, np.int64)
        tree.leaf_depth[:nv + 1] = cd_np[lp]
        return tree
