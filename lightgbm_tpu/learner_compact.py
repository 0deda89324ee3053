"""Compacted TPU tree learner: leaf-wise growth over leaf-contiguous rows.

This is the O(N log L) redesign of the masked learner in ``learner.py``
(which pays a full-data histogram pass per split — O(N·L) row-visits per
tree).  It is the TPU-native analogue of the reference's ``DataPartition``
(`src/treelearner/data_partition.hpp`): the reference keeps a permuted row
index array so each leaf's rows are contiguous and builds the smaller
child's histogram over just those rows
(`serial_tree_learner.cpp:371-385`); here the PAYLOADS themselves (packed
bin codes, gradient channels, row ids) are kept permuted — TPUs have no
fast random gather, so instead of indices we move the data with a stable
one-bit-key `lax.sort` over the parent's window at every split:

  * rows of leaf ℓ live at positions ``[leaf_start[ℓ], leaf_start[ℓ]+cnt)``
  * a split sorts only that window (keys: before/left/right/after, stable)
  * the smaller child's histogram runs over a power-of-two bucketed
    ``dynamic_slice`` window (``lax.switch`` picks the bucket) through the
    packed-word Pallas kernel; the sibling comes from parent subtraction
    (`feature_histogram.hpp:67`).

Σ window sizes over a tree ≈ Σ min(|left|,|right|) ≈ N·log₂(num_leaves),
the reference CPU budget.  Split semantics (gain math, missing handling,
tie-breaks, min_data/min_hessian limits) are byte-identical to the masked
learner — both call ``ops.split.find_best_splits``.

Per-leaf bookkeeping lives in FUSED matrices (``leaf_f``/``cand_f``/…)
rather than one array per quantity: a split step updates 2 rows of 5
matrices instead of ~30 scalars across ~25 arrays, because with 254
sequential steps inside one XLA program the per-op floor (~3µs) — not
FLOPs — dominates the bookkeeping cost.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .binning import MISSING_NAN, MISSING_ZERO
from .config import Config
from .dataset import _ConstructedDataset
from .learner import (NUM_REC_FIELDS, REC_VALID, TPUTreeLearner, _FeatCand)
from .observability.phases import scope
from .ops.hist_pallas import (build_histogram_packed, pack_bin_words,
                              unpack_bin_words)
from .ops.histogram import _on_tpu, build_histogram_onehot
from .ops.split import find_best_splits
from .tree import Tree

# fused per-leaf state columns (acc dtype)
LF_SUM_G, LF_SUM_H, LF_CNT, LF_OUT, LF_DEPTH, LF_MIN_C, LF_MAX_C = range(7)
NUM_LF = 7
# fused per-leaf best-candidate columns (acc dtype)
CF_GAIN, CF_LSG, CF_LSH, CF_LCNT, CF_RSG, CF_RSH, CF_RCNT, CF_LOUT, \
    CF_ROUT = range(9)
NUM_CF = 9
# int candidate columns; flags bit0 = default_left, bit1 = is_cat
CI_FEAT, CI_THR, CI_FLAGS = range(3)
NUM_CI = 3


class CompactState(NamedTuple):
    bins_p: jax.Array      # (Fw, N) int32 — packed bins, permuted by leaf
    w_p: jax.Array         # (3, N) f32 — (g·bag, h·bag, bag), permuted
    rid_p: jax.Array       # (N,) int32 — original row id at each position
    lid_p: jax.Array       # (N,) int32 — leaf id at each position
    leaf_i: jax.Array      # (L, 2) int32 — [window start, window size]
    leaf_f: jax.Array      # (L, NUM_LF) acc — sums/cnt/output/depth/bounds
    hist_pool: jax.Array   # (L, F, B, 3)
    cand_f: jax.Array      # (L, NUM_CF) acc — per-leaf best split floats
    cand_i: jax.Array      # (L, NUM_CI) int32 — feature/threshold/flags
    cand_b: jax.Array      # (L, W) uint32 — categorical bitsets
    num_leaves: jax.Array
    rec_f: jax.Array       # (L-1, NUM_REC_FIELDS) f32
    rec_i: jax.Array       # (L-1, 2) int32 — exact bagged left/right counts
    rec_cat: jax.Array     # (L-1, W) uint32 — bin bitset of cat splits


def to_row_order(rid_p: jax.Array, values: jax.Array, bound: int
                 ) -> jax.Array:
    """``values`` (int32 in ``[0, bound)``, one a row in partition order)
    back in original row order: ``out[rid_p[i]] = values[i]``, by a sort on
    ``rid_p`` (a permutation of ``arange(n)``, so no sort here needs
    stability, which costs the chip a hidden row-index operand).  Where row
    id and value fit 32 bits together they sort as ONE unsigned word; the
    shapes decide, at trace time."""
    n = rid_p.shape[0]
    bits = (bound - 1).bit_length()
    if (n - 1).bit_length() + bits <= 32:
        word = (rid_p.astype(jnp.uint32) << bits) | values.astype(jnp.uint32)
        low = lax.sort(word, is_stable=False) & jnp.uint32((1 << bits) - 1)
        return low.astype(jnp.int32)
    return lax.sort([rid_p, values], num_keys=1, is_stable=False)[1]


class CompactTPUTreeLearner(TPUTreeLearner):
    """Leaf-wise learner with leaf-contiguous row compaction (see module
    docstring).  Factory slot: `src/treelearner/tree_learner.cpp:9-33`,
    (tree_learner=serial, device_type=tpu)."""

    _supports_bundle = True

    def __init__(self, cfg: Config, data: _ConstructedDataset,
                 hist_backend: str = "auto"):
        super().__init__(cfg, data, hist_backend)
        self.n_pad = int(data.num_data_padded)
        # EFB: histograms and the device row payload live in BUNDLE columns
        # (`efb.py`); the per-feature view is reconstructed at scan time
        # (the sharded subclass opts out — its feature-axis scatter assumes
        # unbundled columns)
        self._bundle = getattr(data, "bundle", None) \
            if self._supports_bundle else None
        if self._bundle is not None:
            bu = self._bundle
            from .dataset import _round_up
            g_pad = _round_up(bu.num_groups, data.FEATURE_TILE)
            self._hist_cols = bu.num_groups
            self._hist_nbins = int(max(self.num_bins_padded,
                                       bu.max_group_bin))
            f_pad = g_pad
            idx, valid, fix = bu.unbundle_maps(
                self.num_features, self.num_bins_padded, self._hist_nbins,
                self.np_num_bin)
            self._ub_idx = jnp.asarray(idx)
            self._ub_valid = jnp.asarray(valid)
            self._ub_fix = jnp.asarray(fix)
            self.f_gcol = jnp.asarray(bu.f_gcol)
            self.f_goff = jnp.asarray(bu.f_off)
            self.f_bundled = jnp.asarray(bu.f_bundled)
        else:
            f_pad = data.bins.shape[0]       # padded to a multiple of 8
            self._hist_cols = self.num_features
            self._hist_nbins = self.num_bins_padded
        assert f_pad % 4 == 0, f_pad
        self.fw = f_pad // 4
        self._bins_packed = None             # packed device array, lazy
        # power-of-two window buckets, smallest..largest(=N); the Pallas
        # kernel requires window sizes that are multiples of 1024
        mw = max(int(cfg.tpu_min_window), 1024)
        mw = 1 << (mw - 1).bit_length()  # round up to a power of two
        sizes = []
        s0 = mw
        while s0 < self.n_pad:
            sizes.append(s0)
            s0 *= 2
        sizes.append(self.n_pad)
        self._win_sizes = sizes
        self._win_sizes_arr = jnp.asarray(sizes, dtype=jnp.int32)
        self._use_pallas = self._kernels_fit(hist_backend, self.n_pad)
        prec_map = {"bf16x2": 2, "bf16x3": 3, "highest": 0}
        if cfg.tpu_hist_precision not in prec_map:
            raise ValueError(f"tpu_hist_precision must be one of "
                             f"{sorted(prec_map)}, got {cfg.tpu_hist_precision}")
        self._hist_nterms = prec_map[cfg.tpu_hist_precision]
        self._sort_cutoff = int(cfg.tpu_sort_cutoff)
        self._acc = jnp.float64 if self.hist_dp else jnp.float32
        # quantized-gradient mode (ops/quant.py) is a WAVE-learner gate
        # (_init_wave_dims); the default here keeps the shared histogram
        # branches on the f32 path for the sequential compact learner
        self._quant = False
        self._q_inv = None      # (1/sg, 1/sh) — traced, set per tree
        self._q_cnt = None      # 1/(sh·m̄) count rescale — traced
        self._q_mbar = None     # m̄ mean hess mass per bagged row
        self._jit_tree_c = jax.jit(self._train_tree_compact)

    def _kernels_fit(self, hist_backend: str, rows: int) -> bool:
        """Whether the Pallas histogram kernels run over a row axis of
        ``rows`` (the whole table; a shard under a mesh)."""
        return (hist_backend in ("auto", "pallas") and _on_tpu()
                and not self.hist_dp and rows % 1024 == 0)

    # -- packed bins ---------------------------------------------------------

    def bins_packed(self) -> jax.Array:
        if self._bins_packed is None:
            if self._bundle is not None:
                src = jnp.asarray(self._bundle.encode(self.data))
            else:
                src = self.data.device_bins()
            packed = pack_bin_words(src)
            if isinstance(packed, jax.core.Tracer):
                return packed  # called under trace — don't cache the tracer
            self._bins_packed = packed
        return self._bins_packed

    def _rows_len(self) -> int:
        """Length of the row axis the window branches slice (the LOCAL
        shard length under the sharded learner)."""
        return self.n_pad

    def _sync_counts(self, lc_bag, c_bag):
        """Bagged split counts; the sharded learner psums local counts."""
        return lc_bag, c_bag

    def _sync_counts3(self, cnt3):
        """Wave-learner (3, W) member counts [left rows, left bagged,
        total bagged]; the sharded learner psums the BAGGED rows only
        (row 0 is local window geometry)."""
        return cnt3

    def _global_scalar(self, v):
        """Scalar reduction seam; the sharded learner psums."""
        return v

    def _global_max(self, v):
        """Elementwise max-reduction seam (quantization scale derivation);
        the sharded learner pmaxes."""
        return v

    def _global_row_offset(self):
        """This shard's offset into the GLOBAL row order — the stateless
        stochastic-rounding hash keys on global row indices so every
        device quantizes its rows exactly as the serial learner would."""
        return jnp.int32(0)

    def _reduce_hist(self, local_hist):
        """Histogram exchange seam; the sharded learner reduce-scatters."""
        return local_hist

    def _reduce_hist_batch(self, local_hists):
        """Batched (K, F, B, 3) histogram exchange seam — ONE collective
        for K stacked member histograms (the sharded learner
        psum_scatters over the feature axis); identity when local."""
        return local_hists

    def _child_best_rows(self, hist_left, hist_right, crow_f, feature_mask,
                         depth_ok, constraints):
        """Children's best-split rows; the sharded learner scans feature
        slices and merges globally."""
        return self._cand_rows_pair(hist_left, hist_right, crow_f,
                                    feature_mask, depth_ok, constraints)

    # -- bucket helpers ------------------------------------------------------

    def _bucket_idx(self, cnt):
        """Index of the smallest window size >= cnt."""
        return jnp.sum(cnt > self._win_sizes_arr).astype(jnp.int32)

    # -- windowed histogram --------------------------------------------------

    def _make_hist_branch(self, S: int):
        fw, f, b = self.fw, self._hist_cols, self._hist_nbins
        n = self._rows_len()

        def branch(bins_p, w_p, lid_p, start, cnt, leaf):
            sa = jnp.clip(start, 0, n - S).astype(jnp.int32)
            off = (start - sa).astype(jnp.int32)
            bw = lax.dynamic_slice(bins_p, (jnp.int32(0), sa), (fw, S))
            ww = lax.dynamic_slice(w_p, (jnp.int32(0), sa), (3, S))
            lid = lax.dynamic_slice(lid_p, (sa,), (S,))
            pos = jnp.arange(S, dtype=jnp.int32)
            # leaf-id equality folds in the mask-mode bottom of the tree,
            # where windows are frozen and a leaf's rows are scattered
            # within its ancestor's window
            m = (pos >= off) & (pos < off + cnt) & (lid == leaf)
            wm = ww * m[None, :].astype(ww.dtype)
            if self._quant:
                # quantized lanes: TWO channels ride the contraction and
                # the count channel is synthesized as Σhq/m̄ = Σhd ·
                # (1/(sh·m̄)) (normalized hessian mass — see ops/quant.py);
                # _q_cnt is a trace-time attribute set per boosting round
                if self._use_pallas:
                    h = build_histogram_packed(bw, wm, num_bins=b,
                                               quant=True)[:f]
                else:
                    bu = unpack_bin_words(bw, f)
                    h2 = build_histogram_onehot(bu, wm[:2], num_bins=b)
                    h = jnp.concatenate([h2, h2[:, :, 1:2]], axis=2)
                return h * jnp.stack([jnp.float32(1.0), jnp.float32(1.0),
                                      self._q_cnt])
            if self._use_pallas:
                h = build_histogram_packed(bw, wm, num_bins=b,
                                           nterms=self._hist_nterms)[:f]
            else:
                bu = unpack_bin_words(bw, f)
                h = build_histogram_onehot(bu, wm, num_bins=b, dp=self.hist_dp)
            return h

        return branch

    # -- windowed partition --------------------------------------------------

    def _make_partition_branch(self, S: int, sort_mode: bool):
        """One bucket's ``DataPartition::Split``.

        sort_mode=True (windows above ``tpu_sort_cutoff``): a stable
        one-bit-key lax.sort physically compacts the two children into
        adjacent windows.  sort_mode=False (the bottom of the tree): the
        window is FROZEN — only the leaf-id lane is rewritten elementwise
        and both children inherit the parent's window; histogram masking by
        leaf id replaces physical compaction.  Bitonic sorts at small sizes
        are all fixed stage latency, so skipping them wins even though
        bottom histograms then scan the frozen (larger) window.
        Returns (bins_p, w_p, rid_p, lid_p, ls, lw, rs, rw, lc_bag, c_bag).
        """
        fw, n = self.fw, self._rows_len()

        def branch(bins_p, w_p, rid_p, lid_p, s, c, leaf, feat, thr, dleft,
                   is_cat, cat_bits, new_leaf, do):
            sa = jnp.clip(s, 0, n - S).astype(jnp.int32)
            off = (s - sa).astype(jnp.int32)
            bw = lax.dynamic_slice(bins_p, (jnp.int32(0), sa), (fw, S))
            ww = lax.dynamic_slice(w_p, (jnp.int32(0), sa), (3, S))
            lid = lax.dynamic_slice(lid_p, (sa,), (S,))
            pos = jnp.arange(S, dtype=jnp.int32)
            in_seg = (pos >= off) & (pos < off + c) & (lid == leaf)
            # decision on the split feature (NumericalDecisionInner,
            # `tree.h:233-249`; CategoricalDecisionInner `tree.h:270-277`)
            # — unpack the one feature's (or its bundle's) byte lane
            col = self.f_gcol[feat] if self._bundle is not None else feat
            word = lax.dynamic_slice(bw, (col // 4, jnp.int32(0)), (1, S))[0]
            code = (word >> ((col % 4) * 8)) & 0xFF
            if self._bundle is not None:
                # bundle code → this feature's bin (out-of-range codes mean
                # another member was active → this feature sits at default)
                boff = self.f_goff[feat]
                d = self.f_default_bin[feat]
                r = code - boff
                in_r = (r >= 0) & (r < self.f_num_bin[feat] - 1)
                dec = r + (r >= d).astype(r.dtype)
                frow = jnp.where(self.f_bundled[feat],
                                 jnp.where(in_r, dec, d), code)
            else:
                frow = code
            mt = self.f_missing[feat]
            db = self.f_default_bin[feat]
            nb = self.f_num_bin[feat]
            is_missing = ((mt == MISSING_ZERO) & (frow == db)) | \
                         ((mt == MISSING_NAN) & (frow == nb - 1))
            go_left = jnp.where(is_missing, dleft, frow <= thr)
            if self.has_categorical:
                cat_left = (cat_bits[frow >> 5]
                            >> (frow & 31).astype(jnp.uint32)) & 1
                go_left = jnp.where(is_cat, cat_left.astype(bool), go_left)
            segl = in_seg & go_left
            bag = ww[2] > 0.5
            lc_bag = jnp.sum((segl & bag).astype(jnp.int32)).astype(jnp.int32)
            c_bag = jnp.sum((in_seg & bag).astype(jnp.int32)).astype(jnp.int32)

            if sort_mode:
                rid = lax.dynamic_slice(rid_p, (sa,), (S,))
                key = jnp.where(in_seg,
                                jnp.where(go_left, 1, 2),
                                jnp.where(pos < off, 0, 3)).astype(jnp.int32)
                key = jnp.where(do, key, 0)
                ops = ([key] + [bw[i] for i in range(fw)]
                       + [ww[0], ww[1], ww[2], rid, lid])
                sd = lax.sort(ops, num_keys=1, is_stable=True)
                bw2 = jnp.stack(sd[1:1 + fw])
                ww2 = jnp.stack(sd[1 + fw:4 + fw])
                rid2, lid2 = sd[4 + fw], sd[5 + fw]
                lc_w = jnp.sum(segl.astype(jnp.int32)).astype(jnp.int32)
                in_right = (pos >= off + lc_w) & (pos < off + c)
                lid2 = jnp.where(do & in_right, new_leaf, lid2)
                bins_p = lax.dynamic_update_slice(bins_p, bw2,
                                                  (jnp.int32(0), sa))
                w_p = lax.dynamic_update_slice(w_p, ww2, (jnp.int32(0), sa))
                rid_p = lax.dynamic_update_slice(rid_p, rid2, (sa,))
                lid_p = lax.dynamic_update_slice(lid_p, lid2, (sa,))
                ls, lw = s, lc_w
                rs, rw = s + lc_w, c - lc_w
            else:
                lid2 = jnp.where(do & in_seg & ~go_left, new_leaf, lid)
                lid_p = lax.dynamic_update_slice(lid_p, lid2, (sa,))
                ls = rs = s
                lw = rw = c
            return (bins_p, w_p, rid_p, lid_p, ls, lw, rs, rw, lc_bag,
                    c_bag)

        return branch

    # -- EFB unbundling ------------------------------------------------------

    def _unbundle_hist(self, hist_g, sum_g, sum_h, cnt):
        """(G, Bg, 3) bundle histogram → (F, Bf, 3) per-feature view; the
        default-bin entry of each bundled member is rebuilt from the leaf
        totals (``Dataset::FixHistogram``)."""
        flat = hist_g.reshape(-1, 3)
        view = flat[self._ub_idx]
        view = view * self._ub_valid[..., None].astype(view.dtype)
        dt = view.dtype
        totals = jnp.stack([sum_g.astype(dt), sum_h.astype(dt),
                            cnt.astype(dt)])
        dflt = totals[None, :] - jnp.sum(view, axis=1)
        bsel = (jnp.arange(view.shape[1])[None, :]
                == self.f_default_bin[:, None]) & self._ub_fix[:, None]
        return jnp.where(bsel[..., None], dflt[:, None, :], view)

    def _feature_cands(self, hist, sum_g, sum_h, cnt, feature_mask,
                       min_c=None, max_c=None):
        if self._bundle is not None:
            hist = self._unbundle_hist(hist, sum_g, sum_h, cnt)
        return super()._feature_cands(hist, sum_g, sum_h, cnt, feature_mask,
                                      min_c, max_c)

    # -- per-leaf candidates (packed rows) -----------------------------------

    def _pack_cand_rows(self, cands: _FeatCand, depth_ok):
        """(K, F)-batched per-feature candidates → per-leaf best rows
        ((K, NUM_CF) acc, (K, NUM_CI) int32, (K, W) uint32); argmax over
        features with lowest index winning ties
        (`serial_tree_learner.cpp:505-520`)."""
        best_f = jnp.argmax(cands.gain, axis=1).astype(jnp.int32)   # (K,)
        pick = lambda a: jnp.take_along_axis(a, best_f[:, None], axis=1)[:, 0]
        gain = jnp.where(depth_ok, pick(cands.gain), -jnp.inf)
        cf = jnp.stack([
            gain.astype(self._acc),
            pick(cands.left_sum_g), pick(cands.left_sum_h),
            pick(cands.left_cnt),
            pick(cands.right_sum_g), pick(cands.right_sum_h),
            pick(cands.right_cnt),
            pick(cands.left_output), pick(cands.right_output)],
            axis=-1).astype(self._acc)
        flags = pick(cands.default_left).astype(jnp.int32) \
            + 2 * pick(cands.is_cat).astype(jnp.int32)
        ci = jnp.stack([best_f, pick(cands.threshold), flags], axis=-1)
        cb = jnp.take_along_axis(cands.cat_bits, best_f[:, None, None],
                                 axis=1)[:, 0]
        return cf, ci.astype(jnp.int32), cb

    def _cand_rows_pair(self, hist_l, hist_r, crow_f, feature_mask,
                        depth_ok, constraints=None):
        """Best-split rows for both children in one batched scan."""
        hist2 = jnp.stack([hist_l, hist_r])
        sg = jnp.stack([crow_f[CF_LSG], crow_f[CF_RSG]])
        sh = jnp.stack([crow_f[CF_LSH], crow_f[CF_RSH]])
        cn = jnp.stack([crow_f[CF_LCNT], crow_f[CF_RCNT]])

        if constraints is not None:
            mins, maxs = constraints
            cands = jax.vmap(
                lambda h, g, hh, c, mn, mx: self._feature_cands(
                    h, g, hh, c, feature_mask, mn, mx)
            )(hist2, sg, sh, cn, mins, maxs)
        else:
            cands = jax.vmap(
                lambda h, g, hh, c: self._feature_cands(h, g, hh, c,
                                                        feature_mask)
            )(hist2, sg, sh, cn)
        return self._pack_cand_rows(cands, depth_ok)

    # -- root ----------------------------------------------------------------

    def _init_root_compact(self, bins_p, grad, hess, bag, feature_mask
                           ) -> CompactState:
        n, f, b, L = self.n_pad, self.num_features, self.num_bins_padded, \
            self.num_leaves
        acc = self._acc
        w = jnp.stack([grad * bag, hess * bag, bag], axis=0)
        lid0 = jnp.zeros(n, jnp.int32)
        root_hist = self._hist_branches[-1](bins_p, w, lid0, jnp.int32(0),
                                            jnp.int32(n), jnp.int32(0))
        sum_g = jnp.sum((grad * bag).astype(acc))
        sum_h = jnp.sum((hess * bag).astype(acc))
        cnt = jnp.sum(bag.astype(acc))
        md = int(self.cfg.max_depth)
        depth_ok = jnp.asarray([True if md <= 0 else md > 0])
        cands = jax.vmap(
            lambda h, g, hh, c: self._feature_cands(h, g, hh, c, feature_mask)
        )(root_hist[None], sum_g[None], sum_h[None], cnt[None])
        cf_root, ci_root, cb_root = self._pack_cand_rows(cands, depth_ok)

        root_lf = jnp.asarray(
            [0.0, 0.0, 0.0, 0.0, 0.0, -jnp.inf, jnp.inf], acc)
        root_lf = root_lf.at[LF_SUM_G].set(sum_g).at[LF_SUM_H].set(sum_h) \
                         .at[LF_CNT].set(cnt)
        return CompactState(
            bins_p=bins_p,
            w_p=w,
            rid_p=jnp.arange(n, dtype=jnp.int32),
            lid_p=lid0,
            leaf_i=jnp.zeros((L, 2), jnp.int32).at[0, 1].set(n),
            leaf_f=jnp.zeros((L, NUM_LF), acc)
                      .at[:, LF_MIN_C].set(-jnp.inf)
                      .at[:, LF_MAX_C].set(jnp.inf)
                      .at[0].set(root_lf),
            hist_pool=jnp.zeros((L,) + root_hist.shape, root_hist.dtype)
                         .at[0].set(root_hist),
            cand_f=jnp.zeros((L, NUM_CF), acc)
                      .at[:, CF_GAIN].set(-jnp.inf)
                      .at[0].set(cf_root[0]),
            cand_i=jnp.zeros((L, NUM_CI), jnp.int32).at[0].set(ci_root[0]),
            cand_b=jnp.zeros((L, self.cat_W), jnp.uint32).at[0]
                      .set(cb_root[0]),
            num_leaves=jnp.asarray(1, jnp.int32),
            rec_f=jnp.zeros((L - 1, NUM_REC_FIELDS), jnp.float32),
            rec_i=jnp.zeros((L - 1, 2), jnp.int32),
            rec_cat=jnp.zeros((L - 1, self.cat_W), jnp.uint32))

    # -- one split -----------------------------------------------------------

    def _split_step_compact(self, state: CompactState, feature_mask,
                            step_idx, forced=None) -> CompactState:
        """One split.  ``forced=(leaf, crow_f, crow_i, crow_b, do)``
        replaces best-gain selection with a forced split
        (`serial_tree_learner.cpp:543-663`); everything downstream —
        partition, smaller-child histogram, children bookkeeping, record
        emission — is shared."""
        cfg = self.cfg
        self._coll_ctx = ("split_step", "split")
        if forced is None:
            best_leaf = jnp.argmax(state.cand_f[:, CF_GAIN]) \
                .astype(jnp.int32)
            crow_f = state.cand_f[best_leaf]      # (NUM_CF,) acc
            crow_i = state.cand_i[best_leaf]      # (NUM_CI,) int32
            crow_b = state.cand_b[best_leaf]      # (W,) uint32
            # the leaf-budget guard matters for fixed-trip callers (the
            # sharded fori_loop runs L-1 iterations regardless of how many
            # forced splits preceded); the serial while_loop's condition
            # makes it redundant there
            do = (crow_f[CF_GAIN] > 0.0) & \
                (state.num_leaves < self.num_leaves)
        else:
            best_leaf, crow_f, crow_i, crow_b, do = forced
            best_leaf = jnp.asarray(best_leaf, jnp.int32)
        new_leaf = state.num_leaves
        idx2 = jnp.stack([best_leaf, new_leaf])
        lrow_i = state.leaf_i[best_leaf]
        lrow_f = state.leaf_f[best_leaf]
        best_gain = crow_f[CF_GAIN]
        feat = crow_i[CI_FEAT]
        thr = crow_i[CI_THR]
        dleft = (crow_i[CI_FLAGS] & 1) == 1
        is_cat = (crow_i[CI_FLAGS] & 2) == 2
        s = lrow_i[0]
        c = lrow_i[1]

        # ---- partition the parent's window (DataPartition::Split)
        pidx = self._bucket_idx(c)
        bins_p, w_p, rid_p, lid_p, ls, lw, rs, rw, lc_bag, c_bag = \
            lax.switch(
                pidx, self._partition_branches, state.bins_p, state.w_p,
                state.rid_p, state.lid_p, s, c, best_leaf, feat, thr, dleft,
                is_cat, crow_b, new_leaf, do)
        lc_bag, c_bag = self._sync_counts(lc_bag, c_bag)

        # ---- smaller-child histogram + sibling subtraction
        # (`serial_tree_learner.cpp:371-385`); the smaller child is chosen by
        # BAGGED counts like the reference (left_cnt <= right_cnt), while the
        # slice itself is that child's window (mask-mode children share the
        # parent's frozen window and are selected by leaf id)
        left_smaller = lc_bag <= (c_bag - lc_bag)
        small_leaf = jnp.where(left_smaller, best_leaf, new_leaf)
        small_start = jnp.where(left_smaller, ls, rs)
        small_cnt = jnp.where(left_smaller, lw, rw)
        hidx = self._bucket_idx(jnp.maximum(small_cnt, 1))
        hist_small = self._reduce_hist(lax.switch(
            hidx, self._hist_branches, bins_p, w_p, lid_p, small_start,
            small_cnt, small_leaf))
        hist_parent = state.hist_pool[best_leaf]
        hist_large = hist_parent - hist_small
        hist_left = jnp.where(left_smaller, hist_small, hist_large)
        hist_right = jnp.where(left_smaller, hist_large, hist_small)

        def upd2(arr, row_l, row_r):
            """Write the two children's rows at [best_leaf, new_leaf] in one
            scatter; exact no-op when the step is disabled."""
            orig = arr[idx2]
            rows = jnp.stack([row_l, row_r])
            return arr.at[idx2].set(jnp.where(do, rows, orig))

        hist_pool = upd2(state.hist_pool, hist_left, hist_right)

        # ---- children bookkeeping rows.  Forced splits mirror the
        # reference's inconsistency verbatim: child SUMS come from
        # GatherInfoForThreshold (right = bins >= thr) while child COUNTS
        # come from the actual partition (left = bins <= thr) — the
        # reference's ``LeafSplits::Init(leaf, data_partition_, sum_g,
        # sum_h)`` reads ``leaf_count`` from the partition
        # (`leaf_splits.hpp:40-52`), so its next scans run with partition
        # counts against GatherInfo sums.
        child_depth = lrow_f[LF_DEPTH] + 1.0
        if forced is not None:
            crow_f = crow_f.at[CF_LCNT].set(lc_bag.astype(self._acc)) \
                           .at[CF_RCNT].set((c_bag - lc_bag)
                                            .astype(self._acc))
        lout = crow_f[CF_LOUT]
        rout = crow_f[CF_ROUT]
        pmin = lrow_f[LF_MIN_C]
        pmax = lrow_f[LF_MAX_C]
        if self.has_monotone:
            mono_t = jnp.where(is_cat, 0, self.f_monotone[feat])
            mid = ((lout + rout) / 2.0).astype(self._acc)
            lmin = jnp.where(mono_t < 0, mid, pmin)
            lmax = jnp.where(mono_t > 0, mid, pmax)
            rmin = jnp.where(mono_t > 0, mid, pmin)
            rmax = jnp.where(mono_t < 0, mid, pmax)
            constraints = (jnp.stack([lmin, rmin]), jnp.stack([lmax, rmax]))
        else:
            lmin = rmin = pmin
            lmax = rmax = pmax
            constraints = None
        lf_l = jnp.stack([crow_f[CF_LSG], crow_f[CF_LSH], crow_f[CF_LCNT],
                          lout, child_depth, lmin, lmax])
        lf_r = jnp.stack([crow_f[CF_RSG], crow_f[CF_RSH], crow_f[CF_RCNT],
                          rout, child_depth, rmin, rmax])
        leaf_f = upd2(state.leaf_f, lf_l, lf_r)
        leaf_i = upd2(
            state.leaf_i,
            jnp.stack([ls, lw]).astype(jnp.int32),
            jnp.stack([rs, rw]).astype(jnp.int32))

        # ---- children's best splits (with monotone constraint propagation)
        md = int(cfg.max_depth)
        depth_ok = jnp.asarray([True, True]) if md <= 0 \
            else jnp.stack([child_depth < md] * 2)
        cf_rows, ci_rows, cb_rows = self._child_best_rows(
            hist_left, hist_right, crow_f, feature_mask, depth_ok,
            constraints)
        cand_f = upd2(state.cand_f, cf_rows[0], cf_rows[1])
        cand_i = upd2(state.cand_i, ci_rows[0], ci_rows[1])
        cand_b = upd2(state.cand_b, cb_rows[0], cb_rows[1])

        # ---- record for host-side tree assembly (field order = REC_*)
        rec = jnp.stack([
            do.astype(self._acc), best_leaf.astype(self._acc),
            feat.astype(self._acc), thr.astype(self._acc),
            dleft.astype(self._acc), best_gain,
            lout, rout, crow_f[CF_LCNT], crow_f[CF_RCNT],
            lrow_f[LF_OUT], lrow_f[LF_CNT],
            crow_f[CF_LSH], crow_f[CF_RSH],
            crow_f[CF_LSG], crow_f[CF_RSG],
            is_cat.astype(self._acc)]).astype(jnp.float32)
        rec_f = state.rec_f.at[step_idx].set(rec)
        rec_i = state.rec_i.at[step_idx].set(
            jnp.stack([lc_bag, c_bag - lc_bag]).astype(jnp.int32))
        rec_cat = state.rec_cat.at[step_idx].set(crow_b)

        return CompactState(
            bins_p=bins_p, w_p=w_p, rid_p=rid_p, lid_p=lid_p,
            leaf_i=leaf_i, leaf_f=leaf_f, hist_pool=hist_pool,
            cand_f=cand_f, cand_i=cand_i, cand_b=cand_b,
            num_leaves=state.num_leaves + do.astype(jnp.int32),
            rec_f=rec_f, rec_i=rec_i, rec_cat=rec_cat)

    # -- forced splits (`serial_tree_learner.cpp:543-663`) -------------------

    def set_forced_splits(self, forced) -> None:
        """Install the static BFS forced-split list (``forced.py``); must be
        called before the first ``train_async`` (it re-wraps the jitted
        tree program)."""
        self._forced = list(forced) if forced else None
        self._jit_tree_c = jax.jit(self._train_tree_compact)

    def _forced_hrow(self, state: CompactState, fs, sum_g, sum_h, cnt):
        """FIXED (B, 3) histogram row of the forced feature at the target
        leaf.  Seam for the sharded learners, whose pools hold feature
        SLICES (data/feature-parallel) or local-unreduced histograms
        (voting) — they fetch/reduce the one row and fix it alone."""
        hist = state.hist_pool[fs.leaf]
        if self._bundle is not None:
            hist = self._unbundle_hist(hist, sum_g, sum_h, cnt)
        # the reference FixHistograms before GatherInfoForThreshold
        # (`serial_tree_learner.cpp:486` runs inside the ForceSplits loop's
        # FindBestSplits) — forced chains must see the same default-bin
        # reconstruction the scans do
        hist = self._fix_histogram(hist, sum_g, sum_h, cnt)
        return hist[fs.feature_inner]                      # (B, 3), static f

    def _forced_candidate_compact(self, state: CompactState, fs):
        """Candidate rows for one forced split from the target leaf's
        pooled histogram (GatherInfoForThreshold semantics)."""
        from .ops.split import K_EPSILON, forced_split_info
        cfg = self.cfg
        leaf = fs.leaf
        lrow = state.leaf_f[leaf]
        sum_g, sum_h, cnt = lrow[LF_SUM_G], lrow[LF_SUM_H], lrow[LF_CNT]
        hrow = self._forced_hrow(state, fs, sum_g, sum_h, cnt)
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
        acc = self._acc
        crow_f = jnp.stack([gain, lg, lh - K_EPSILON, lc, rg,
                            rh - K_EPSILON, rc, lo, ro]).astype(acc)
        flags = 2 if fs.is_cat else 1     # numerical: default_left=True
        crow_i = jnp.asarray([fs.feature_inner, fs.threshold_bin, flags],
                             jnp.int32)
        cb = np.zeros(self.cat_W, np.uint32)
        if fs.is_cat:
            cb[fs.threshold_bin // 32] |= np.uint32(
                1 << (fs.threshold_bin % 32))
        return crow_f, crow_i, jnp.asarray(cb), valid

    def _forced_phase_compact(self, state: CompactState, feature_mask
                              ) -> CompactState:
        """Unrolled BFS of the forced-split tree before best-gain growth;
        an invalid forced split aborts the remaining queue exactly like the
        reference's break (`serial_tree_learner.cpp:612-616`)."""
        forced = getattr(self, "_forced", None)
        if not forced:
            return state
        aborted = jnp.asarray(False)
        for fs in forced:
            crow_f, crow_i, crow_b, valid = \
                self._forced_candidate_compact(state, fs)
            do = valid & ~aborted
            state = self._split_step_compact(
                state, feature_mask, state.num_leaves - 1,
                forced=(fs.leaf, crow_f, crow_i, crow_b, do))
            aborted = aborted | ~valid
        return state

    # -- whole tree ----------------------------------------------------------

    def _train_tree_compact(self, bins_p, grad, hess, bag, feature_mask):
        # bins arrive as an ARGUMENT, not a closure constant — embedded
        # constants ship with every (remote) compile request
        self._ledger.begin_trace()
        self._hist_branches = [self._make_hist_branch(S)
                               for S in self._win_sizes]
        self._partition_branches = [
            self._make_partition_branch(S, sort_mode=S > self._sort_cutoff)
            for S in self._win_sizes]
        with scope("root"):
            state = self._init_root_compact(bins_p, grad, hess, bag,
                                            feature_mask)
            state = self._forced_phase_compact(state, feature_mask)

        # records are written at cursor ``num_leaves - 1`` (number of
        # successful splits so far), so an aborted forced phase can't leave
        # an invalid-record gap that truncates host assembly
        def cond(st):
            return (st.num_leaves < self.num_leaves) & \
                (jnp.max(st.cand_f[:, CF_GAIN]) > 0.0)

        with scope("grow"):
            state = jax.lax.while_loop(
                cond,
                lambda st: self._split_step_compact(st, feature_mask,
                                                    st.num_leaves - 1),
                state)
        # leaf partition in ORIGINAL row order for the score updater
        with scope("emit"):
            leaf_id = to_row_order(state.rid_p, state.lid_p, self.num_leaves)
            leaf_output = state.leaf_f[:, LF_OUT].astype(jnp.float32)
        return (state.rec_f, state.rec_i, state.rec_cat, leaf_id,
                leaf_output)

    # -- host orchestration --------------------------------------------------

    def train_async(self, grad: jax.Array, hess: jax.Array, bag: jax.Array,
                    feature_mask: Optional[jax.Array] = None):
        """Dispatch one tree build; returns device arrays with NO host sync:
        (rec_f, rec_i, rec_cat, leaf_id, leaf_output)."""
        if feature_mask is None:
            feature_mask = jnp.ones(self.num_features, dtype=bool)
        return self._jit_tree_c(self.bins_packed(), grad, hess, bag,
                                feature_mask)

    def assemble_host(self, rec_f, rec_i, rec_cat=None) -> Tree:
        return self._assemble_compact(
            np.asarray(rec_f), np.asarray(rec_i),
            None if rec_cat is None else np.asarray(rec_cat))

    def train(self, grad: jax.Array, hess: jax.Array, bag: jax.Array,
              feature_mask: Optional[jax.Array] = None, fused: bool = True
              ) -> Tuple[Tree, jax.Array]:
        rec_f, rec_i, rec_cat, leaf_id, _ = self.train_async(
            grad, hess, bag, feature_mask)
        tree = self.assemble_host(rec_f, rec_i, rec_cat)
        return tree, leaf_id

    def _assemble_compact(self, rec_f: np.ndarray, rec_i: np.ndarray,
                          rec_cat: Optional[np.ndarray] = None) -> Tree:
        tree = Tree(self.num_leaves)
        for i in range(rec_f.shape[0]):
            r = rec_f[i]
            if r[REC_VALID] < 0.5:
                break
            self._split_host_tree(
                tree, r, None if rec_cat is None else rec_cat[i],
                left_cnt=int(rec_i[i, 0]), right_cnt=int(rec_i[i, 1]))
        return tree


def create_tree_learner(cfg: Config, data: _ConstructedDataset,
                        hist_backend: str = "auto"):
    """(tree_learner, device) → learner, the analogue of
    ``TreeLearner::CreateTreeLearner`` (`src/treelearner/tree_learner.cpp:9-33`).

    The frontier-wave learner (`learner_wave.py`) is the default where
    eligible; the sequential compact learner covers the rest of serial mode;
    the masked learner remains for >256-bin datasets (bin codes don't pack
    4-per-word) and for the GSPMD parallel modes (whose sharding drapes over
    the masked learner's full-row passes).
    """
    mode = cfg.tpu_learner
    explicit = mode != "auto"
    verbose = int(getattr(cfg, "verbosity", 1))
    if mode == "auto":
        mode = "wave"
    reason = None
    if mode == "wave" and cfg.forcedsplits_filename:
        # forced splits ride the sequential learners' split-step machinery;
        # the compact learner builds the identical tree, just without
        # frontier batching
        if verbose >= 1:
            print("[lightgbm_tpu] forcedsplits_filename set: using the "
                  "sequential compact learner (identical trees)")
        mode = "compact"
    if mode == "wave":
        from .learner_wave import WaveTPUTreeLearner, wave_ineligible_reason
        reason = wave_ineligible_reason(cfg, data)
        if reason is None:
            return WaveTPUTreeLearner(cfg, data, hist_backend)
        mode = "compact"
        if explicit:
            import warnings
            warnings.warn(
                f"tpu_learner=wave was requested but is ineligible "
                f"({reason}); falling back to the sequential compact "
                f"learner")
        elif verbose >= 1:
            print(f"[lightgbm_tpu] wave learner ineligible ({reason}); "
                  f"using the sequential compact learner")
    if mode == "compact":
        if data.max_num_bin > 256 or cfg.tree_learner not in ("serial",):
            why = (f"max_num_bin={data.max_num_bin} > 256"
                   if data.max_num_bin > 256
                   else f"tree_learner={cfg.tree_learner}")
            if explicit:
                import warnings
                warnings.warn(f"tpu_learner=compact was requested but is "
                              f"ineligible ({why}); falling back to the "
                              f"masked learner")
            elif verbose >= 1:
                print(f"[lightgbm_tpu] compact learner ineligible ({why}); "
                      f"using the masked learner")
            mode = "masked"
    if mode == "compact":
        return CompactTPUTreeLearner(cfg, data, hist_backend)
    return TPUTreeLearner(cfg, data, hist_backend)
