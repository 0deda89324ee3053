"""Data-parallel compact learner: shard_map + psum_scatter over a mesh.

TPU-native re-design of ``DataParallelTreeLearner``
(`src/treelearner/data_parallel_tree_learner.cpp:49-254`): every device owns
a row shard and keeps the compact learner's leaf-contiguous layout over its
LOCAL rows (partition sorts are local); the two cross-device exchanges per
split mirror the reference's wire protocol exactly:

  * histograms: local windowed histogram → ``lax.psum_scatter`` over the
    (padded) feature axis, so each device sums and then SCANS a feature
    slice — the reference's ``ReduceScatter`` +
    ``HistogramBinEntry::SumReducer`` (`data_parallel_tree_learner.cpp:
    146-161`), riding ICI instead of sockets.
  * best split: each device packs its feature-slice winner into a tiny
    fixed-width record, ``lax.all_gather`` + argmax replaces
    ``SyncUpGlobalBestSplit`` (`parallel_tree_learner.h:186-209`); ties
    break toward the lowest global feature index because shard slices are
    contiguous and ascending in the axis index.

Leaf sums/counts are ``psum``-ed; the tiny replicated record stream drives
identical host tree assembly on every process.  The whole tree builds
inside ONE ``shard_map``-ped jit, so XLA schedules collectives alongside
local compute; under a multi-host mesh the same program spans DCN.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..dataset import _ConstructedDataset
from ..learner import NUM_REC_FIELDS
from ..learner_compact import (CF_GAIN, CF_LCNT, CF_LOUT, CF_LSG, CF_LSH,
                               CF_RCNT, CF_ROUT, CF_RSG, CF_RSH, CI_FEAT,
                               CI_FLAGS, CI_THR, LF_CNT, LF_DEPTH, LF_MAX_C,
                               LF_MIN_C, LF_OUT, LF_SUM_G, LF_SUM_H, NUM_CF,
                               NUM_CI, NUM_LF, CompactState,
                               CompactTPUTreeLearner, to_row_order)
from ..observability.phases import scope
from ..ops.split import find_best_splits
from ..tree import Tree



class ShardedCompactLearner(CompactTPUTreeLearner):
    """`tree_learner=data` (and the data half of voting) on the compact
    learner.  One row shard per device; histograms reduce-scattered over
    features."""

    _supports_bundle = False
    _placement_mode = "data"     # rules_for_mode table this learner rides

    def __init__(self, cfg: Config, data: _ConstructedDataset, mesh: Mesh,
                 hist_backend: str = "auto"):
        from .sharding import row_axis
        self.mesh = mesh
        self.axis = row_axis(mesh)
        self.D = int(np.prod(mesh.devices.shape))
        super().__init__(cfg, data, hist_backend)
        if self.n_pad % self.D:
            raise ValueError(f"padded rows {self.n_pad} not divisible by "
                             f"mesh size {self.D}")
        self.n_local = self.n_pad // self.D
        f_pad = data.bins.shape[0]
        if f_pad % self.D:
            raise ValueError(f"padded features {f_pad} not divisible by "
                             f"mesh size {self.D}")
        self.f_pad = f_pad
        self.fs = f_pad // self.D            # features per shard (padded)
        # local window buckets (windows live in the local row axis)
        self._init_local_windows(cfg, self.n_local)
        self._use_pallas = False  # local XLA one-hot path under shard_map
        self._pad_feature_meta(data, f_pad)
        self._sharded_bins = None
        self._jit_tree_c = None  # built lazily (needs the sharded bins)

    def _init_local_windows(self, cfg: Config, n_local: int) -> None:
        """Window-bucket ladder over the local row axis (shared by every
        sharded learner; feature-parallel passes the FULL row count)."""
        mw = max(int(cfg.tpu_min_window), 1024)
        mw = 1 << (mw - 1).bit_length()
        sizes = []
        s0 = mw
        while s0 < n_local:
            sizes.append(s0)
            s0 *= 2
        sizes.append(n_local)
        self._win_sizes = sizes
        self._win_sizes_arr = jnp.asarray(sizes, dtype=jnp.int32)

    def _pad_feature_meta(self, data: _ConstructedDataset,
                          f_pad: int) -> None:
        """Feature metadata padded to f_pad so shard slices are uniform;
        padding slots are trivial features (num_bin=0 → -inf gain)."""
        num_bin, missing, default_bin, is_cat = data.feature_meta_arrays()
        pad = f_pad - len(num_bin)
        zp = lambda a, fill=0: np.concatenate(
            [a, np.full(pad, fill, a.dtype)]) if pad else a
        self.fp_num_bin = jnp.asarray(zp(num_bin))
        self.fp_missing = jnp.asarray(zp(missing))
        self.fp_default_bin = jnp.asarray(zp(default_bin))
        self.fp_is_cat = jnp.asarray(zp(is_cat.astype(np.int32)) > 0)
        mono = np.zeros(f_pad, np.int8)
        if self.has_monotone:
            mono[:self.num_features] = np.asarray(self.f_monotone)
        self.fp_monotone = jnp.asarray(mono) if self.has_monotone else None
        pen = np.ones(f_pad, np.float32)
        if self.has_penalty:
            pen[:self.num_features] = np.asarray(self.f_penalty)
        self.fp_penalty = jnp.asarray(pen) if self.has_penalty else None
        # the inherited partition branch and shared split step read
        # per-feature metadata with a padded feature index — rebind to the
        # padded arrays
        self.f_num_bin = self.fp_num_bin
        self.f_missing = self.fp_missing
        self.f_default_bin = self.fp_default_bin
        if self.has_monotone:
            self.f_monotone = self.fp_monotone

    def _rows_len(self) -> int:
        return self.n_local

    # -- forced splits (`serial_tree_learner.cpp:543-663`) -------------------
    # The reference's parallel learners inherit ForceSplits from the serial
    # learner (`data_parallel_tree_learner.cpp:257-258` templates over it);
    # here the shared `_forced_phase_compact` runs inside the shard_map
    # program — only the histogram-row fetch differs (the pool is feature-
    # scattered, so the owning device broadcasts the row via a tiny psum).

    def set_forced_splits(self, forced) -> None:
        self._forced = list(forced) if forced else None
        self._jit_tree_c = None              # rebuilt lazily with the phase

    def _fix_hrow(self, hrow, fi: int, sum_g, sum_h, cnt):
        """Single-feature ``Dataset::FixHistogram`` (the sliced pools make
        the full-width `_fix_histogram` inapplicable)."""
        db = int(self.np_default_bin[fi])
        if db <= 0:
            return hrow
        totals = jnp.stack([sum_g, sum_h, cnt]).astype(hrow.dtype)
        others = jnp.sum(hrow, axis=0) - hrow[db]
        return hrow.at[db].set(totals - others)

    def _forced_hrow(self, state, fs, sum_g, sum_h, cnt):
        fi = fs.feature_inner
        owner, loc = divmod(fi, self.fs)
        row = state.hist_pool[fs.leaf, loc]              # (B, 3) slice row
        d = lax.axis_index(self.axis)
        with scope("exchange"):
            hrow = lax.psum(jnp.where(d == owner, row, jnp.zeros_like(row)),
                            self.axis)
        return self._fix_hrow(hrow, fi, sum_g, sum_h, cnt)

    # -- sharded data placement (rule-driven, `parallel/sharding.py`) --------

    def _rules(self):
        from .sharding import rules_for_mode
        return rules_for_mode(self._placement_mode, self.mesh)

    def sharded_bins(self) -> jax.Array:
        """The packed table, each device's block packed on the host and
        placed on that device alone: the unpacked table never reaches a
        chip (packing it on one device casts it to int32 on the way: four
        times the table, beside it, on the chip that holds one shard)."""
        if self._sharded_bins is None:
            from ..ops.hist_pallas import pack_bin_words_host
            bins = self.data.bins                 # host (f_pad, n_pad) uint8
            fw = bins.shape[0] // 4     # all words (``self.fw`` is a TILE's
            #                             under a 2-D mesh)

            def block(index):
                words, rows = index
                lo, hi, _ = words.indices(fw)
                return pack_bin_words_host(bins[4 * lo:4 * hi, rows])

            self._sharded_bins = jax.make_array_from_callback(
                (fw, self.n_pad), self._rules().sharding_for("bins"), block)
        return self._sharded_bins

    def place_table(self) -> None:
        self.sharded_bins()

    def _row_sharded(self, arr):
        return self._rules().place("rows", arr)

    def _reduce_hist(self, local_hist):
        """Histogram exchange: reduce-scatter over the feature axis so each
        device sums (and later scans) a feature slice
        (`data_parallel_tree_learner.cpp:146-161`)."""
        return self._exchange(local_hist, 0)

    def _reduce_hist_batch(self, local_hists):
        """Batched (K, F, B, 3) member histograms exchanged in ONE
        collective (scatter over the feature axis), mirroring the wave
        body's single psum_scatter per wave — K per-member exchanges
        would pay K collective latencies per stall event."""
        return self._exchange(local_hists, 1)

    # every collective of the tree program sits under the device scope
    # ``exchange`` (observability/phases.py: MESH_STAGES), inside the phase
    # and stage of its call site

    def _sync_counts(self, lc_bag, c_bag):
        """Global bagged counts from the local partition's sums."""
        self._rec_coll("psum", lc_bag)
        self._rec_coll("psum", c_bag)
        with scope("exchange"):
            return (lax.psum(lc_bag, self.axis), lax.psum(c_bag, self.axis))

    def _global_scalar(self, v):
        self._rec_coll("psum", v)
        with scope("exchange"):
            return lax.psum(v, self.axis)

    def _global_max(self, v):
        self._rec_coll("pmax", v)
        with scope("exchange"):
            return lax.pmax(v, self.axis)

    def _global_row_offset(self):
        # rows are shard-contiguous in axis order, so shard d quantizes
        # rows [d·n_local, (d+1)·n_local) exactly as the serial learner
        # would (the stochastic-rounding hash keys on the global index)
        return lax.axis_index(self.axis) * jnp.int32(self.n_local)

    # -- int16 histogram wire format (quantized mode, ops/quant.py) ----------

    def _wire_int16(self) -> bool:
        """Quantized histograms ride the exchange as int16 integer units
        when every reduced channel provably fits (GLOBAL row bound)."""
        from ..ops.quant import exchange_tier
        return bool(getattr(self, "_quant", False)) \
            and exchange_tier(self.n_pad) == "int16"

    def _exchange(self, h, dim: int):
        """One histogram reduce-scatter over the data axis.  In quantized
        mode with the int16 tier active, channels are divided back to
        integer units and shipped as int16 — HALF the f32 payload — then
        rescaled after the integer reduction (exact: sums are bounded by
        the tier gate).  The ledger records the PACKED operand so traced
        collective payload bytes reflect the wire format."""
        with scope("exchange"):
            if self._wire_int16():
                from ..ops.quant import pack_hist_int16, unpack_hist_int16
                inv_sg, inv_sh = self._q_inv
                h16 = pack_hist_int16(h, inv_sg, inv_sh, self._q_mbar)
                self._rec_coll("psum_scatter", h16)
                h16 = lax.psum_scatter(h16, self.axis, scatter_dimension=dim,
                                       tiled=True)
                return unpack_hist_int16(h16, *self._q_scales,
                                         1.0 / self._q_mbar)
            self._rec_coll("psum_scatter", h)
            return lax.psum_scatter(h, self.axis, scatter_dimension=dim,
                                    tiled=True)

    def _child_best_rows(self, hist_left, hist_right, crow_f, fmask_pad,
                         depth_ok, constraints):
        hist2 = jnp.stack([hist_left, hist_right])
        sums = (jnp.stack([crow_f[CF_LSG], crow_f[CF_RSG]]),
                jnp.stack([crow_f[CF_LSH], crow_f[CF_RSH]]),
                jnp.stack([crow_f[CF_LCNT], crow_f[CF_RCNT]]))
        return self._best_rows_global(hist2, sums, fmask_pad, depth_ok,
                                      constraints)

    # -- per-shard split finding --------------------------------------------

    def _shard_slice(self, full):
        d = lax.axis_index(self.axis)
        return lax.dynamic_slice_in_dim(full, d * self.fs, self.fs)

    def _feature_cands_shard(self, hist, sum_g, sum_h, cnt, fmask_pad,
                             min_c=None, max_c=None):
        """The merged numerical+categorical finder over THIS device's
        feature slice of the reduce-scattered histogram."""
        return self._feature_cands_meta(
            hist, sum_g, sum_h, cnt,
            self._shard_slice(self.fp_num_bin),
            self._shard_slice(self.fp_missing),
            self._shard_slice(self.fp_default_bin),
            self._shard_slice(self.fp_is_cat),
            self._shard_slice(fmask_pad),
            self._shard_slice(self.fp_monotone) if self.has_monotone else None,
            self._shard_slice(self.fp_penalty) if self.has_penalty else None,
            min_c, max_c)

    def _feature_cands_meta(self, hist, sum_g, sum_h, cnt, num_bin, missing,
                            default_bin, is_cat, fmask_sel, mono, pen,
                            min_c=None, max_c=None):
        """Merged finder over an arbitrary feature subset described by the
        given metadata arrays (a contiguous shard slice, or a gathered
        voting selection)."""
        # ``Dataset::FixHistogram`` on the subset, mirroring the serial
        # scan (`learner.py:_feature_cands`): rebuild each default-bin
        # entry as leaf totals minus the other bins.  An exact no-op on
        # consistent paths, but FORCED-SPLIT chains carry the reference's
        # GatherInfo-vs-partition sum inconsistency whose delta lands in
        # the default bin — without this the sharded scans see different
        # histograms than serial on forced descendants (round-5 bug).
        dt = hist.dtype
        dbm = (jnp.arange(hist.shape[1])[None, :] == default_bin[:, None]) \
            & (default_bin[:, None] > 0)
        totals = jnp.stack([sum_g, sum_h, cnt]).astype(dt)
        others = jnp.sum(jnp.where(dbm[..., None], 0.0, hist), axis=1)
        hist = jnp.where(dbm[..., None],
                         (totals[None, :] - others)[:, None, :], hist)
        fsel = hist.shape[0]
        fmask = fmask_sel & ~is_cat
        if not self.has_monotone:
            min_c = max_c = None
        elif min_c is None:
            min_c = jnp.asarray(-jnp.inf, hist.dtype)
            max_c = jnp.asarray(jnp.inf, hist.dtype)
        num = find_best_splits(
            hist, sum_g, sum_h, cnt, num_bin, missing, default_bin, fmask,
            mono, min_c, max_c, **self._split_kwargs)
        if self.has_penalty:
            num = num._replace(gain=jnp.where(
                jnp.isneginf(num.gain), num.gain, num.gain * pen))
        gain, thr, dl = num.gain, num.threshold, num.default_left
        if self.has_categorical:
            from ..ops.split_cat import find_best_splits_categorical
            cmask = fmask_sel & is_cat
            cat = find_best_splits_categorical(
                hist, sum_g, sum_h, cnt, num_bin, missing, cmask,
                min_c, max_c, **self._cat_split_kwargs)
            if self.has_penalty:
                cat = cat._replace(gain=jnp.where(
                    jnp.isneginf(cat.gain), cat.gain, cat.gain * pen))
            pickc = lambda c, n_: jnp.where(is_cat, c, n_)
            gain = pickc(cat.gain, num.gain)
            thr = jnp.where(is_cat, 0, num.threshold)
            dl = jnp.where(is_cat, False, num.default_left)
            lsg = pickc(cat.left_sum_g, num.left_sum_g)
            lsh = pickc(cat.left_sum_h, num.left_sum_h)
            lcn = pickc(cat.left_cnt, num.left_cnt)
            rsg = pickc(cat.right_sum_g, num.right_sum_g)
            rsh = pickc(cat.right_sum_h, num.right_sum_h)
            rcn = pickc(cat.right_cnt, num.right_cnt)
            lo = pickc(cat.left_output, num.left_output)
            ro = pickc(cat.right_output, num.right_output)
            bits = jnp.where(is_cat[:, None], cat.bits,
                             jnp.zeros((fsel, self.cat_W), jnp.uint32))
        else:
            lsg, lsh, lcn = num.left_sum_g, num.left_sum_h, num.left_cnt
            rsg, rsh, rcn = num.right_sum_g, num.right_sum_h, num.right_cnt
            lo, ro = num.left_output, num.right_output
            bits = jnp.zeros((fsel, self.cat_W), jnp.uint32)
            is_cat = jnp.zeros(fsel, bool)
        return gain, thr, dl, is_cat, bits, lsg, lsh, lcn, rsg, rsh, rcn, \
            lo, ro

    def _best_rows_global(self, hist2, crow_sums, fmask_pad, depth_ok,
                          constraints):
        """Per-child best split over ALL features: local slice scan →
        all_gather of one packed row per device → global argmax
        (``SyncUpGlobalBestSplit``)."""
        K = hist2.shape[0]
        d = lax.axis_index(self.axis)

        def one(hist, sg, sh, cn, mn, mx):
            g, thr, dl, ic, bits, lsg, lsh, lcn, rsg, rsh, rcn, lo, ro = \
                self._feature_cands_shard(hist, sg, sh, cn, fmask_pad, mn, mx)
            bf = jnp.argmax(g).astype(jnp.int32)
            pick = lambda a: a[bf]
            cf = jnp.stack([pick(g).astype(self._acc), pick(lsg), pick(lsh),
                            pick(lcn), pick(rsg), pick(rsh), pick(rcn),
                            pick(lo), pick(ro)]).astype(self._acc)
            flags = pick(dl).astype(jnp.int32) + 2 * pick(ic).astype(jnp.int32)
            ci = jnp.stack([bf + d * self.fs, pick(thr), flags])
            return cf, ci.astype(jnp.int32), bits[bf]

        sg2, sh2, cn2 = crow_sums
        if constraints is not None:
            mins, maxs = constraints
            cf, ci, cb = jax.vmap(one)(hist2, sg2, sh2, cn2, mins, maxs)
        else:
            cf, ci, cb = jax.vmap(
                lambda h, g, hh, c: one(h, g, hh, c, None, None)
            )(hist2, sg2, sh2, cn2)
        # global winner per child (tiny allgather)
        for x in (cf, ci, cb):
            self._rec_coll("all_gather", x)
        with scope("exchange"):
            cf_all = lax.all_gather(cf, self.axis)     # (D, K, NUM_CF)
            ci_all = lax.all_gather(ci, self.axis)
            cb_all = lax.all_gather(cb, self.axis)
        win = jnp.argmax(cf_all[:, :, CF_GAIN], axis=0)   # (K,) device idx
        cf_g = jnp.take_along_axis(
            cf_all, win[None, :, None], axis=0)[0]
        ci_g = jnp.take_along_axis(
            ci_all, win[None, :, None], axis=0)[0]
        cb_g = jnp.take_along_axis(
            cb_all, win[None, :, None], axis=0)[0]
        cf_g = cf_g.at[:, CF_GAIN].set(
            jnp.where(depth_ok, cf_g[:, CF_GAIN], -jnp.inf))
        return cf_g, ci_g, cb_g

    # -- the sharded tree ----------------------------------------------------

    def _train_tree_sharded(self, bins_p, grad, hess, bag, fmask_pad):
        """Body under shard_map: all row-axis arrays are LOCAL shards."""
        self._ledger.begin_trace()
        self._coll_ctx = ("root", "tree")
        axis = self.axis
        n, L = self.n_local, self.num_leaves
        b = self.num_bins_padded
        acc = self._acc
        self._hist_branches = [self._make_hist_branch_shard(S)
                               for S in self._win_sizes]
        self._partition_branches = [
            self._make_partition_branch(S, sort_mode=S > self._sort_cutoff)
            for S in self._win_sizes]

        w = jnp.stack([grad * bag, hess * bag, bag], axis=0)
        lid0 = jnp.zeros(n, jnp.int32)
        local_root = self._hist_branches[-1](bins_p, w, lid0, jnp.int32(0),
                                             jnp.int32(n), jnp.int32(0))
        root_hist = self._reduce_hist(local_root)   # (fs, B, 3) scattered
        sum_g = self._global_scalar(jnp.sum((grad * bag).astype(acc)))
        sum_h = self._global_scalar(jnp.sum((hess * bag).astype(acc)))
        cnt = self._global_scalar(jnp.sum(bag.astype(acc)))

        md = int(self.cfg.max_depth)
        depth_ok = jnp.asarray([True if md <= 0 else md > 0])
        cf_root, ci_root, cb_root = self._best_rows_global(
            root_hist[None], (sum_g[None], sum_h[None], cnt[None]),
            fmask_pad, depth_ok, None)

        root_lf = jnp.zeros(NUM_LF, acc) \
            .at[LF_SUM_G].set(sum_g).at[LF_SUM_H].set(sum_h) \
            .at[LF_CNT].set(cnt).at[LF_MIN_C].set(-jnp.inf) \
            .at[LF_MAX_C].set(jnp.inf)
        state = CompactState(
            bins_p=bins_p,
            w_p=w,
            rid_p=jnp.arange(n, dtype=jnp.int32),
            lid_p=jnp.zeros(n, jnp.int32),
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
            cand_b=jnp.zeros((L, self.cat_W), jnp.uint32)
                      .at[0].set(cb_root[0]),
            num_leaves=jnp.asarray(1, jnp.int32),
            rec_f=jnp.zeros((L - 1, NUM_REC_FIELDS), jnp.float32),
            rec_i=jnp.zeros((L - 1, 2), jnp.int32),
            rec_cat=jnp.zeros((L - 1, self.cat_W), jnp.uint32))

        state = self._forced_phase_compact(state, fmask_pad)

        def body(i, st):
            # records land at cursor num_leaves-1 (like the serial learner)
            # so the forced phase and best-gain growth share one stream;
            # iterations past the leaf budget are exact no-ops
            return self._split_step_compact(st, fmask_pad,
                                            st.num_leaves - 1)

        state = jax.lax.fori_loop(0, L - 1, body, state)
        leaf_id = to_row_order(state.rid_p, state.lid_p, L)
        leaf_output = state.leaf_f[:, LF_OUT].astype(jnp.float32)
        return (state.rec_f, state.rec_i, state.rec_cat, leaf_id,
                leaf_output)

    def _make_hist_branch_shard(self, S: int):
        """Local windowed histogram over the FULL padded feature axis (the
        scatter happens outside the bucket switch — collectives must not
        live under data-dependent branches)."""
        fw, b = self.fw, self.num_bins_padded
        n = self.n_local
        from ..ops.hist_pallas import unpack_bin_words
        from ..ops.histogram import build_histogram_onehot

        def branch(bins_p, w_p, lid_p, start, cnt, leaf):
            sa = jnp.clip(start, 0, n - S).astype(jnp.int32)
            off = (start - sa).astype(jnp.int32)
            bw = lax.dynamic_slice(bins_p, (jnp.int32(0), sa), (fw, S))
            ww = lax.dynamic_slice(w_p, (jnp.int32(0), sa), (3, S))
            lid = lax.dynamic_slice(lid_p, (sa,), (S,))
            pos = jnp.arange(S, dtype=jnp.int32)
            m = (pos >= off) & (pos < off + cnt) & (lid == leaf)
            wm = ww * m[None, :].astype(ww.dtype)
            bu = unpack_bin_words(bw, fw * 4)     # keep padded features
            if self._quant:
                # quantized lanes (mirrors the serial branch): two
                # channels ride the contraction, the count channel is the
                # normalized Σhq/m̄ effective row count — identical
                # channels to the serial quant learner keep the records
                # stream exact
                h2 = build_histogram_onehot(bu, wm[:2], num_bins=b)
                h = jnp.concatenate([h2, h2[:, :, 1:2]], axis=2)
                return h * jnp.stack([jnp.float32(1.0), jnp.float32(1.0),
                                      self._q_cnt])
            return build_histogram_onehot(bu, wm, num_bins=b,
                                          dp=self.hist_dp)

        return branch

    # -- host orchestration --------------------------------------------------

    def _build_jit(self):
        if self._jit_tree_c is None:
            ax = self.axis
            kw = dict(mesh=self.mesh,
                      in_specs=(P(None, ax), P(ax), P(ax), P(ax), P()),
                      out_specs=(P(), P(), P(), P(ax), P()))
            fn = jax.shard_map(self._train_tree_sharded, check_vma=False,
                               **kw)
            self._jit_tree_c = jax.jit(fn)
        return self._jit_tree_c

    def _padded_feature_mask(self, feature_mask) -> jax.Array:
        """The (f_pad,) mask of the tree step: the sampled features (all of
        them where none were sampled), False on the padding columns."""
        if feature_mask is None:
            feature_mask = jnp.ones(self.num_features, dtype=bool)
        return jnp.zeros(self.f_pad, bool).at[:self.num_features].set(
            feature_mask)

    def train_async(self, grad: jax.Array, hess: jax.Array, bag: jax.Array,
                    feature_mask: Optional[jax.Array] = None):
        return self._build_jit()(self.sharded_bins(), grad, hess, bag,
                                 self._padded_feature_mask(feature_mask))

    def lowered_hlo_text(self) -> str:
        """Compiled HLO of the sharded tree step (for collective asserts)."""
        n = self.n_pad
        z = jnp.zeros(n, jnp.float32)
        fmask_pad = jnp.ones(self.f_pad, bool)
        return self._build_jit().lower(
            self.sharded_bins(), z, z, z, fmask_pad).compile().as_text()

    # -- attribution probe (observability/attribution.py) --------------------

    def _probe_program(self, body, in_specs, out_specs, args):
        """Build + cache a standalone jitted shard_map probe over this
        learner's real exchange seam.  The ledger is muted while the
        probe traces, so ``collectives.sites`` and the analysis-gate
        budgets never see the probe's sites; the probe jit itself is
        outside the gate's traced-program set."""
        ledger = self._ledger
        kw = dict(mesh=self.mesh, in_specs=in_specs, out_specs=out_specs)
        fn = jax.shard_map(body, check_vma=False, **kw)
        jfn = jax.jit(fn)

        def run(*a):
            with ledger.muted():
                return jfn(*a)

        self._probe_fn, self._probe_args = run, tuple(args)
        return self._probe_fn, self._probe_args

    def exchange_probe(self):
        """The REAL root-histogram exchange (`_exchange` dim 0: the
        reduce-scatter over the feature axis) over a representative zero
        buffer."""
        if getattr(self, "_probe_fn", None) is None:
            return self._probe_program(
                lambda h: self._exchange(h, 0), P(), P(self.axis),
                (jnp.zeros((self.f_pad, self.num_bins_padded, 3),
                           jnp.float32),))
        return self._probe_fn, self._probe_args


def make_sharded_learner(cfg: Config, data: _ConstructedDataset,
                         mesh: Mesh) -> ShardedCompactLearner:
    return ShardedCompactLearner(cfg, data, mesh)


class ShardedVotingLearner(ShardedCompactLearner):
    """``tree_learner=voting`` — PV-Tree feature voting to cut histogram
    communication (`voting_parallel_tree_learner.cpp:166-345`).

    Per child: every device ranks features on its LOCAL (unreduced)
    histograms and proposes its top-``top_k`` (``LocalVoting``); one tiny
    all_gather of vote indices elects the global top-2k by vote count with
    low-index tie-break (``GlobalVoting`` / ``ArgMaxK``); only the ELECTED
    features' histograms are reduce-scattered (``CopyLocalHistogram``) and
    scanned.  The histogram pool stays local-unreduced so parent
    subtraction needs no extra wire traffic — communicated volume per split
    drops from (F, B, 3) to (2k, B, 3)."""

    _placement_mode = "voting"

    def __init__(self, cfg: Config, data: _ConstructedDataset, mesh: Mesh,
                 hist_backend: str = "auto"):
        super().__init__(cfg, data, mesh, hist_backend)
        self._init_voting_sizing(cfg)

    def _init_voting_sizing(self, cfg: Config) -> None:
        """2k elected features, rounded to a mesh multiple for the scatter
        (f_pad is itself a mesh multiple, so min() preserves divisibility).
        Shared with the voting-wave learner — keep the rounding rules in
        one place."""
        k2 = max(2 * int(cfg.top_k), self.D)
        k2 = min(((k2 + self.D - 1) // self.D) * self.D, self.f_pad)
        self.k_vote = min(int(cfg.top_k), self.f_pad)
        self.k2 = k2
        self.k2s = k2 // self.D              # elected features per device

    def _reduce_hist(self, local_hist):
        # the pool stays LOCAL; reduction happens per elected feature set
        return local_hist

    def _reduce_hist_batch(self, local_hists):
        # likewise: the batched stall-correction histograms stay local
        return local_hists

    def _forced_hrow(self, state, fs, sum_g, sum_h, cnt):
        # the voting pool is full-width LOCAL-unreduced: reduce the one
        # forced feature's row across devices, then fix it
        with scope("exchange"):
            hrow = lax.psum(state.hist_pool[fs.leaf, fs.feature_inner],
                            self.axis)
        return self._fix_hrow(hrow, fs.feature_inner, sum_g, sum_h, cnt)

    def exchange_probe(self):
        """Voting's real wire payload is the ELECTED feature set (2k wide,
        not f_pad) — probe the elected-width reduce-scatter."""
        if getattr(self, "_probe_fn", None) is None:
            return self._probe_program(
                lambda h: self._exchange(h, 0), P(), P(self.axis),
                (jnp.zeros((self.k2, self.num_bins_padded, 3),
                           jnp.float32),))
        return self._probe_fn, self._probe_args

    def _best_rows_global(self, hist2, crow_sums, fmask_pad, depth_ok,
                          constraints):
        """hist2 here is (K, f_pad, B, 3) LOCAL-unreduced."""
        K = hist2.shape[0]
        d = lax.axis_index(self.axis)
        sg2, sh2, cn2 = crow_sums

        def one(hist, sg, sh, cn, mn, mx):
            # ---- LocalVoting: rank features on this device's local rows
            lsg = jnp.sum(hist[0, :, 0])
            lsh = jnp.sum(hist[0, :, 1])
            lcn = jnp.sum(hist[0, :, 2])
            g_loc, *_ = self._feature_cands_meta(
                hist, lsg, lsh, lcn, self.fp_num_bin, self.fp_missing,
                self.fp_default_bin, self.fp_is_cat, fmask_pad,
                self.fp_monotone, self.fp_penalty)
            vals, votes = lax.top_k(g_loc, self.k_vote)       # (k,)
            self._rec_coll("all_gather", votes)
            self._rec_coll("all_gather", vals)
            with scope("exchange"):
                all_votes = lax.all_gather(votes, self.axis).reshape(-1)
                all_valid = ~jnp.isneginf(
                    lax.all_gather(vals, self.axis).reshape(-1))
            counts = jnp.zeros(self.f_pad, jnp.int32).at[all_votes].add(
                all_valid.astype(jnp.int32), mode="drop")
            # GlobalVoting: top-2k by count, low feature index breaks ties
            score = counts.astype(jnp.float32) * self.f_pad \
                - jnp.arange(self.f_pad, dtype=jnp.float32)
            sel = jnp.sort(lax.top_k(score, self.k2)[1]).astype(jnp.int32)
            # ---- CopyLocalHistogram: exchange only elected features
            sel_hist = self._exchange(hist[sel], 0)           # (k2s, B, 3)
            my_sel = lax.dynamic_slice_in_dim(sel, d * self.k2s, self.k2s)
            gidx = lambda a: a[my_sel]
            g, thr, dl, ic, bits, lsg2, lsh2, lcn2, rsg, rsh, rcn, lo, ro = \
                self._feature_cands_meta(
                    sel_hist, sg, sh, cn,
                    gidx(self.fp_num_bin), gidx(self.fp_missing),
                    gidx(self.fp_default_bin), gidx(self.fp_is_cat),
                    gidx(fmask_pad),
                    gidx(self.fp_monotone) if self.has_monotone else None,
                    gidx(self.fp_penalty) if self.has_penalty else None,
                    mn, mx)
            bf = jnp.argmax(g).astype(jnp.int32)
            pick = lambda a: a[bf]
            cf = jnp.stack([pick(g).astype(self._acc), pick(lsg2),
                            pick(lsh2), pick(lcn2), pick(rsg), pick(rsh),
                            pick(rcn), pick(lo), pick(ro)]).astype(self._acc)
            flags = pick(dl).astype(jnp.int32) + 2 * pick(ic).astype(jnp.int32)
            ci = jnp.stack([my_sel[bf], pick(thr), flags])
            return cf, ci.astype(jnp.int32), bits[bf]

        if constraints is not None:
            mins, maxs = constraints
            cf, ci, cb = jax.vmap(one)(hist2, sg2, sh2, cn2, mins, maxs)
        else:
            cf, ci, cb = jax.vmap(
                lambda h, g, hh, c: one(h, g, hh, c, None, None)
            )(hist2, sg2, sh2, cn2)
        with scope("exchange"):
            cf_all = lax.all_gather(cf, self.axis)
            ci_all = lax.all_gather(ci, self.axis)
            cb_all = lax.all_gather(cb, self.axis)
        # global winner; exact tie-break toward the LOWEST feature index —
        # unlike the sharded scan, the election's device slices are not
        # contiguous feature ranges, so the argmax alone is not enough
        gains = cf_all[:, :, CF_GAIN]
        max_gain = jnp.max(gains, axis=0)
        at_max = gains == max_gain[None, :]
        feat_masked = jnp.where(at_max, ci_all[:, :, CI_FEAT],
                                jnp.int32(1 << 30))
        win = jnp.argmin(feat_masked, axis=0)
        cf_g = jnp.take_along_axis(cf_all, win[None, :, None], axis=0)[0]
        ci_g = jnp.take_along_axis(ci_all, win[None, :, None], axis=0)[0]
        cb_g = jnp.take_along_axis(cb_all, win[None, :, None], axis=0)[0]
        cf_g = cf_g.at[:, CF_GAIN].set(
            jnp.where(depth_ok, cf_g[:, CF_GAIN], -jnp.inf))
        return cf_g, ci_g, cb_g
