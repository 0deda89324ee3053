"""Feature-parallel learners on the fast (compact/wave) tree learners.

TPU-native ``tree_learner=feature``
(`src/treelearner/feature_parallel_tree_learner.cpp:29-73`): every machine
holds ALL rows, histograms + split scans cover only its FEATURE shard, and
the winning split is agreed with a tiny allgather (``SyncUpGlobalBestSplit``,
`parallel_tree_learner.h:186-209`); the row partition is then performed
identically everywhere (the reference's workers also keep full data — the
mode trades replicated partitioning for an F/D scan load, its win on wide
dense datasets like Epsilon 400K×2000).

Round 3 draped feature-parallel over the slow masked learner; these
subclasses put it on the compact and frontier-wave learners instead:
row-axis seams revert to the serial behavior (rows are NOT sharded), while
the histogram branches compute only the local word slice and the split
scans ride the same slice machinery as the data-parallel learner.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..config import Config
from ..dataset import _ConstructedDataset
from ..learner_wave import WaveTPUTreeLearner
from ..observability.phases import scope
from .compact_sharded import ShardedCompactLearner


class FeatureShardedCompactLearner(ShardedCompactLearner):
    """`tree_learner=feature` on the compact learner: replicated rows,
    feature-sliced histograms + scans, allgathered best splits."""

    _placement_mode = "feature"

    def __init__(self, cfg: Config, data: _ConstructedDataset, mesh: Mesh,
                 hist_backend: str = "auto"):
        super().__init__(cfg, data, mesh, hist_backend)
        # rows are replicated: window buckets span the FULL row axis
        self.n_local = self.n_pad
        self._init_local_windows(cfg, self.n_pad)
        # pad the packed-word axis to a mesh multiple (padding words carry
        # num_bin=0 features -> -inf gains, never selected)
        self.fw2 = ((self.fw + self.D - 1) // self.D) * self.D
        self.fws = self.fw2 // self.D       # words per device
        f_pad2 = self.fw2 * 4
        if f_pad2 != self.f_pad:
            pad = f_pad2 - self.f_pad
            zp = lambda a, fill=0: jnp.concatenate(
                [a, jnp.full((pad,), fill, a.dtype)])
            self.fp_num_bin = zp(self.fp_num_bin)
            self.fp_missing = zp(self.fp_missing)
            self.fp_default_bin = zp(self.fp_default_bin)
            self.fp_is_cat = zp(self.fp_is_cat.astype(jnp.int32)) > 0
            if self.has_monotone:
                self.fp_monotone = zp(self.fp_monotone)
            if self.has_penalty:
                self.fp_penalty = zp(self.fp_penalty, 1.0)
            self.f_num_bin = self.fp_num_bin
            self.f_missing = self.fp_missing
            self.f_default_bin = self.fp_default_bin
            if self.has_monotone:
                self.f_monotone = self.fp_monotone
            self.f_pad = f_pad2
            self.fw = self.fw2
        self.fs = self.f_pad // self.D      # features per device

    # rows replicated -> the serial row seams
    def _rows_len(self) -> int:
        return self.n_pad

    def _sync_counts(self, lc_bag, c_bag):
        return lc_bag, c_bag

    def _sync_counts3(self, cnt3):
        return cnt3

    def _global_scalar(self, v):
        return v                            # rows are replicated

    def _global_max(self, v):
        return v                            # rows are replicated

    def _global_row_offset(self):
        return jnp.int32(0)                 # every device holds all rows

    def _reduce_hist(self, local_hist):
        return local_hist                   # hist IS the local slice

    def _reduce_hist_batch(self, local_hists):
        return local_hists                  # feature slices need no exchange

    def _make_hist_branch_shard(self, S: int):
        """Windowed histogram over THIS device's feature-word slice of the
        replicated packed bins."""
        fws, b = self.fws, self.num_bins_padded
        n = self.n_pad
        from ..ops.hist_pallas import unpack_bin_words
        from ..ops.histogram import build_histogram_onehot

        def branch(bins_p, w_p, lid_p, start, cnt, leaf):
            d = lax.axis_index(self.axis)
            bw_f = lax.dynamic_slice_in_dim(bins_p, d * fws, fws, axis=0)
            sa = jnp.clip(start, 0, n - S).astype(jnp.int32)
            off = (start - sa).astype(jnp.int32)
            bw = lax.dynamic_slice(bw_f, (jnp.int32(0), sa), (fws, S))
            ww = lax.dynamic_slice(w_p, (jnp.int32(0), sa), (3, S))
            lid = lax.dynamic_slice(lid_p, (sa,), (S,))
            pos = jnp.arange(S, dtype=jnp.int32)
            m = (pos >= off) & (pos < off + cnt) & (lid == leaf)
            wm = ww * m[None, :].astype(ww.dtype)
            bu = unpack_bin_words(bw, fws * 4)
            if self._quant:
                # quantized lanes over the feature slice (no exchange —
                # same channel contract as the serial quant branch)
                h2 = build_histogram_onehot(bu, wm[:2], num_bins=b)
                h = jnp.concatenate([h2, h2[:, :, 1:2]], axis=2)
                return h * jnp.stack([jnp.float32(1.0), jnp.float32(1.0),
                                      self._q_cnt])
            return build_histogram_onehot(bu, wm, num_bins=b,
                                          dp=self.hist_dp)

        return branch

    def _train_tree_feature_sharded(self, bins_p, grad, hess, bag,
                                    fmask_pad):
        # identical body to the data-parallel tree, but with replicated
        # rows the collectives reduce to the best-split allgather only
        return self._train_tree_sharded(bins_p, grad, hess, bag, fmask_pad)

    def _build_jit(self):
        if self._jit_tree_c is None:
            ax = self.axis
            kw = dict(mesh=self.mesh,
                      in_specs=(P(None, None), P(), P(), P(), P()),
                      out_specs=(P(), P(), P(), P(), P()))
            fn = jax.shard_map(self._train_tree_feature_sharded,
                               check_vma=False, **kw)
            self._jit_tree_c = jax.jit(fn)
        return self._jit_tree_c

    def sharded_bins(self) -> jax.Array:
        # replicated bins: every worker holds all rows and features, the
        # reference feature-parallel data model
        if self._sharded_bins is None:
            packed = self.bins_packed()
            if packed.shape[0] != self.fw2:
                packed = jnp.concatenate(
                    [packed, jnp.zeros((self.fw2 - packed.shape[0],
                                        packed.shape[1]), packed.dtype)])
            self._sharded_bins = self._rules().place("bins", packed)
        return self._sharded_bins

    def exchange_probe(self):
        """Feature-parallel's only per-split wire traffic is the tiny
        best-split allgather (``SyncUpGlobalBestSplit``,
        `_best_rows_global`) — probe exactly those three rows."""
        if getattr(self, "_probe_fn", None) is None:
            from ..learner_compact import NUM_CF, NUM_CI
            ax = self.axis

            def body(cf, ci, cb):
                return (lax.all_gather(cf, ax), lax.all_gather(ci, ax),
                        lax.all_gather(cb, ax))

            return self._probe_program(
                body, (P(), P(), P()), (P(), P(), P()),
                (jnp.zeros((1, NUM_CF), self._acc),
                 jnp.zeros((1, NUM_CI), jnp.int32),
                 jnp.zeros((1, self.cat_W), jnp.uint32)))
        return self._probe_fn, self._probe_args


class FeatureShardedWaveLearner(FeatureShardedCompactLearner,
                                WaveTPUTreeLearner):
    """`tree_learner=feature` on the frontier-wave learner: the wave's
    member histograms each cover the local feature slice (no exchange at
    all — subtraction and the pool stay slice-local); only the 2W best
    child splits are allgathered per wave."""

    def __init__(self, cfg: Config, data: _ConstructedDataset, mesh: Mesh,
                 hist_backend: str = "auto"):
        FeatureShardedCompactLearner.__init__(self, cfg, data, mesh,
                                              hist_backend)
        self._init_wave_dims(cfg)
        self.fw_col = jnp.arange(self.f_pad, dtype=jnp.int32)
        self.fw_goff = jnp.zeros(self.f_pad, jnp.int32)
        self.fw_bnd = jnp.zeros(self.f_pad, jnp.int32)
        self._jit_tree_w = None

    def _cand_rows_batch(self, hists, sg, sh, cn, feature_mask, depth_ok,
                         constraints):
        return self._best_rows_global(hists, (sg, sh, cn), feature_mask,
                                      depth_ok, constraints)

    # _wave_member_hists: the inherited WaveTPUTreeLearner scan branch is
    # already slice-local (sharded learners run with _use_pallas=False and
    # the hist branches compute this device's feature slice) — no override

    def _train_tree_feature_wave(self, bins_p, grad, hess, bag, fmask_pad):
        self._ledger.begin_trace()
        self._hist_branches = [self._make_hist_branch_shard(S)
                               for S in self._win_sizes]
        self._stall_branches = [
            self._make_stall_branch(S, sort_mode=S > self._stall_cutoff)
            for S in self._win_sizes]
        with scope("root"):
            st = self._init_root_wave(bins_p, grad, hess, bag, fmask_pad)

        def gcond(s):
            return (s.num_splits < self.grow_budget) & \
                (jnp.max(self._pool_gains(s)) > 0.0)

        with scope("grow"):
            st = lax.while_loop(
                gcond, lambda s: self._wave_step(s, fmask_pad), st)
            if self._defer_sorts and self._stall_batch == 1:
                # batched (K>1) replay corrections mask through phys_i
                # spans and skip the pre-replay materialization (see
                # learner_wave)
                st = lax.cond(st.pending, self._materialize_sort,
                              lambda s: s, st)
        return self._emit_tree_wave(st, fmask_pad)

    def train_async(self, grad: jax.Array, hess: jax.Array, bag: jax.Array,
                    feature_mask: Optional[jax.Array] = None):
        fmask_pad = self._padded_feature_mask(feature_mask)
        if self._jit_tree_w is None:
            ax = self.axis
            out_specs = (P(), P(), P(), P(), P())
            if self._telemetry:
                out_specs = out_specs + (P(),)
            kw = dict(mesh=self.mesh,
                      in_specs=(P(None, None), P(), P(), P(), P()),
                      out_specs=out_specs)
            fn = jax.shard_map(self._train_tree_feature_wave,
                               check_vma=False, **kw)
            self._jit_tree_w = jax.jit(fn, donate_argnums=(1, 2)) \
                if self._donate else jax.jit(fn)
        return self._pop_telem(self._jit_tree_w(
            self.sharded_bins(), grad, hess, bag, fmask_pad))

    def lowered_hlo_text(self) -> str:
        # grad/hess are donate_argnums under _donate: each position gets
        # its OWN buffer so the donated args never alias bag (LGB009)
        g, h, b = (jnp.zeros(self.n_pad, jnp.float32) for _ in range(3))
        self.train_async(g, h, b)
        g, h, b = (jnp.zeros(self.n_pad, jnp.float32) for _ in range(3))
        fmask_pad = jnp.ones(self.f_pad, bool)
        return self._jit_tree_w.lower(
            self.sharded_bins(), g, h, b, fmask_pad).compile().as_text()


def feature_sharded_eligible(cfg: Config, data: _ConstructedDataset,
                             mesh_size: int) -> bool:
    if data.max_num_bin > 256:
        return False
    # the word axis pads itself to a mesh multiple; the base
    # compact-sharded scaffolding still asserts f_pad and n_pad
    # divisibility in its __init__, so gate on both here
    if data.bins.shape[0] % max(mesh_size, 1):
        return False
    if data.num_data_padded % max(mesh_size, 1):
        return False
    return True
