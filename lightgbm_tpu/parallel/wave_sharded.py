"""Data-parallel frontier-wave learner: wave growth over row shards.

Round-3's ``ShardedCompactLearner`` wraps the SEQUENTIAL compact learner —
254 dependent split steps per tree, each paying the collective + bookkeeping
floor.  This subclass ports the frontier-wave growth
(`lightgbm_tpu/learner_wave.py`) into the shard_map program, mirroring the
reference's template of parallelizing its fastest serial learner
(`src/treelearner/data_parallel_tree_learner.cpp:257-258` instantiates over
the serial learner):

  * every device runs the wave partition over its LOCAL rows (the one
    stable sort per wave sorts the local shard);
  * the opening's levels (``tpu_wave_open_levels``) run unsorted as the
    serial learner's do: one multi-slot pass over a shard's own rows a
    level, then the same one reduce-scatter as a wave's;
  * the W smaller-child histograms of a wave are ``psum_scatter``-ed over
    the feature axis in ONE batched collective per wave — W× fewer
    exchanges than the sequential sharded learner
    (`data_parallel_tree_learner.cpp:146-161` reduce-scatters per split);
  * the 2W children's best splits come from per-device feature-slice scans
    merged by a tiny all_gather (``SyncUpGlobalBestSplit``,
    `parallel_tree_learner.h:186-209`);
  * node/candidate state stays replicated, so the exact greedy replay (and
    its leaf numbering) is pure replicated bookkeeping — no communication.

Exactness: the records stream is identical to the serial wave learner's
(`tests/test_parallel.py::test_wave_sharded_records_match_serial`).

``_wave_body`` (shared with the serial learner) sorts only a shard's own
rows by LOCAL window geometry (``growth_sort``), so the partition holds no
collective site (`analysis/budgets.json` pins them); ``_init_wave_dims``
re-runs with the shard-local row count.  The fused split-scan does NOT
apply here: the sharded candidate scans go through ``_best_rows_global``
(feature-slice scans + all_gather), which overrides ``_cand_rows_batch``
entirely.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..config import Config
from ..dataset import _ConstructedDataset
from ..learner_wave import WaveState, WaveTPUTreeLearner, \
    _segment_row_block, wave_budget_reason
from ..observability.phases import scope
from .compact_sharded import ShardedCompactLearner


class ShardedWaveLearner(ShardedCompactLearner, WaveTPUTreeLearner):
    """`tree_learner=data` on the frontier-wave learner (see module
    docstring).  MRO: sharded seams (_reduce_hist/_sync_counts/
    _best_rows_global) override the serial ones; wave growth/replay comes
    from WaveTPUTreeLearner."""

    def __init__(self, cfg: Config, data: _ConstructedDataset, mesh: Mesh,
                 hist_backend: str = "auto"):
        ShardedCompactLearner.__init__(self, cfg, data, mesh, hist_backend)
        # the kernels per shard, where the serial learner would run them
        # over these rows.  A shard's histogram keeps the padded feature
        # axis (the exchange scatters it; the voting learner elects from it)
        self._use_pallas = self._kernels_fit(hist_backend, self.n_local)
        self._hist_cols = self.f_pad
        self._seg_rb = _segment_row_block(self.n_local)
        # wave bookkeeping over the PADDED feature axis (no EFB bundles in
        # the sharded path; metadata was padded by the sharded __init__),
        # the opening's depth by the serial rule on a shard's rows
        self._init_wave_dims(cfg)
        self.fw_col = jnp.arange(self.f_pad, dtype=jnp.int32)
        self.fw_goff = jnp.zeros(self.f_pad, jnp.int32)
        self.fw_bnd = jnp.zeros(self.f_pad, jnp.int32)
        self._jit_tree_w = None

    # -- sharded seams used by the wave body ---------------------------------

    def _sync_counts3(self, cnt3):
        # row 0 (left ROW count) is local window geometry; rows 1-2 are
        # the global bagged counts every device must agree on
        self._rec_coll("psum", cnt3[1:])
        with scope("exchange"):
            bagged = lax.psum(cnt3[1:], self.axis)
        return jnp.concatenate([cnt3[:1], bagged], axis=0)

    def _replicated_spans(self, spans):
        # phys_i spans are LOCAL row-window geometry here — replicate the
        # batched-stall gate with the cross-device max so bv (and the
        # whole replay bookkeeping) stays identical on every shard
        self._rec_coll("pmax", spans)
        with scope("exchange"):
            return lax.pmax(spans, self.axis)

    def _cand_rows_batch(self, hists, sg, sh, cn, feature_mask, depth_ok,
                         constraints):
        """(K, fs, B, 3) scattered child histograms -> replicated best
        rows via feature-slice scans + all_gather."""
        return self._best_rows_global(hists, (sg, sh, cn), feature_mask,
                                      depth_ok, constraints)

    def _wave_member_hists(self, st: WaveState, sm_slot, sm_start, sm_cnt,
                           valid, ph, lh_w, rh_w, left_small):
        """Local per-member histograms over the full padded feature axis,
        ONE batched psum_scatter over features per wave, then subtraction
        against the (scattered) parent pool slices."""
        if self._use_pallas:     # one segment-kernel call for the wave
            h_local = self._segment_hists(st, sm_slot, sm_start, sm_cnt,
                                          valid)
            return self._scattered_children(st, h_local, ph, lh_w, rh_w,
                                            left_small)

        def hist_member(_, xs):
            slot, start, cnt, vk = xs

            def compute(_):
                hidx = self._bucket_idx(jnp.maximum(cnt, 1))
                return lax.switch(hidx, self._hist_branches, st.bins_p,
                                  st.w_p, st.lid_p, start, cnt, slot)

            def skip(_):
                b = self.num_bins_padded
                return jnp.zeros((self.f_pad, b, 3), self._hist_dtype())

            return 0, lax.cond(vk, compute, skip, 0)

        _, h_local = lax.scan(hist_member, 0,
                              (sm_slot, sm_start, sm_cnt, valid))
        return self._scattered_children(st, h_local, ph, lh_w, rh_w,
                                        left_small)

    def _multislot_opening(self) -> bool:
        # the opening's pass feeds this learner's exchange seam; a
        # subclass with its own member histograms (voting's local pool)
        # has no such seam
        return self._use_pallas and type(self)._wave_member_hists is \
            ShardedWaveLearner._wave_member_hists

    def _opening_hists(self, st: WaveState, sm_slot, valid, ph, lh_w, rh_w,
                       left_small):
        """One opening level: the shard's multi-slot pass over its own
        rows, then the wave's ONE reduce-scatter and the subtraction."""
        if self._multislot_opening():
            return self._scattered_children(
                st, self._multislot_hists(st, sm_slot, valid), ph, lh_w,
                rh_w, left_small)
        return super()._opening_hists(st, sm_slot, valid, ph, lh_w, rh_w,
                                      left_small)

    def _scattered_children(self, st: WaveState, h_local, ph, lh_w, rh_w,
                            left_small):
        # (W, f_pad, B, 3) -> (W, fs, B, 3): one collective per wave,
        # int16-packed in quantized mode (_exchange)
        return self._subtract_children(st, self._exchange(h_local, 1), ph,
                                       lh_w, rh_w, left_small)

    def _make_hist_branch_shard(self, S: int):
        # with ``_hist_cols`` = f_pad the serial branch IS the shard's
        # (padded features kept), and it runs the packed kernel
        if self._use_pallas:
            return self._make_hist_branch(S)
        return super()._make_hist_branch_shard(S)

    def _hist_dtype(self):
        import jax.numpy as jnp
        return jnp.float64 if self.hist_dp else jnp.float32

    # -- the sharded wave tree ----------------------------------------------

    def _train_tree_wave_sharded(self, bins_p, grad, hess, bag, fmask_pad):
        self._ledger.begin_trace()
        self._hist_branches = [self._make_hist_branch_shard(S)
                               for S in self._win_sizes]
        self._stall_branches = [
            self._make_stall_branch(S, sort_mode=S > self._stall_cutoff)
            for S in self._win_sizes]
        with scope("root"):
            st = self._init_root_wave(bins_p, grad, hess, bag, fmask_pad)
        return self._emit_tree_wave(self._grow_tree(st, fmask_pad),
                                    fmask_pad)

    def train_async(self, grad: jax.Array, hess: jax.Array, bag: jax.Array,
                    feature_mask: Optional[jax.Array] = None):
        return self._pop_telem(self._tree_program()(
            self.sharded_bins(), grad, hess, bag,
            self._padded_feature_mask(feature_mask)))

    def _tree_program(self):
        """The jitted ``shard_map`` tree step (bins, grad, hess, bag, padded
        feature mask), built on first use."""
        if self._jit_tree_w is None:
            ax = self.axis
            out_specs = (P(), P(), P(), P(ax), P())
            if self._telemetry:  # the counter lane is replicated bookkeeping
                out_specs = out_specs + (P(),)
            kw = dict(mesh=self.mesh,
                      in_specs=(self._bins_spec(), P(ax), P(ax), P(ax), P()),
                      out_specs=out_specs)
            fn = jax.shard_map(self._train_tree_wave_sharded,
                               check_vma=False, **kw)
            self._jit_tree_w = jax.jit(fn, donate_argnums=(1, 2)) \
                if self._donate else jax.jit(fn)
        return self._jit_tree_w

    def _bins_spec(self) -> P:
        return P(None, self.axis)           # every word, a device's rows

    def lowered_hlo_text(self) -> str:
        # grad/hess are donate_argnums under _donate: each position gets
        # its OWN buffer so the donated args never alias bag (LGB009)
        n = self.n_pad
        g, h, b = (jnp.zeros(n, jnp.float32) for _ in range(3))
        fmask_pad = jnp.ones(self.f_pad, bool)
        return self._tree_program().lower(
            self.sharded_bins(), g, h, b, fmask_pad).compile().as_text()

    def exchange_probe(self):
        """The wave learner's real per-wave exchange: ONE batched
        psum_scatter over the (W, f_pad, B, 3) member histograms,
        scattered over the feature axis (`_wave_member_hists`)."""
        if getattr(self, "_probe_fn", None) is None:
            return self._probe_program(
                lambda h: self._exchange(h, 1), P(),
                P(None, self.axis),
                (jnp.zeros((self.W, self.f_pad, self.num_bins_padded, 3),
                           self._hist_dtype()),))
        return self._probe_fn, self._probe_args


class ShardedVotingWaveLearner(ShardedWaveLearner):
    """``tree_learner=voting`` on the frontier-wave learner: the histogram
    pool stays LOCAL-unreduced (exactly like the sequential
    ``ShardedVotingLearner``) and every wave's 2W children each run the
    PV-Tree election — local top-k votes, global top-2k election, elected
    features' histograms reduce-scattered and scanned
    (`voting_parallel_tree_learner.cpp:166-345`) — inside the one batched
    candidate scan, so the election happens once per wave instead of once
    per split."""

    def __init__(self, cfg: Config, data: _ConstructedDataset, mesh: Mesh,
                 hist_backend: str = "auto"):
        super().__init__(cfg, data, mesh, hist_backend)
        from .compact_sharded import ShardedVotingLearner
        ShardedVotingLearner._init_voting_sizing(self, cfg)
        # no opening, whatever is asked: its levels would scan full spans
        # member by member into a pool that stays local, and the segment
        # kernel's chunk capacity does not hold K full spans
        self.open_levels = 0

    def _reduce_hist(self, local_hist):
        # the pool stays LOCAL; reduction happens per elected feature set
        return local_hist

    def _reduce_hist_batch(self, local_hists):
        # batched stall-correction histograms stay local too (the voting
        # protocol reduces only elected features inside the candidate scan)
        return local_hists

    def _wave_member_hists(self, st, sm_slot, sm_start, sm_cnt, valid, ph,
                           lh_w, rh_w, left_small):
        # local full-width member histograms, NO exchange — subtraction
        # against the local pool (the voting protocol reduces only the
        # elected features inside the candidate scan)
        return WaveTPUTreeLearner._wave_member_hists(
            self, st, sm_slot, sm_start, sm_cnt, valid, ph, lh_w, rh_w,
            left_small)

    def _cand_rows_batch(self, hists, sg, sh, cn, feature_mask, depth_ok,
                         constraints):
        from .compact_sharded import ShardedVotingLearner
        return ShardedVotingLearner._best_rows_global(
            self, hists, (sg, sh, cn), feature_mask, depth_ok, constraints)

    def exchange_probe(self):
        # voting's wire payload is the elected (2k-wide) feature set —
        # probe that seam, not the full-width wave exchange
        from .compact_sharded import ShardedVotingLearner
        return ShardedVotingLearner.exchange_probe(self)


def wave_sharded_eligible(cfg: Config, data: _ConstructedDataset,
                          mesh_size: int) -> bool:
    """The sharded wave learner reuses the serial wave shape/byte gates
    with the PER-DEVICE shard length (no EFB condition — the sharded path
    never bundles).  NOTE: ``wave_budget_reason`` sizes the histogram pool
    at the FULL feature width — exact for the voting learner's
    local-unreduced pool, conservative for data-parallel's scattered one;
    keep it that way if the formula is ever tightened."""
    if cfg.tpu_learner not in ("auto", "wave"):
        return False       # explicit compact/masked request is honored
    if data.max_num_bin > 256:
        return False
    if data.num_data_padded % max(mesh_size, 1):
        return False
    if data.bins.shape[0] % max(mesh_size, 1):
        return False
    return wave_budget_reason(
        cfg, int(data.num_data_padded) // max(mesh_size, 1),
        data.bins.shape[0], int(data.max_num_bin)) is None
