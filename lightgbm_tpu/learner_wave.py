"""Frontier-wave TPU tree learner: batched speculative leaf-wise growth.

The sequential compact learner (`learner_compact.py`) builds a tree as 254
dependent split steps inside one XLA program: per-step bookkeeping and
per-window sort latency before any real data work.  This learner
restructures the growth into a few *frontier waves* (nine for 255 leaves at
10.5M rows) while preserving exact best-first (leaf-wise) semantics:

  1. **Grow.**  Each wave splits the top-W positive-gain frontier leaves at
     once: one full-array sort (``growth_sort``, the permutation of a
     stable sort on the window keys; 128.3 ms at 10.5M rows: ledger, PR 29)
     re-compacts every split window simultaneously, on every second wave
     (per-row split parameters come from an MXU mask-matmul, never an XLA
     gather: ``jnp.take`` over 10.5M rows read 86.5 ms; ledger, PR 26,
     parent side), then the smaller-child histograms run per member
     (subtraction for siblings) and all 2W children are scanned in one
     batched split finder.  Where a level's histograms are one multi-slot
     kernel pass the first levels grow UNSORTED (the opening:
     ``tpu_wave_open_levels``) and one sort compacts them all.
  2. **Trim.**  An exact greedy replay over the grown forest re-derives the
     reference's pop order (`serial_tree_learner.cpp:185-218`: split the
     globally best leaf, insert its children): children's gains are all
     known, so the replay is pure bookkeeping (``phase_replay_ms_per_iter``
     35 to 43 ms, half of it stall corrections: ledger, PR 30).  The
     replayed pop sequence assigns the reference leaf numbering (left child
     inherits the parent index, right child gets ``num_leaves``), emits the
     host-assembly records in pop order, and maps speculative leaves back
     to their final ancestors.
  3. **Correct.**  If the replay wants to pop a leaf the growth never split
     (possible near the num_leaves budget where speculation and greedy can
     diverge), it splits that leaf on the spot — a mask-mode single split —
     and continues.  Slot arrays are sized so this path can never overflow
     (growth ≤ budget splits, stalls ≤ budget pops), so the result is
     always *exactly* the best-first tree.

Everything the sequential learners guarantee is preserved: identical gain
math and tie-breaks (lowest leaf index, `serial_tree_learner.cpp:505-520`),
smaller-child histogram + sibling subtraction (`:371-385`), monotone
constraint propagation, categorical bitset splits, EFB bundle decoding,
exact integer bagged counts, and the host record format — so
``assemble_host`` and the whole boosting loop are unchanged.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .binning import MISSING_NAN, MISSING_ZERO
from .config import Config
from .dataset import _ConstructedDataset
from .learner import NUM_REC_FIELDS
from .learner_compact import (CF_GAIN, CF_LCNT, CF_LOUT, CF_LSG, CF_LSH,
                              CF_RCNT, CF_ROUT, CF_RSG, CF_RSH, CI_FEAT,
                              CI_FLAGS, CI_THR, LF_CNT, LF_DEPTH, LF_MAX_C,
                              LF_MIN_C, LF_OUT, LF_SUM_G, LF_SUM_H, NUM_CF,
                              NUM_CI, NUM_LF, CompactTPUTreeLearner,
                              to_row_order)
from .observability.phases import scope
from .observability.telemetry import (TEL_FROZEN_MEMBERS, TEL_GROW_SPLITS,
                                      TEL_NSLOTS, TEL_POPS,
                                      TEL_STALL_EXTRAS, TEL_STALL_SORT_MODE,
                                      TEL_STALL_SPLITS, TEL_TOTAL_SPLITS,
                                      TEL_WAVE_MEMBERS, TEL_WAVE_SORTS,
                                      TEL_WAVES)
from .ops.histogram import _on_tpu
from .ops.lookup import lookup_int

_HIGH = lax.Precision.HIGHEST


def _stall_extras_cap(budget: int) -> int:
    """Cap on speculative batch EXTRAS (members beyond the sim's stalled
    top) across the whole replay — a dedicated counter in the replay loop
    enforces it, so the slot/pool reserve stays tight."""
    return min(budget - 1, 64)


def _resolve_stall_batch(cfg: Config) -> int:
    """``tpu_wave_stall_batch`` with -1 = auto = 4 at every scale (no
    reading in the ledger compares widths: ROADMAP queue 3, item 3)."""
    k = int(getattr(cfg, "tpu_wave_stall_batch", -1))
    if k < 0:
        k = 4
    return max(1, min(k, 16))


def _correction_reserve(cfg: Config, budget: int) -> int:
    """Worst-case replay correction splits, for slot/hist-pool sizing.

    Every stalled TOP maps to a distinct pop, so tops <= budget; batch
    extras (stall_batch > 1) are counted separately in the replay loop
    and capped at ``_stall_extras_cap``.  Shared by ``_init_wave_dims``
    and ``wave_budget_reason`` so the formulas cannot drift."""
    k = _resolve_stall_batch(cfg)
    return budget if k == 1 else budget + _stall_extras_cap(budget)


def _resolve_overshoot(cfg: Config, local_rows: int) -> float:
    """Auto for ``tpu_wave_overshoot`` (see config.py).

    With batched mask-mode replay corrections (``tpu_wave_stall_batch`` >
    1, the default) a speculation miss costs ~window-sized work amortized
    over K members, so buying misses down with extra speculative waves —
    whose full-array passes cost ∝N — no longer pays AT ANY SCALE:
    overshoot 0 wins (v5e: 9.28 vs 8.05 it/s at 1M, 0.854 vs 0.770 at
    10.5M).  The single-miss-per-pass path (stall_batch=1) keeps the
    round-4 scale-dependent optimum (0.7 at 1M, 0.25 at 10.5M)."""
    ov = float(cfg.tpu_wave_overshoot)
    if ov < 0:
        if _resolve_stall_batch(cfg) > 1:
            ov = 0.0
        else:
            ov = 0.7 if local_rows <= 2_000_000 else 0.25
    return ov


# auto depth of the unsorted opening and the local rows from which it pays
# (my chip runs, PR 31; PERF.md section 6).  Five levels (members 1, 2, 4,
# 8, 16) leave 32 + 64 + 64 + 63 splits to four waves, which sort, defer,
# sort, defer: TWO full-array sorts a 255-leaf tree where the sorted ramp
# pays four.  At 10.5M rows a sort costs 128 ms (12.2 ns a row) and the five
# multi-slot passes 289; at 1M rows a sort costs 4.6 ns a row, a pass 2.7 as
# at every size, and the opening loses; 9.0 ns a row at 4.19M (PR 30)
_AUTO_OPEN_LEVELS = 5
_AUTO_OPEN_MIN_ROWS = 1 << 22


def _resolve_open_levels(cfg: Config, local_rows: int,
                         multislot: bool) -> int:
    """``tpu_wave_open_levels`` with -1 = auto (see config.py): an explicit
    depth keeps its meaning; auto opens ``_AUTO_OPEN_LEVELS`` levels where
    each level's histograms are ONE multi-slot kernel pass (``multislot``:
    the serial or data-parallel learner on the Pallas path) over at least
    ``_AUTO_OPEN_MIN_ROWS`` local rows, and none anywhere else (the fallback
    pays K full-span scans a level; a sort over few rows is cheap)."""
    ol = int(cfg.tpu_wave_open_levels)
    if ol >= 0:
        return ol
    if multislot and local_rows >= _AUTO_OPEN_MIN_ROWS:
        return _AUTO_OPEN_LEVELS
    return 0


def _segment_grid_buckets(capacity: int, width: int, opened: bool) -> list:
    """Grid sizes (chunk capacities) the segment kernel is built for at one
    call site, largest first: late waves have few real chunks, so the call
    picks the smallest that holds them and no-op grid cells don't dominate.
    The ladder halves from ``capacity`` down to ``2 * width``.  A program
    that opens the ramp (``opened``) keeps no bucket under 256 chunks:
    every bucket is one more trace and lowering of the kernel in the job's
    first iteration, and the opening's five bodies have to be paid for
    there (``setup_s``: PERF.md section 6, PR 31).  Every other program
    (CPU, voting, 2-D, under 2^22 rows) keeps the whole ladder."""
    floor = 2 * width
    if opened:
        floor = min(max(floor, 256), capacity)
    sizes = []
    cap = capacity
    while cap > floor:
        sizes.append(cap)
        cap //= 2
    sizes.append(max(floor, cap))
    return sizes


class WaveState(NamedTuple):
    # row payloads, permuted so every leaf's rows are contiguous
    bins_p: jax.Array     # (fw, N) int32 packed bin words
    w_p: jax.Array        # (3, N) f32 (g*bag, h*bag, bag)
    rid_p: jax.Array      # (N,) int32 original row ids
    lid_p: jax.Array      # (N,) int32 node-slot ids
    key_p: jax.Array      # (N,) int32 window-order sort keys: 2 x the
    #                       start of the row's logical window
    # per-node-slot state (M slots; a split allocates 2 fresh child slots)
    node_i: jax.Array     # (M, 2) int32 LOGICAL window [start, width]
    phys_i: jax.Array     # (M, 2) int32 materialized covering span (equals
    #                       node_i except for children created on a
    #                       sort-DEFERRING wave, whose rows still live in
    #                       the parent's span until the next sort)
    node_f: jax.Array     # (M, NUM_LF) acc sums/cnt/out/depth/bounds
    cand_f: jax.Array     # (M, NUM_CF) acc best-split floats
    cand_i: jax.Array     # (M, NUM_CI) int32 feature/threshold/flags
    cand_b: jax.Array     # (M, Wc) uint32 categorical bitsets
    parent: jax.Array     # (M,) int32
    child0: jax.Array     # (M,) int32 left child slot (right = +1)
    hslot: jax.Array      # (M,) int32 histogram pool slot
    split_m: jax.Array    # (M,) bool node has been split
    cnt_i: jax.Array      # (M, 2) int32 exact bagged child counts at split
    hist_pool: jax.Array  # (H, F, B, 3)
    num_nodes: jax.Array  # () int32
    num_splits: jax.Array  # () int32
    pending: jax.Array    # () bool — keys assigned but not yet sorted
    # (TEL_NSLOTS,) int32 device counter lane, or None when telemetry is
    # off — None is an empty pytree, so the disabled program is unchanged
    telem: Optional[jax.Array] = None


# the growth sort carries the bagging bit above the node slot in ONE int32
_BAG_SHIFT = 30


def growth_sort_operands(fw: int) -> int:
    """Row-sized operands of ``growth_sort`` at ``fw`` bin words: key, row
    id, the words, two weight lanes, node slot with the bagging bit."""
    return fw + 5


def growth_sort(key_p, bins_p, w_p, rid_p, lid_p, num_slots: int):
    """The full-array sort that re-compacts every keyed window: the five
    row payloads of a ``WaveState`` permuted by ``key_p``, ties in position
    order, as a stable sort on ``key_p`` would leave them.  Only operands
    that hold information are sorted (ledger, PR 28: a sort costs 10 ms an
    operand at 10.5M rows):

    * no stability, which the chip pays for with a hidden row-index
      operand: ``rid_p`` starts a tree as ``arange`` and every permutation
      since is a stable partition of windows, so among rows of one key
      position order IS ``rid_p`` order, and ``rid_p`` sorts as second key;
    * ``w_p[2]`` is the bagging mask, 0.0 or 1.0 on every path, and rides
      as bit ``_BAG_SHIFT`` of the ``lid_p`` operand (node slots lie below
      ``num_slots``)."""
    fw = bins_p.shape[0]
    assert num_slots <= 1 << _BAG_SHIFT, num_slots
    lid_bag = lid_p | ((w_p[2] > 0.5).astype(jnp.int32) << _BAG_SHIFT)
    ops = ([key_p, rid_p] + [bins_p[i] for i in range(fw)]
           + [w_p[0], w_p[1], lid_bag])
    assert len(ops) == growth_sort_operands(fw)
    sd = lax.sort(ops, num_keys=2, is_stable=False)
    lid_bag = sd[4 + fw]
    w_p = jnp.stack([sd[2 + fw], sd[3 + fw],
                     (lid_bag >> _BAG_SHIFT).astype(w_p.dtype)])
    return (sd[0], jnp.stack(sd[2:2 + fw]), w_p, sd[1],
            lid_bag & ((1 << _BAG_SHIFT) - 1))


def _segment_row_block(rows: int) -> int:
    """Row block of the segment histogram kernel: the largest power of two
    up to 2048 that divides the row axis."""
    rb = min(2048, rows)
    while rows % rb:
        rb //= 2
    return rb


class WaveTPUTreeLearner(CompactTPUTreeLearner):
    """Frontier-wave serial learner (factory slot
    `src/treelearner/tree_learner.cpp:9-33`, tree_learner=serial,
    device_type=tpu; supersedes the sequential compact learner where
    eligible)."""

    def __init__(self, cfg: Config, data: _ConstructedDataset,
                 hist_backend: str = "auto"):
        super().__init__(cfg, data, hist_backend)
        self._init_wave_dims(cfg)
        F = self.num_features
        if self._bundle is not None:
            col = np.asarray(self._bundle.f_gcol, np.int32)
            goff = np.asarray(self._bundle.f_off, np.int32)
            bnd = np.asarray(self._bundle.f_bundled, np.int32)
        else:
            col = np.arange(F, dtype=np.int32)
            goff = np.zeros(F, np.int32)
            bnd = np.zeros(F, np.int32)
        self.fw_col = jnp.asarray(col)
        self.fw_goff = jnp.asarray(goff)
        self.fw_bnd = jnp.asarray(bnd)
        self._seg_rb = _segment_row_block(self.n_pad)
        # fused Pallas split-scan (Config.tpu_wave_pallas_scan): the
        # batched child scans run as one kernel; constrained/categorical/
        # penalized/f64 configs keep the XLA path (scan_ineligible_reason)
        from .ops.scan_pallas import scan_ineligible_reason
        sp = str(getattr(cfg, "tpu_wave_pallas_scan", "auto"))
        s_reason = scan_ineligible_reason(
            self.num_features, self.num_bins_padded, self.has_monotone,
            self.has_categorical, self.has_penalty, self.hist_dp)
        if sp == "on":
            self._use_scan = s_reason is None
            self._scan_interpret = not _on_tpu()
        elif sp == "auto":
            self._use_scan = self._use_pallas and s_reason is None
            self._scan_interpret = False
        else:
            self._use_scan = False
            self._scan_interpret = False
        if self._donate:
            # jax matches donated inputs to outputs by EXACT aval at
            # num_partitions=1 (mlir._set_up_aliases) and the tree
            # program has no f32[n_pad] output, so a bare donate_argnums
            # here is silently dropped ("donated buffers were not
            # usable") — the sharded learners only escape because the
            # SPMD path routes donation through XLA's size-matching
            # buffer_donor pass.  Bitcasting leaf_id (int32[n_pad]) out
            # as its f32 bit-pattern gives the donated grad buffer a
            # landing slot; train_async casts it back at the call seam.
            # That is the program's ONLY n_pad-sized output, so only grad
            # is donated: a donated hess has nowhere to land (the v5e
            # compile reports it "not usable" and leaves it allocated).
            # The analysis gate asserts input_output_alias in this
            # program's compiled HLO (analysis/donation.py).
            def _tree_w_donating(bins_p, grad, hess, bag, fmask):
                out = self._train_tree_wave(bins_p, grad, hess, bag,
                                            fmask)
                leaf_f32 = jax.lax.bitcast_convert_type(out[3],
                                                        jnp.float32)
                return out[:3] + (leaf_f32,) + out[4:]

            self._jit_tree_w = jax.jit(_tree_w_donating,
                                       donate_argnums=(1,))
            self._tree_w_bitcast = True
        else:
            self._jit_tree_w = jax.jit(self._train_tree_wave)
            self._tree_w_bitcast = False

    def _fused_ok(self) -> bool:
        """Whether this learner runs the fused hist→subtract→fix→scan
        chain (``ops/scan_pallas.py:fused_child_scans``).  Quant mode
        only (the packed-histogram layout is what makes one kernel pay),
        and only where BOTH the batched scan path and the serial member
        hists apply — the sharded subclasses interpose a collective
        between the member hists and the scans, which the fused kernel
        cannot straddle."""
        from .ops.scan_pallas import fused_scan_ineligible_reason
        return (self._quant and getattr(self, "_use_scan", False)
                and self._bundle is None
                and type(self)._cand_rows_batch
                is WaveTPUTreeLearner._cand_rows_batch
                and type(self)._wave_member_hists
                is WaveTPUTreeLearner._wave_member_hists
                and fused_scan_ineligible_reason(
                    self.num_features, self._hist_nbins) is None)

    def _init_wave_dims(self, cfg: Config) -> None:
        """Wave sizing/bookkeeping shared by the serial and sharded wave
        learners (kept in one place so the slot/pool formulas can't drift
        from ``wave_ineligible_reason``'s byte estimate).

        Growth OVERSHOOTS the split budget: speculative top-W selection
        near the end of the budget misses leaves the exact greedy replay
        wants (measured: 40 replay stalls per 255-leaf tree at 1M rows,
        each a full sequential split step), while extra bottom waves are
        cheap (small windows freeze — no sort).  The replay still pops
        exactly ``budget`` splits, so the tree is unchanged.  Slot/pool
        sizing makes overflow impossible: growth performs <= grow_budget
        splits, the replay correction <= ``_correction_reserve`` more."""
        self.budget = self.num_leaves - 1
        self.W = max(1, min(int(cfg.tpu_wave_width), self.budget))
        try:
            rows = self._rows_len()
        except AttributeError:
            # sharded learners reach here mid-MRO (WaveTPUTreeLearner's
            # __init__ runs before ShardedCompactLearner sets n_local);
            # their own __init__ re-runs _init_wave_dims with local rows
            rows = self.n_pad
        ov = _resolve_overshoot(cfg, rows)
        self.grow_budget = min(
            self.budget + int(np.ceil(self.budget * ov)),
            2 * self.budget)
        # sort-deferral alternation (Config.tpu_wave_defer_sorts)
        self._defer_sorts = bool(getattr(cfg, "tpu_wave_defer_sorts", True))
        # replay stall-correction batch width (Config.tpu_wave_stall_batch)
        self._stall_batch = _resolve_stall_batch(cfg)
        self._stall_fuse_top = bool(
            getattr(cfg, "tpu_wave_stall_fuse_top", True))
        self._extras_cap = _stall_extras_cap(self.budget)
        # vectorized-partition span cap (tests shrink it via config so the
        # replicated gate is exercised at CI sizes)
        vc = int(getattr(cfg, "tpu_wave_vec_cap", -1))
        self._vec_cap = self._VEC_CAP if vc <= 0 else vc
        corr = _correction_reserve(cfg, self.budget)
        self.M = 1 + 2 * (self.grow_budget + corr)
        self.H = self.grow_budget + corr + 2
        # row-chunk bound for the per-row mask contractions: bounds the
        # (rows, W) transients to ~256 MB at any N (lax.map'd above it)
        self._row_chunk = 1 << 20
        # frozen (shared-span) windows can be as large as the wave cutoff,
        # so phase-2 stall splits may only sort above it (a sort-mode
        # partition of a shared window would reorder sibling rows)
        self._wave_cutoff = int(cfg.tpu_wave_sort_cutoff)
        self._stall_cutoff = max(self._sort_cutoff, self._wave_cutoff)
        # quantized-gradient training (Config.tpu_quantized_grad): int8
        # gradient / int16 hessian discretization with stochastic rounding
        # (ops/quant.py — the LightGBM quantized-training recipe).  Set
        # HERE, not in __init__: the 2-D sharded learner re-runs
        # _init_wave_dims without ever entering WaveTPUTreeLearner's
        # __init__, and every wave learner must agree on the gate
        from .ops.quant import quant_ineligible_reason
        qg = str(getattr(cfg, "tpu_quantized_grad", "auto"))
        # gate on the GLOBAL padded row count (a reduced histogram bin can
        # hold every row), not the shard-local window the wave sizing uses
        q_reason = quant_ineligible_reason(self.n_pad, self.hist_dp)
        if qg == "on":
            self._quant = q_reason is None
        else:
            # auto stays OFF until an on-hardware win is recorded
            # (ROADMAP queue 1 item 3 tracks the TPU leg)
            self._quant = False
            if q_reason is None:
                q_reason = "tpu_quantized_grad=%s (quantization is " \
                           "opt-in)" % qg
        self._quant_reason = None if self._quant else q_reason
        # level-wise opening depth (Config.tpu_wave_open_levels)
        self.open_levels = min(
            _resolve_open_levels(cfg, rows, self._multislot_opening()
                                 and not self._quant),
            (self.budget + 1).bit_length() - 1)
        self._q_inv = None
        self._q_scales = None
        self._q_raw = None
        self._q_cnt = None
        self._q_mbar = None
        # cross-iteration buffer donation (Config.tpu_donate_buffers):
        # grad/hess enter the tree program donated so iteration N+1 reuses
        # iteration N's HBM; auto = on-TPU only (the CPU backend gains
        # nothing and donation muddies interpret-mode debugging)
        dn = str(getattr(cfg, "tpu_donate_buffers", "auto"))
        self._donate = dn == "on" or (dn == "auto" and _on_tpu())
        if str(getattr(cfg, "boosting", "gbdt")) == "rf":
            # random forest refits from ONE retained gradient set every
            # iteration (rf.py keeps _rf_grad across iters); donating
            # those buffers would invalidate them after the first tree
            self._donate = False

    # -- batched split finder -------------------------------------------------

    def _cand_rows_batch(self, hists, sg, sh, cn, feature_mask, depth_ok,
                         constraints):
        """Best-split rows for K children in one vmapped scan
        (generalizes ``_cand_rows_pair``).  With the fused Pallas
        split-scan enabled the whole (K, F, B) search — cumulative
        scans, gain masks, per-feature argmax — runs as one kernel."""
        if getattr(self, "_use_scan", False) and constraints is None:
            from .learner import _FeatCand
            from .ops.scan_pallas import find_best_splits_batched
            h = hists
            if self._bundle is not None:
                h = jax.vmap(self._unbundle_hist)(h, sg, sh, cn)
            h = jax.vmap(self._fix_histogram)(h, sg, sh, cn)
            kw = {k: v for k, v in self._split_kwargs.items()
                  if k != "skip_missing_scan"}
            num = find_best_splits_batched(
                h, sg, sh, cn, self.f_num_bin, self.f_missing,
                self.f_default_bin, feature_mask & self._cat_mask,
                interpret=self._scan_interpret, **kw)
            kk = num.gain.shape[0]
            f = self.num_features
            cands = _FeatCand(
                gain=num.gain, threshold=num.threshold,
                default_left=num.default_left,
                is_cat=jnp.zeros((kk, f), bool),
                cat_bits=jnp.zeros((kk, f, self.cat_W), jnp.uint32),
                left_sum_g=num.left_sum_g, left_sum_h=num.left_sum_h,
                left_cnt=num.left_cnt, right_sum_g=num.right_sum_g,
                right_sum_h=num.right_sum_h, right_cnt=num.right_cnt,
                left_output=num.left_output,
                right_output=num.right_output)
            return self._pack_cand_rows(cands, depth_ok)
        if constraints is not None:
            mins, maxs = constraints
            cands = jax.vmap(
                lambda h, g, hh, c, mn, mx: self._feature_cands(
                    h, g, hh, c, feature_mask, mn, mx)
            )(hists, sg, sh, cn, mins, maxs)
        else:
            cands = jax.vmap(
                lambda h, g, hh, c: self._feature_cands(h, g, hh, c,
                                                        feature_mask)
            )(hists, sg, sh, cn)
        return self._pack_cand_rows(cands, depth_ok)

    # -- root -----------------------------------------------------------------

    def _init_root_wave(self, bins_p, grad, hess, bag, feature_mask
                        ) -> WaveState:
        n, L, M, H = self._rows_len(), self.num_leaves, self.M, self.H
        acc = self._acc
        self._coll_ctx = ("root", "tree")
        if self._quant:
            # per-round discretization (ops/quant.py): power-of-two
            # scales from the GLOBAL |g|/h maxima, stochastic rounding
            # keyed on the global row index.  The weight lanes carry the
            # DEQUANTIZED values gq*sg / hq*sh — exact in bf16, so the
            # Pallas quant hist path and sibling subtraction stay
            # bit-exact — and the scale tuple rides trace-time attributes
            # that the hist-branch closures read within this same trace.
            from .ops.quant import quantize_gradients
            gb = (grad * bag).astype(jnp.float32)
            hb = (hess * bag).astype(jnp.float32)
            mx = self._global_max(jnp.stack([jnp.max(jnp.abs(gb)),
                                             jnp.max(hb)]))
            gd, hd, sg, sh = quantize_gradients(
                gb, hb, bag, self._global_row_offset(), mx[0], mx[1])
            self._q_scales = (sg, sh)
            self._q_inv = (1.0 / sg, 1.0 / sh)
            self._q_raw = (gb, hb)     # retained f32 for leaf renewal
            w = jnp.stack([gd, hd, bag], axis=0)
            # count-channel normalization, BEFORE any histogram builds
            # (the branch closures read _q_cnt): the channel carries
            # Σhq/m̄ — hessian mass over the mean mass per bagged row —
            # so min_data_in_leaf keeps its row-count scale (raw Σhq
            # admits ~m̄× smaller leaves and the trees grow much deeper,
            # see ops/quant.py).  All three sums are exact integer
            # multiples of their scale within the F32_EXACT_ROWS gate,
            # so m̄ and every derived rescale are order-independent and
            # the sharded learners stay record-exact.
            q_tot = self._global_scalar(jnp.stack(
                [jnp.sum(gd.astype(acc)), jnp.sum(hd.astype(acc)),
                 jnp.sum(bag.astype(acc))]))
            mbar = jnp.maximum(q_tot[1] * self._q_inv[1], 1.0) \
                / jnp.maximum(q_tot[2], 1.0)
            self._q_mbar = mbar
            self._q_cnt = self._q_inv[1] / mbar
        else:
            w = jnp.stack([grad * bag, hess * bag, bag], axis=0)
        lid0 = jnp.zeros(n, jnp.int32)
        with scope("hist"):
            root_hist = self._reduce_hist(
                self._hist_branches[-1](bins_p, w, lid0, jnp.int32(0),
                                        jnp.int32(n), jnp.int32(0)))
        if self._quant:
            # root totals from the DEQUANTIZED lanes so FixHistogram's
            # totals-minus-others algebra matches the histogram contents;
            # the count total rides the same normalized Σhq/m̄ scale as
            # the histogram count channel
            sum_g, sum_h = q_tot[0], q_tot[1]
            cnt = (sum_h * self._q_cnt).astype(acc)
        else:
            sum_g = self._global_scalar(jnp.sum((grad * bag).astype(acc)))
            sum_h = self._global_scalar(jnp.sum((hess * bag).astype(acc)))
            # the bagged rows cross a mesh as an INTEGER: four shards of a
            # 53M-row job sum past 2^24, where float32 skips odd numbers
            cnt = self._global_scalar(
                jnp.sum(bag > 0.5, dtype=jnp.int32)).astype(acc)
        md = int(self.cfg.max_depth)
        depth_ok = jnp.asarray([True if md <= 0 else md > 0])
        with scope("scan"):
            cf, ci, cb = self._cand_rows_batch(
                root_hist[None], sum_g[None], sum_h[None], cnt[None],
                feature_mask, depth_ok, None)
        root_lf = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.0, -jnp.inf, jnp.inf],
                              acc)
        root_lf = root_lf.at[LF_SUM_G].set(sum_g).at[LF_SUM_H].set(sum_h) \
                         .at[LF_CNT].set(cnt)
        return WaveState(
            bins_p=bins_p, w_p=w,
            rid_p=jnp.arange(n, dtype=jnp.int32),
            lid_p=lid0,
            key_p=jnp.zeros(n, jnp.int32),
            node_i=jnp.zeros((M, 2), jnp.int32).at[0, 1].set(n),
            phys_i=jnp.zeros((M, 2), jnp.int32).at[0, 1].set(n),
            node_f=jnp.zeros((M, NUM_LF), acc)
                      .at[:, LF_MIN_C].set(-jnp.inf)
                      .at[:, LF_MAX_C].set(jnp.inf)
                      .at[0].set(root_lf),
            cand_f=jnp.zeros((M, NUM_CF), acc)
                      .at[:, CF_GAIN].set(-jnp.inf)
                      .at[0].set(cf[0]),
            cand_i=jnp.zeros((M, NUM_CI), jnp.int32).at[0].set(ci[0]),
            cand_b=jnp.zeros((M, self.cat_W), jnp.uint32).at[0].set(cb[0]),
            parent=jnp.zeros(M, jnp.int32),
            child0=jnp.zeros(M, jnp.int32),
            hslot=jnp.zeros(M, jnp.int32),
            split_m=jnp.zeros(M, bool),
            cnt_i=jnp.zeros((M, 2), jnp.int32),
            hist_pool=jnp.zeros((H,) + root_hist.shape, root_hist.dtype)
                         .at[0].set(root_hist),
            num_nodes=jnp.asarray(1, jnp.int32),
            num_splits=jnp.asarray(0, jnp.int32),
            pending=jnp.asarray(False),
            telem=(jnp.zeros(TEL_NSLOTS, jnp.int32)
                   if self._telemetry else None))

    # -- one growth wave ------------------------------------------------------

    def _pool_gains(self, st: WaveState):
        alive = (jnp.arange(self.M) < st.num_nodes) & ~st.split_m
        return jnp.where(alive, st.cand_f[:, CF_GAIN], -jnp.inf)

    def _children_bookkeeping(self, st, wi, valid, lslot, rslot, lc_bag,
                              c_bag, li, ri, lh, rh, hists2, feature_mask,
                              phys_l=None, phys_r=None, fused_parts=None):
        """Shared by the wave body (K=W) and the stall split (K=1): writes
        all per-child node state given the children's histograms.
        ``phys_l/phys_r`` are the children's materialized covering spans
        (default: the logical windows — correct whenever the caller's rows
        are physically compacted, as in the stall split).

        ``fused_parts`` (quant fused mode): ``(h_small, ph, left_small,
        lh_w, rh_w)`` — the caller computed ONLY the smaller-child
        histograms and ``hists2`` is None; sibling subtraction, the
        default-bin fix and both child split scans run inside one Pallas
        kernel here (``ops/scan_pallas.py:fused_child_scans``), which
        also hands back the raw child histograms for the pool writes."""
        if phys_l is None:
            phys_l, phys_r = li, ri
        acc = self._acc
        K = wi.shape[0]
        pcf = st.cand_f[wi]                       # (K, NUM_CF)
        pci = st.cand_i[wi]
        pnf = st.node_f[wi]
        cd = pnf[:, LF_DEPTH] + 1.0
        md = int(self.cfg.max_depth)
        if md <= 0:
            depth_ok = jnp.ones(2 * K, bool)
        else:
            depth_ok = jnp.repeat(cd < md, 2)
        # monotone constraint propagation (`serial_tree_learner.cpp:765-776`)
        pmin = pnf[:, LF_MIN_C]
        pmax = pnf[:, LF_MAX_C]
        if self.has_monotone:
            feat = pci[:, CI_FEAT]
            is_cat = (pci[:, CI_FLAGS] & 2) == 2
            mono_t = jnp.where(is_cat, 0, self.f_monotone[feat])
            mid = ((pcf[:, CF_LOUT] + pcf[:, CF_ROUT]) / 2.0).astype(acc)
            lmin = jnp.where(mono_t < 0, mid, pmin)
            lmax = jnp.where(mono_t > 0, mid, pmax)
            rmin = jnp.where(mono_t > 0, mid, pmin)
            rmax = jnp.where(mono_t < 0, mid, pmax)
            mins2 = jnp.stack([lmin, rmin], 1).reshape(-1)
            maxs2 = jnp.stack([lmax, rmax], 1).reshape(-1)
            constraints = (mins2, maxs2)
        else:
            lmin = rmin = pmin
            lmax = rmax = pmax
            constraints = None
        # batched child split scans
        i2 = lambda a, b: jnp.stack([a, b], 1).reshape(-1)  # interleave K->2K
        sg2 = i2(pcf[:, CF_LSG], pcf[:, CF_RSG])
        sh2 = i2(pcf[:, CF_LSH], pcf[:, CF_RSH])
        cn2 = i2(pcf[:, CF_LCNT], pcf[:, CF_RCNT])
        if fused_parts is not None:
            from .learner import _FeatCand
            from .ops.scan_pallas import fused_child_scans
            h_small, ph_k, left_small, lh_w, rh_w = fused_parts
            h_par = st.hist_pool[ph_k]
            kw = {k: v for k, v in self._split_kwargs.items()
                  if k != "skip_missing_scan"}
            with scope("scan"):
                num, hl, hr = fused_child_scans(
                    h_small, h_par, left_small, sg2, sh2, cn2,
                    self.f_num_bin, self.f_missing, self.f_default_bin,
                    feature_mask & self._cat_mask,
                    interpret=self._scan_interpret, **kw)
            st = st._replace(
                hist_pool=st.hist_pool.at[lh_w].set(hl).at[rh_w].set(hr))
            f = self.num_features
            cands = _FeatCand(
                gain=num.gain, threshold=num.threshold,
                default_left=num.default_left,
                is_cat=jnp.zeros((2 * K, f), bool),
                cat_bits=jnp.zeros((2 * K, f, self.cat_W), jnp.uint32),
                left_sum_g=num.left_sum_g, left_sum_h=num.left_sum_h,
                left_cnt=num.left_cnt, right_sum_g=num.right_sum_g,
                right_sum_h=num.right_sum_h, right_cnt=num.right_cnt,
                left_output=num.left_output,
                right_output=num.right_output)
            cf2, ci2, cb2 = self._pack_cand_rows(cands, depth_ok)
        else:
            with scope("scan"):
                cf2, ci2, cb2 = self._cand_rows_batch(
                    hists2, sg2, sh2, cn2, feature_mask, depth_ok,
                    constraints)
        # per-child leaf rows
        lf_l = jnp.stack([pcf[:, CF_LSG], pcf[:, CF_LSH], pcf[:, CF_LCNT],
                          pcf[:, CF_LOUT], cd, lmin, lmax], 1)
        lf_r = jnp.stack([pcf[:, CF_RSG], pcf[:, CF_RSH], pcf[:, CF_RCNT],
                          pcf[:, CF_ROUT], cd, rmin, rmax], 1)
        lf2 = jnp.stack([lf_l, lf_r], 1).reshape(2 * K, NUM_LF).astype(acc)
        # scatter everything (invalid members write out of bounds -> dropped)
        oob = jnp.int32(self.M + 7)
        ls_w = jnp.where(valid, lslot, oob)
        rs_w = jnp.where(valid, rslot, oob)
        s2 = i2(ls_w, rs_w)
        st = st._replace(
            node_i=st.node_i.at[ls_w].set(li).at[rs_w].set(ri),
            phys_i=st.phys_i.at[ls_w].set(phys_l).at[rs_w].set(phys_r),
            node_f=st.node_f.at[s2].set(lf2),
            cand_f=st.cand_f.at[s2].set(cf2),
            cand_i=st.cand_i.at[s2].set(ci2),
            cand_b=st.cand_b.at[s2].set(cb2),
            parent=st.parent.at[s2].set(jnp.repeat(wi, 2)),
            child0=st.child0.at[jnp.where(valid, wi, oob)].set(lslot),
            hslot=st.hslot.at[ls_w].set(lh).at[rs_w].set(rh),
            split_m=st.split_m.at[jnp.where(valid, wi, oob)].set(True),
            cnt_i=st.cnt_i.at[jnp.where(valid, wi, oob)].set(
                jnp.stack([lc_bag, c_bag - lc_bag], 1).astype(jnp.int32)),
            num_nodes=st.num_nodes
            + 2 * jnp.sum(valid, dtype=jnp.int32).astype(jnp.int32),
            num_splits=st.num_splits
            + jnp.sum(valid, dtype=jnp.int32).astype(jnp.int32))
        return st

    def _wave_body(self, st: WaveState, feature_mask, width: int = 0,
                   opening: bool = False) -> WaveState:
        """One growth wave.  ``width`` overrides the member cap (0 = the
        configured W).  ``opening=True`` runs the wave in LEVEL-OPENING
        mode: no sort executes — every valid member's children get distinct
        LOGICAL windows and their rows get the matching sort keys, so a
        single later sort (the first growth wave's, or
        ``_materialize_sort`` without deferral) compacts all opening levels
        at once; member histograms run as full-array lid-masked passes
        (``_opening_hists``) since no window is physically contiguous
        yet."""
        W = width or self.W
        M, n = self.M, self._rows_len()
        fw = self.fw
        self._coll_ctx = ("grow_wave", "wave")
        with scope("select"):
            # ---- select the wave: top-W positive-gain frontier leaves
            g = self._pool_gains(st)
            gv, wi = lax.top_k(g, W)
            rem = self.grow_budget - st.num_splits
            valid = (gv > 0.0) & (jnp.arange(W) < rem)
            pos = jnp.cumsum(valid.astype(jnp.int32)) - valid.astype(jnp.int32)
            lslot = st.num_nodes + 2 * pos
            rslot = lslot + 1
            # ---- per-member split params (small gathers over node tables)
            feat = st.cand_i[wi, CI_FEAT]
            thr = st.cand_i[wi, CI_THR]
            flags = st.cand_i[wi, CI_FLAGS]
            dleft = (flags & 1).astype(jnp.float32)
            iscat = ((flags & 2) >> 1).astype(jnp.float32)
            ps = st.node_i[wi, 0]
            cw = st.node_i[wi, 1]
            col = self.fw_col[feat]
            widx = col // 4
            shift = (col % 4) * 8
            mt = self.f_missing[feat]
            db = self.f_default_bin[feat]
            nb = self.f_num_bin[feat]
            boff = self.fw_goff[feat]
            bnd = self.fw_bnd[feat]
            # members at or below the wave cutoff split in place (lid rewrite,
            # children share the parent span); only keyed members' rows get new
            # window keys.  Opening mode keys EVERY valid member (children get
            # logical windows now, physical compaction happens at the next
            # sort); normal mode keys the members it sorts
            if opening:
                sortable = valid
            else:
                sortable = valid & (cw > self._wave_cutoff)
            P = jnp.stack([widx.astype(jnp.float32), shift.astype(jnp.float32),
                           thr.astype(jnp.float32), dleft, iscat,
                           mt.astype(jnp.float32), db.astype(jnp.float32),
                           nb.astype(jnp.float32), boff.astype(jnp.float32),
                           bnd.astype(jnp.float32), lslot.astype(jnp.float32),
                           rslot.astype(jnp.float32),
                           sortable.astype(jnp.float32)],
                          axis=1)                                       # (W, C)
            cat16 = None
            if self.has_categorical:
                cb_w = st.cand_b[wi]                                # (W, Wc)
                cat16 = jnp.concatenate(
                    [(cb_w & jnp.uint32(0xFFFF)).astype(jnp.float32),
                     (cb_w >> jnp.uint32(16)).astype(jnp.float32)], axis=1)

        with scope("partition"):
            # -- pass 1 (per row chunk): wave-member mask -> split params via
            # MXU mask-matmul (gathers are ~5 ms/M rows on TPU, the one-hot
            # contraction ~0.5 ms), per-row decision, partial exact counts
            def decide(bins_c, lid_c, bag_c):
                ch_n = lid_c.shape[0]
                mask = (lid_c[:, None] == wi[None, :]) & valid[None, :]
                mask_f = mask.astype(jnp.float32)
                pm = lax.dot_general(mask_f, P, (((1,), (0,)), ((), ())),
                                     precision=_HIGH)               # (ch, C)
                in_wave = jnp.any(mask, axis=1)
                ri = lambda c: jnp.rint(pm[:, c]).astype(jnp.int32)
                widx_r, shift_r, thr_r = ri(0), ri(1), ri(2)
                dleft_r = pm[:, 3] > 0.5
                iscat_r = pm[:, 4] > 0.5
                mt_r, db_r, nb_r = ri(5), ri(6), ri(7)
                boff_r, bnd_r = ri(8), ri(9)
                lslot_r, rslot_r = ri(10), ri(11)
                sortable_r = pm[:, 12] > 0.5
                # per-row decision (NumericalDecisionInner `tree.h:233-249`)
                word = self._word_select(bins_c, widx_r)
                code = (word >> shift_r) & 0xFF
                if self._bundle is not None:
                    r = code - boff_r
                    in_r = (r >= 0) & (r < nb_r - 1)
                    dec = r + (r >= db_r).astype(r.dtype)
                    frow = jnp.where(bnd_r == 1, jnp.where(in_r, dec, db_r),
                                     code)
                else:
                    frow = code
                is_missing = ((mt_r == MISSING_ZERO) & (frow == db_r)) | \
                             ((mt_r == MISSING_NAN) & (frow == nb_r - 1))
                go_left = jnp.where(is_missing, dleft_r, frow <= thr_r)
                if self.has_categorical:
                    catpm = lax.dot_general(mask_f, cat16,
                                            (((1,), (0,)), ((), ())),
                                            precision=_HIGH)        # (ch, 2*Wc)
                    j = frow >> 5
                    lo = jnp.zeros(ch_n, jnp.float32)
                    hi = jnp.zeros(ch_n, jnp.float32)
                    for jj in range(self.cat_W):
                        sel = j == jj
                        lo = lo + jnp.where(sel, catpm[:, jj], 0.0)
                        hi = hi + jnp.where(sel, catpm[:, self.cat_W + jj], 0.0)
                    catw = (jnp.rint(hi).astype(jnp.int32).astype(jnp.uint32)
                            << jnp.uint32(16)) | \
                        jnp.rint(lo).astype(jnp.int32).astype(jnp.uint32)
                    cat_left = (catw >> (frow & 31).astype(jnp.uint32)) & 1
                    go_left = jnp.where(iscat_r, cat_left == 1, go_left)
                go_left = go_left & in_wave
                # exact integer counts via f32-exact one-hot contractions: the
                # chunk bound keeps per-chunk counts <= 2^20 (f32-exact); the
                # cross-chunk sum runs in int32, so exactness holds at ANY row
                # count (this was the old `n_pad < 2^24` eligibility gate)
                gl_f = go_left.astype(jnp.float32)
                bag_f = bag_c.astype(jnp.float32)
                w3 = jnp.stack([gl_f, gl_f * bag_f, bag_f], 0)
                cnt3 = lax.dot_general(w3, mask_f, (((1,), (0,)), ((), ())),
                                       precision=_HIGH)             # (3, W)
                lid_new = jnp.where(in_wave,
                                    jnp.where(go_left, lslot_r, rslot_r), lid_c)
                return (go_left, in_wave & sortable_r, lid_new,
                        jnp.rint(cnt3).astype(jnp.int32))

            Cm = 1
            while n // Cm > self._row_chunk and Cm < 1024 \
                    and n % (Cm * 2) == 0:
                Cm *= 2
            bag_b = st.w_p[2] > 0.5
            if Cm == 1:
                go_left, sort_r, lid_p, cnt3 = decide(st.bins_p, st.lid_p, bag_b)
            else:
                ch = n // Cm
                go_left, sort_r, lid_p, cnt3c = lax.map(
                    lambda a: decide(*a),
                    (st.bins_p.reshape(fw, Cm, ch).transpose(1, 0, 2),
                     st.lid_p.reshape(Cm, ch), bag_b.reshape(Cm, ch)))
                go_left = go_left.reshape(-1)
                sort_r = sort_r.reshape(-1)
                lid_p = lid_p.reshape(-1)
                cnt3 = jnp.sum(cnt3c, axis=0, dtype=jnp.int32)
            cnt3 = self._sync_counts3(cnt3)
            lc_w = cnt3[0]
            lc_bag = cnt3[1]
            c_bag = cnt3[2]

            # -- pass 2: window-order keys.  INVARIANT: every leaf's rows carry
            # key = 2 * (its window start) — strictly increasing with position,
            # so ``growth_sort`` (ties in position order) is the identity on
            # untouched leaves and partitions each split window in place.  The
            # children's starts are already known pre-sort (s and s+lc), so
            # both get final keys here.  Starts are routed through the
            # contraction as hi/lo 12-bit planes (one nonzero per row -> each
            # plane f32-exact at any N).
            starts2 = jnp.stack([ps, ps + lc_w], axis=1)            # (W, 2)
            planes = jnp.concatenate(
                [(starts2 >> 12).astype(jnp.float32),
                 (starts2 & 0xFFF).astype(jnp.float32)], axis=1)    # (W, 4)

            def keys(lid_old_c, go_c, sort_c, key_c):
                mask_f = ((lid_old_c[:, None] == wi[None, :])
                          & valid[None, :]).astype(jnp.float32)
                ks = lax.dot_general(mask_f, planes,
                                     (((1,), (0,)), ((), ())),
                                     precision=_HIGH)               # (ch, 4)
                ki = jnp.rint(ks).astype(jnp.int32)
                kl = 2 * ((ki[:, 0] << 12) + ki[:, 2])
                kr = 2 * ((ki[:, 1] << 12) + ki[:, 3])
                return jnp.where(sort_c, jnp.where(go_c, kl, kr), key_c)

            if Cm == 1:
                key_p = keys(st.lid_p, go_left, sort_r, st.key_p)
            else:
                ch = n // Cm
                key_p = lax.map(
                    lambda a: keys(*a),
                    (st.lid_p.reshape(Cm, ch), go_left.reshape(Cm, ch),
                     sort_r.reshape(Cm, ch),
                     st.key_p.reshape(Cm, ch))).reshape(-1)
            # ---- ONE ``growth_sort`` re-compacts every sortable split window.
            # Skipped when the whole wave froze (the tree's bottom waves), when
            # opening mode defers ALL compaction to the next sort,
            # and — under sort-deferral alternation — on every wave without a
            # PENDING key set: a deferring wave only assigns logical windows +
            # keys, and the NEXT wave's single sort materializes both levels.
            do_sort = jnp.any(sortable)
            if opening:
                st = st._replace(lid_p=lid_p, key_p=key_p)
                sorted_now = jnp.asarray(False)
            else:
                if self._defer_sorts:
                    sort_now = st.pending
                else:
                    sort_now = do_sort

                key_p, bins_p, w_p, rid_p, lid_p = lax.cond(
                    sort_now, lambda a: growth_sort(*a, self.M), lambda a: a,
                    (key_p, st.bins_p, st.w_p, st.rid_p, lid_p))
                st = st._replace(bins_p=bins_p, w_p=w_p, rid_p=rid_p,
                                 lid_p=lid_p, key_p=key_p)
                sorted_now = sort_now
            st = st._replace(pending=(st.pending | do_sort) & ~sorted_now)
        with scope("select"):
            # ---- child windows: sortable members split [s,lc)/[s+lc,..);
            # frozen members' children share the parent span
            li = jnp.stack([ps, jnp.where(sortable, lc_w, cw)], 1)
            ri2 = jnp.stack([jnp.where(sortable, ps + lc_w, ps),
                             jnp.where(sortable, cw - lc_w, cw)], 1)
            # children's materialized covering spans: the logical windows when
            # this wave sorted (everything compacts), the MEMBER's span when
            # the sort was deferred (rows haven't moved)
            mphys = st.phys_i[wi]                                   # (W, 2)
            phys_l = jnp.where(sorted_now, li, mphys)
            phys_r = jnp.where(sorted_now, ri2, mphys)
            # ---- smaller-child histograms (+ sibling subtraction) per member.
            # Post-sort, every member's window is materialized — scan the
            # logical child window (or the shared node span for frozen
            # members); on a deferring wave scan the member's covering span
            # with the lid mask doing the selection
            left_small = lc_bag <= (c_bag - lc_bag)
            sm_slot = jnp.where(left_small, lslot, rslot)
            sm_start = jnp.where(sorted_now,
                                 jnp.where(sortable & ~left_small, ps + lc_w,
                                           ps),
                                 mphys[:, 0])
            sm_cnt = jnp.where(sorted_now,
                               jnp.where(sortable,
                                         jnp.where(left_small, lc_w,
                                                   cw - lc_w), cw),
                               mphys[:, 1])
            ph = st.hslot[wi]
            rh = 1 + st.num_splits + pos
            oobh = jnp.int32(self.H + 7)
            lh_w = jnp.where(valid, ph, oobh)
            rh_w = jnp.where(valid, rh, oobh)

        if not opening and getattr(self, "_use_fused", False):
            # fused chain: only the smaller-child histograms run here —
            # subtraction, select, FixHistogram and both child scans
            # collapse into one Pallas launch in _children_bookkeeping
            with scope("hist"):
                h_small = self._member_small_hists(st, sm_slot, sm_start,
                                                   sm_cnt, valid)
            with scope("select"):
                st = self._children_bookkeeping(
                    st, wi, valid, lslot, rslot, lc_bag, c_bag, li, ri2,
                    ph, rh, None, feature_mask, phys_l, phys_r,
                    fused_parts=(h_small, ph, left_small, lh_w, rh_w))
        else:
            with scope("hist"):
                if opening:
                    # sm_start/sm_cnt reference LOGICAL windows (nothing
                    # has been compacted yet) — opening hists mask by lid
                    # over the full array
                    pool, hl, hr = self._opening_hists(
                        st, sm_slot, valid, ph, lh_w, rh_w, left_small)
                else:
                    pool, hl, hr = self._wave_member_hists(
                        st, sm_slot, sm_start, sm_cnt, valid, ph, lh_w,
                        rh_w, left_small)
                st = st._replace(hist_pool=pool)
                hists2 = jnp.stack([hl, hr], 1).reshape((2 * W,)
                                                       + hl.shape[1:])
            with scope("select"):
                st = self._children_bookkeeping(
                    st, wi, valid, lslot, rslot, lc_bag, c_bag, li, ri2,
                    ph, rh, hists2, feature_mask, phys_l, phys_r)
        if st.telem is not None:
            st = st._replace(telem=st.telem
                             .at[TEL_WAVES].add(1)
                             .at[TEL_WAVE_SORTS].add(
                                 sorted_now.astype(jnp.int32))
                             .at[TEL_WAVE_MEMBERS].add(
                                 jnp.sum(valid, dtype=jnp.int32))
                             .at[TEL_FROZEN_MEMBERS].add(
                                 jnp.sum(valid & ~sortable,
                                         dtype=jnp.int32)))
        # a sort materializes EVERY node (stale covering spans from the
        # previous deferring wave included), not just this wave's children
        return st._replace(phys_i=jnp.where(sorted_now, st.node_i,
                                            st.phys_i))

    def _member_small_hists(self, st: WaveState, sm_slot, sm_start, sm_cnt,
                            valid):
        """Smaller-child histograms ONLY (no subtraction / pool writes) —
        the fused wave step (``_use_fused``) folds everything downstream
        into the ``fused_child_scans`` kernel."""
        if self._use_pallas:
            return self._segment_hists(st, sm_slot, sm_start, sm_cnt,
                                       valid)

        def hist_member(carry, xs):
            slot, start, cnt, vk = xs

            def compute(_):
                hidx = self._bucket_idx(jnp.maximum(cnt, 1))
                return lax.switch(hidx, self._hist_branches, st.bins_p,
                                  st.w_p, st.lid_p, start, cnt, slot)

            return carry, lax.cond(
                vk, compute, lambda _: jnp.zeros_like(st.hist_pool[0]),
                0)

        _, h_small = lax.scan(hist_member, 0,
                              (sm_slot, sm_start, sm_cnt, valid))
        return h_small

    def _wave_member_hists(self, st: WaveState, sm_slot, sm_start, sm_cnt,
                           valid, ph, lh_w, rh_w, left_small):
        """Smaller-child histograms for all wave members + sibling
        subtraction + pool writes; returns (pool, hl, hr).  The sharded
        subclass overrides this to reduce-scatter the W local histograms
        over the feature axis before subtraction."""
        if self._use_pallas:
            return self._subtract_children(
                st, self._segment_hists(st, sm_slot, sm_start, sm_cnt,
                                        valid), ph, lh_w, rh_w, left_small)

        def hist_member(pool, xs):
            slot, start, cnt, phk, lhk, rhk, lsm, vk = xs

            def compute(pool):
                hidx = self._bucket_idx(jnp.maximum(cnt, 1))
                h_small = lax.switch(hidx, self._hist_branches,
                                     st.bins_p, st.w_p, st.lid_p, start,
                                     cnt, slot)
                h_par = pool[phk]
                h_large = h_par - h_small
                hl = jnp.where(lsm, h_small, h_large)
                hr = jnp.where(lsm, h_large, h_small)
                return pool.at[lhk].set(hl).at[rhk].set(hr), (hl, hr)

            def skip(pool):
                z = jnp.zeros_like(pool[0])
                return pool, (z, z)

            # only the valid prefix holds members — the cond keeps
            # invalid slots from paying a histogram pass
            return lax.cond(vk, compute, skip, pool)

        pool, (hl, hr) = lax.scan(
            hist_member, st.hist_pool,
            (sm_slot, sm_start, sm_cnt, ph, lh_w, rh_w, left_small,
             valid))
        return pool, hl, hr

    @staticmethod
    def _subtract_children(st: WaveState, h_small, ph, lh_w, rh_w,
                           left_small):
        """Sibling subtraction of the (K, F, B, 3) smaller-child histograms
        against their parents' pool slots, and the pool writes: (pool, hl,
        hr)."""
        h_large = st.hist_pool[ph] - h_small
        lsm = left_small[:, None, None, None]
        hl = jnp.where(lsm, h_small, h_large)
        hr = jnp.where(lsm, h_large, h_small)
        pool = st.hist_pool.at[lh_w].set(hl).at[rh_w].set(hr)
        return pool, hl, hr

    def _multislot_opening(self) -> bool:
        """Whether an opening level's histograms are one multi-slot kernel
        pass: the Pallas path and the serial member-histogram seam (the
        data-parallel learner answers for its own exchange seam)."""
        return self._use_pallas and type(self)._wave_member_hists is \
            WaveTPUTreeLearner._wave_member_hists

    def _multislot_hists(self, st: WaveState, sm_slot, valid):
        """The K smaller children's histograms of one opening level over
        this learner's own rows, in root order: ONE multi-slot pass
        (`ops/hist_pallas.py:build_histogram_multislot`), each row routed
        to its member's slot, or to none."""
        from .ops.hist_pallas import build_histogram_multislot
        K = sm_slot.shape[0]
        sl = jnp.where(valid, sm_slot, -1)
        slot_r = jnp.full(st.lid_p.shape, K, jnp.int32)
        for k in range(K):
            slot_r = jnp.where(st.lid_p == sl[k], k, slot_r)
        h_small = build_histogram_multislot(
            st.bins_p, st.w_p, slot_r, num_bins=self._hist_nbins,
            n_slots=K, row_block=self._seg_rb, nterms=self._hist_nterms,
            quant=self._quant)[:, :self._hist_cols]
        if self._quant:
            h_small = h_small * jnp.stack(
                [jnp.float32(1.0), jnp.float32(1.0), self._q_cnt])
        return h_small

    def _opening_hists(self, st: WaveState, sm_slot, valid, ph, lh_w, rh_w,
                       left_small):
        """Smaller-child histograms for one OPENING level: rows are still
        in root order (no sort has run), so the segment kernel's chunk walk
        cannot apply.  TPU: ONE multi-slot pass (``_multislot_hists``).
        Fallback (CPU / f64): per-member full-span lid-masked scans through
        the regular member-hist seam."""
        if self._multislot_opening():
            return self._subtract_children(
                st, self._multislot_hists(st, sm_slot, valid), ph, lh_w,
                rh_w, left_small)
        n = self._rows_len()
        return self._wave_member_hists(
            st, sm_slot, jnp.zeros_like(sm_slot),
            jnp.full_like(sm_slot, n), valid, ph, lh_w, rh_w, left_small)

    def _materialize_sort(self, st: WaveState) -> WaveState:
        """One full-array ``growth_sort`` on pending window keys (a
        deferring wave's before the K=1 replay; the opening levels' where
        deferral is off): every leaf's rows land contiguously at its logical
        window (keys are 2×(window start), strictly increasing with
        position — the invariant the per-wave sorts maintain), after which
        the regular wave flow's physical-window machinery applies."""
        with scope("partition"):
            key_p, bins_p, w_p, rid_p, lid_p = growth_sort(
                st.key_p, st.bins_p, st.w_p, st.rid_p, st.lid_p, self.M)
            if st.telem is not None:
                st = st._replace(telem=st.telem.at[TEL_WAVE_SORTS].add(1))
            return st._replace(
                key_p=key_p, bins_p=bins_p, w_p=w_p, rid_p=rid_p,
                lid_p=lid_p, phys_i=st.node_i, pending=jnp.asarray(False))

    def _segment_hists(self, st: WaveState, sm_slot, sm_start, sm_cnt,
                       valid, t_cap: Optional[int] = None):
        """Smaller-child histograms for every wave member in ONE Pallas
        call (`ops/hist_pallas.py:build_histogram_segments`): the chunk
        list walks each member's row-blocks; rows are masked by lid so
        block alignment never matters.  Invalid members get one all-masked
        chunk so their output slot is defined (zeros).

        ``t_cap`` overrides the chunk-capacity bound for callers whose
        members don't satisfy the wave invariants (the batched replay
        correction: members may share large un-materialized covering
        spans, so its cap is K * (rows/rb + 2) + 1).  A too-small cap
        would silently DROP row-blocks, so the default wave formula must
        cover the wave flows."""
        from .ops.hist_pallas import build_histogram_segments
        W = sm_slot.shape[0]        # wave width (narrow on ramp waves)
        rb = self._seg_rb
        # sortable smaller-child windows are disjoint (<= n_pad rows total);
        # frozen members scan their shared parent span (<= wave cutoff each)
        wc = min(self._wave_cutoff, self._rows_len())
        T = (t_cap if t_cap is not None
             else self._rows_len() // rb + W + W * (wc // rb + 2) + 1)
        first_blk = jnp.where(valid, sm_start // rb, 0)
        last_blk = jnp.where(
            valid, (sm_start + jnp.maximum(sm_cnt, 1) - 1) // rb, 0)
        nblk = jnp.where(valid, last_blk - first_blk + 1, 1)
        leaf_of = jnp.where(valid, sm_slot, -1)
        off = jnp.cumsum(nblk)
        starts = (off - nblk).astype(jnp.int32)
        total = off[W - 1]
        tpos = jnp.arange(T, dtype=jnp.int32)
        started = jnp.zeros(T, jnp.int32).at[starts].add(1, mode="drop")
        mem = jnp.clip(jnp.cumsum(started) - 1, 0, W - 1)
        slot_t = jnp.where(tpos < total, mem, W).astype(jnp.int32)
        block_t = jnp.where(tpos < total, first_blk[mem]
                            + (tpos - starts[mem]), 0).astype(jnp.int32)
        leaf_t = jnp.where(tpos < total, leaf_of[mem], -1).astype(jnp.int32)
        Ts = _segment_grid_buckets(T, W, self.open_levels > 0)

        def make_branch(Ti):
            def branch(s_t, b_t, l_t, bins_p, w_p, lid_p):
                return build_histogram_segments(
                    bins_p, w_p, lid_p, s_t[:Ti], b_t[:Ti], l_t[:Ti],
                    num_bins=self._hist_nbins, n_slots=W, row_block=rb,
                    nterms=self._hist_nterms, quant=self._quant)
            return branch

        tarr = jnp.asarray(Ts, dtype=jnp.int32)
        idx = jnp.maximum(jnp.sum(tarr >= total) - 1, 0)
        out = lax.switch(idx, [make_branch(t) for t in Ts], slot_t, block_t,
                         leaf_t, st.bins_p, st.w_p, st.lid_p)
        h = out[:, :self._hist_cols]
        if self._quant:
            # quant kernels duplicate the h lane into the count channel;
            # rescale it to the normalized Σhq/m̄ effective row count
            h = h * jnp.stack([jnp.float32(1.0), jnp.float32(1.0),
                               self._q_cnt])
        return h

    def _wave_step(self, st: WaveState, feature_mask) -> WaveState:
        """One adaptive-width wave.  The ramp (frontier 1→2→4→…) and the
        exhausted bottom pay per-wave costs that scale with the BODY width
        — the (rows, W) member-mask contractions, the 2W-child scans, the
        bookkeeping — regardless of how few leaves actually split, so a
        frontier of ≤ 8 positive-gain leaves runs a W=8 body instead.
        Selection is identical (top-k of the same gain order, same budget
        guard), so the grown forest is exactly the same."""
        ws = min(8, self.W)
        if ws >= self.W or (1 << self.open_levels) > ws:
            # (an opened ramp leaves the narrow body a tree's exhausted
            # bottom alone, and a second body is a second trace of every
            # kernel in it in the job's first iteration)
            return self._wave_body(st, feature_mask)
        small = jnp.sum(self._pool_gains(st) > 0.0) <= ws
        return lax.cond(
            small,
            lambda s: self._wave_body(s, feature_mask, width=ws),
            lambda s: self._wave_body(s, feature_mask), st)

    # -- split-word extraction seams -----------------------------------------
    # the decide pass and the stall partition both need the split feature's
    # packed bin word per row.  Serial and 1-D learners hold every word
    # locally; the 2-D data×feature learner holds only a word SLICE per
    # device and overrides these with a masked-sum + feature-axis psum.

    def _word_select(self, bins_c, widx_r):
        """Per-row split-feature bin words from a (fw, rows) bins chunk.
        ``widx_r`` carries packed-word indices in THIS learner's word
        numbering (global == local here)."""
        word = jnp.zeros(widx_r.shape[0], jnp.int32)
        for wdi in range(self.fw):
            word = word + jnp.where(widx_r == wdi, bins_c[wdi], 0)
        return word

    def _window_word(self, bw, col):
        """One feature's packed bin word over a sliced (fw, S) window;
        ``col`` is the packed column of the split feature."""
        S = bw.shape[1]
        return lax.dynamic_slice(bw, (col // 4, jnp.int32(0)), (1, S))[0]

    # -- the stall split (exact-replay correction) ---------------------------

    def _span_decide(self, bw, ww, lid, off, c, leaf, feat, thr, dleft,
                     is_cat, cat_bits):
        """Per-row split decision over one sliced window — the decode
        (bin-word extraction, EFB un-bundling, missing-value routing,
        categorical bitset) shared by the K=1 sort/frozen stall partition
        and the batched mask-mode one, so a routing fix cannot
        desynchronize them.  Returns (in_seg, go_left, lc_bag, c_bag)."""
        S = lid.shape[0]
        pos = jnp.arange(S, dtype=jnp.int32)
        in_seg = (pos >= off) & (pos < off + c) & (lid == leaf)
        col = self.fw_col[feat]
        word = self._window_word(bw, col)
        code = (word >> ((col % 4) * 8)) & 0xFF
        if self._bundle is not None:
            boffk = self.fw_goff[feat]
            d = self.f_default_bin[feat]
            r = code - boffk
            in_r = (r >= 0) & (r < self.f_num_bin[feat] - 1)
            dec = r + (r >= d).astype(r.dtype)
            frow = jnp.where(self.fw_bnd[feat] == 1,
                             jnp.where(in_r, dec, d), code)
        else:
            frow = code
        mtk = self.f_missing[feat]
        dbk = self.f_default_bin[feat]
        nbk = self.f_num_bin[feat]
        is_missing = ((mtk == MISSING_ZERO) & (frow == dbk)) | \
                     ((mtk == MISSING_NAN) & (frow == nbk - 1))
        go_left = jnp.where(is_missing, dleft, frow <= thr)
        if self.has_categorical:
            cat_left = (cat_bits[frow >> 5]
                        >> (frow & 31).astype(jnp.uint32)) & 1
            go_left = jnp.where(is_cat, cat_left == 1, go_left)
        bag = ww[2] > 0.5
        lc_bag = jnp.sum(in_seg & go_left & bag, dtype=jnp.int32)
        c_bag = jnp.sum(in_seg & bag, dtype=jnp.int32)
        return in_seg, go_left, lc_bag, c_bag

    def _make_stall_branch(self, S: int, sort_mode: bool):
        """Partition of one window outside the wave flow, mirroring the
        sequential compact learner exactly (`learner_compact.py`
        ``_make_partition_branch``) except that BOTH children get fresh
        node slots (the sequential learner reuses the parent's).

        sort_mode: stable window sort physically compacts the children
        (windows above ``tpu_sort_cutoff``).  Otherwise the window is
        frozen and only lid lanes change; the sort_mode invariant matches
        the sequential learner's — frozen (shared) windows are always
        ≤ cutoff, so a sort-mode stall never reorders another leaf's rows.
        """
        fw, n = self.fw, self._rows_len()

        def branch(bins_p, w_p, rid_p, lid_p, s, c, leaf, feat, thr, dleft,
                   is_cat, cat_bits, l0, r0):
            sa = jnp.clip(s, 0, n - S).astype(jnp.int32)
            off = (s - sa).astype(jnp.int32)
            bw = lax.dynamic_slice(bins_p, (jnp.int32(0), sa), (fw, S))
            ww = lax.dynamic_slice(w_p, (jnp.int32(0), sa), (3, S))
            lid = lax.dynamic_slice(lid_p, (sa,), (S,))
            pos = jnp.arange(S, dtype=jnp.int32)
            in_seg, go_left, lc_bag, c_bag = self._span_decide(
                bw, ww, lid, off, c, leaf, feat, thr, dleft, is_cat,
                cat_bits)
            segl = in_seg & go_left
            if sort_mode:
                rid = lax.dynamic_slice(rid_p, (sa,), (S,))
                key = jnp.where(in_seg,
                                jnp.where(go_left, 1, 2),
                                jnp.where(pos < off, 0, 3)).astype(jnp.int32)
                lid2 = jnp.where(in_seg, jnp.where(go_left, l0, r0), lid)
                ops = ([key] + [bw[i] for i in range(fw)]
                       + [ww[0], ww[1], ww[2], rid, lid2])
                sd = lax.sort(ops, num_keys=1, is_stable=True)
                bw2 = jnp.stack(sd[1:1 + fw])
                ww2 = jnp.stack(sd[1 + fw:4 + fw])
                rid2, lid3 = sd[4 + fw], sd[5 + fw]
                lc_w = jnp.sum(segl.astype(jnp.int32)).astype(jnp.int32)
                bins_p = lax.dynamic_update_slice(bins_p, bw2,
                                                  (jnp.int32(0), sa))
                w_p = lax.dynamic_update_slice(w_p, ww2, (jnp.int32(0), sa))
                rid_p = lax.dynamic_update_slice(rid_p, rid2, (sa,))
                lid_p = lax.dynamic_update_slice(lid_p, lid3, (sa,))
                ls, lw = s, lc_w
                rs, rw = s + lc_w, c - lc_w
            else:
                lid2 = jnp.where(in_seg, jnp.where(go_left, l0, r0), lid)
                lid_p = lax.dynamic_update_slice(lid_p, lid2, (sa,))
                ls = rs = s
                lw = rw = c
            return (bins_p, w_p, rid_p, lid_p, ls, lw, rs, rw,
                    lc_bag.astype(jnp.int32), c_bag.astype(jnp.int32))

        return branch

    def _replicated_spans(self, spans):
        """Replicated view of covering-span widths.  ``phys_i`` holds
        LOCAL window geometry in the row-sharded learners, so any gate
        derived from it must see the cross-device maximum or the replay's
        replicated bookkeeping diverges (round-5 advisor, high); identity
        here — the sharded wave learner overrides with ``lax.pmax``."""
        return spans

    def _stall_split(self, st: WaveState, top, feature_mask) -> WaveState:
        """Split one frontier leaf outside the wave flow (the
        ``tpu_wave_stall_batch=1`` replay path)."""
        self._coll_ctx = ("stall_correction", "stall_event")
        crow_i = st.cand_i[top]
        feat = crow_i[CI_FEAT]
        thr = crow_i[CI_THR]
        dleft = (crow_i[CI_FLAGS] & 1) == 1
        is_cat = (crow_i[CI_FLAGS] & 2) == 2
        cat_bits = st.cand_b[top]
        s = st.node_i[top, 0]
        c = st.node_i[top, 1]
        l0 = st.num_nodes
        r0 = l0 + 1
        pidx = self._bucket_idx(c)
        bins_p, w_p, rid_p, lid_p, ls, lw, rs, rw, lc_bag, c_bag = \
            lax.switch(pidx, self._stall_branches, st.bins_p, st.w_p,
                       st.rid_p, st.lid_p, s, c, top, feat, thr, dleft,
                       is_cat, cat_bits, l0, r0)
        st = st._replace(bins_p=bins_p, w_p=w_p, rid_p=rid_p, lid_p=lid_p)
        lc_bag, c_bag = self._sync_counts(lc_bag, c_bag)
        # smaller-child histogram + sibling subtraction
        left_small = lc_bag <= (c_bag - lc_bag)
        sm_slot = jnp.where(left_small, l0, r0)
        sm_start = jnp.where(left_small, ls, rs)
        sm_cnt = jnp.where(left_small, lw, rw)
        hidx = self._bucket_idx(jnp.maximum(sm_cnt, 1))
        h_small = self._reduce_hist(
            lax.switch(hidx, self._hist_branches, st.bins_p, st.w_p,
                       st.lid_p, sm_start, sm_cnt, sm_slot))
        ph = st.hslot[top]
        h_par = st.hist_pool[ph]
        h_large = h_par - h_small
        hl = jnp.where(left_small, h_small, h_large)
        hr = jnp.where(left_small, h_large, h_small)
        rh = 1 + st.num_splits
        st = st._replace(hist_pool=st.hist_pool.at[ph].set(hl)
                         .at[rh].set(hr))
        one = jnp.ones(1, bool)
        li = jnp.stack([ls, lw])[None, :]
        ri = jnp.stack([rs, rw])[None, :]
        return self._children_bookkeeping(
            st, top[None], one, l0[None], r0[None],
            lc_bag[None], c_bag[None], li, ri, ph[None], rh[None],
            jnp.stack([hl, hr]), feature_mask)

    def _make_stall_mask_branch(self, S: int):
        """Lid-only partition of one covering span for the batched replay
        correction.  No row moves: children share the parent's span the
        way frozen (sub-cutoff) wave windows already do, so the
        surrounding fori_loop carries ONLY the lid lane.  (A first cut
        carried bins/weights/rid/pool through the loop; XLA could not
        alias the carries past their other consumers and inserted ~7 ms
        full-array copies per stall event — the copies, not the splits,
        dominated the replay.)"""
        fw, n = self.fw, self._rows_len()

        def branch(bins_p, w_p, lid_p, s, c, leaf, feat, thr, dleft,
                   is_cat, cat_bits, l0, r0):
            sa = jnp.clip(s, 0, n - S).astype(jnp.int32)
            off = (s - sa).astype(jnp.int32)
            bw = lax.dynamic_slice(bins_p, (jnp.int32(0), sa), (fw, S))
            ww = lax.dynamic_slice(w_p, (jnp.int32(0), sa), (3, S))
            lid = lax.dynamic_slice(lid_p, (sa,), (S,))
            in_seg, go_left, lc_bag, c_bag = self._span_decide(
                bw, ww, lid, off, c, leaf, feat, thr, dleft, is_cat,
                cat_bits)
            lid2 = jnp.where(in_seg, jnp.where(go_left, l0, r0), lid)
            lid_p = lax.dynamic_update_slice(lid_p, lid2, (sa,))
            return lid_p, lc_bag, c_bag

        return branch

    # batch extras must fit a bounded slice so the vectorized partition's
    # stacked (K-1, fw, S) transients stay small; bigger-span leaves can
    # only be corrected as the top of their own event (rare: big spans
    # stall early, at the top of the tree)
    _VEC_CAP = 1 << 17

    def _make_stall_vec_branch(self, S: int, Ke: int):
        """Lid-only partition of Ke covering spans at once: Ke slices of
        one bucket, ONE vmapped ``_span_decide``, Ke masked write-backs.
        Replaces Ke sequential bucket switches (~0.2 ms each on v5e) with
        one fused stage; disjoint lid values make the sequential
        dynamic-update chain commute even when members share a span."""
        fw, n = self.fw, self._rows_len()

        def branch(bins_p, w_p, lid_p, starts, cnts, leaves, feats, thrs,
                   dlefts, iscats, catbits, l0v, r0v):
            sas = jnp.clip(starts, 0, n - S).astype(jnp.int32)
            offs = (starts - sas).astype(jnp.int32)
            z = jnp.int32(0)
            bw_k = jnp.stack([lax.dynamic_slice(bins_p, (z, sas[i]),
                                                (fw, S))
                              for i in range(Ke)])
            ww_k = jnp.stack([lax.dynamic_slice(w_p, (z, sas[i]), (3, S))
                              for i in range(Ke)])
            lid_k = jnp.stack([lax.dynamic_slice(lid_p, (sas[i],), (S,))
                               for i in range(Ke)])
            in_seg, go_left, lc, cb = jax.vmap(self._span_decide)(
                bw_k, ww_k, lid_k, offs, cnts, leaves, feats, thrs,
                dlefts, iscats, catbits)
            for i in range(Ke):
                cur = lax.dynamic_slice(lid_p, (sas[i],), (S,))
                new = jnp.where(in_seg[i],
                                jnp.where(go_left[i], l0v[i], r0v[i]), cur)
                lid_p = lax.dynamic_update_slice(lid_p, new, (sas[i],))
            return lid_p, lc, cb

        return branch

    def _stall_split_batch(self, st: WaveState, tops, bvalid,
                           feature_mask, top_fits=None) -> WaveState:
        """Split up to K frontier leaves in ONE replay correction pass.

        Availability advances only by pops (a split never reveals its
        node to the sim), so members beyond the sim's exact-priority top
        are speculation exactly like the growth overshoot: the replay
        still pops exactly ``budget`` splits in the reference's best-first
        order (`serial_tree_learner.cpp:185-218`), and an unused member
        costs one wasted lid-mask partition while a used one saves a whole
        stall (priority sort + sim re-entry + single correction).  The
        members are distinct frontier leaves with disjoint rows, so the
        sequential lid rewrites commute; bookkeeping and the child split
        scans run ONCE, batched over all members."""
        K = tops.shape[0]
        OOBH = jnp.int32(self.H + 7)
        self._coll_ctx = ("stall_correction", "stall_event")
        bv_i = bvalid.astype(jnp.int32)
        pos = jnp.cumsum(bv_i) - bv_i
        l0s = (st.num_nodes + 2 * pos).astype(jnp.int32)
        r0s = l0s + 1
        phs = st.hslot[tops]
        rhs = (1 + st.num_splits + pos).astype(jnp.int32)
        h_t = st.hist_pool[0]
        bins_p, w_p = st.bins_p, st.w_p   # read-only: no rows move
        # MATERIALIZED covering spans: for a child deferred by sort
        # alternation, node_i holds its logical (post-sort) window but the
        # rows physically sit in the parent's span — phys_i tracks that,
        # which also lets the growth loop skip the pre-replay
        # materialization sort entirely
        spans = st.phys_i[tops]           # (K, 2)
        # Partition stage — UNROLLED over the (static, small) K:
        # straight-line code whose only sequential state is the lid-lane
        # dynamic-update chain, which XLA aliases in place (a fori_loop
        # here paid ~0.35 ms of while-loop overhead per correction event
        # on v5e, and a first cut with per-member histograms inside the
        # loop paid ~0.4 ms per member in switch dispatches)
        lid_p = st.lid_p
        cs = jnp.where(bvalid, spans[:, 1], 0)

        def part_two_stage(lid_in):
            # the TOP (member 0) partitions through its own bucket switch
            # — its span is ungated; an invalid/zero-count member
            # degrades to a zero-row no-op in the smallest bucket, writes
            # masked or dropped
            crow0 = st.cand_i[tops[0]]
            lid2, lc0, c0 = lax.switch(
                self._bucket_idx(jnp.maximum(cs[0], 1)),
                self._stall_mask_branches, bins_p, w_p, lid_in,
                spans[0, 0], cs[0], tops[0], crow0[CI_FEAT],
                crow0[CI_THR], (crow0[CI_FLAGS] & 1) == 1,
                (crow0[CI_FLAGS] & 2) == 2, st.cand_b[tops[0]],
                l0s[0], r0s[0])
            if K == 1:
                return lid2, lc0[None], c0[None]
            # the EXTRAS (span-gated <= _VEC_CAP in do_stall) partition
            # in ONE vectorized stage
            ci_e = st.cand_i[tops[1:]]
            vsz = self._vec_sizes_arr
            vidx = jnp.sum(jnp.maximum(jnp.max(cs[1:]), 1)
                           > vsz).astype(jnp.int32)
            vidx = jnp.minimum(vidx, len(self._stall_vec_branches) - 1)
            lid2, lc_e, c_e = lax.switch(
                vidx, self._stall_vec_branches, bins_p, w_p, lid2,
                spans[1:, 0], cs[1:], tops[1:], ci_e[:, CI_FEAT],
                ci_e[:, CI_THR], (ci_e[:, CI_FLAGS] & 1) == 1,
                (ci_e[:, CI_FLAGS] & 2) == 2, st.cand_b[tops[1:]],
                l0s[1:], r0s[1:])
            return (lid2, jnp.concatenate([lc0[None], lc_e]),
                    jnp.concatenate([c0[None], c_e]))

        if K > 1 and self._stall_fuse_top and top_fits is not None:
            # when the top's span ALSO fits the vec cap (the common case
            # — big spans stall early, at the top of the tree), the
            # whole event is ONE masked pass: one switch dispatch instead
            # of two.  Exact: both stages share _span_decide and the lid
            # rewrites are disjoint.  top_fits is REPLICATED (do_stall
            # derives it from the pmax'd spans), so the cond cannot
            # diverge across shards
            def part_fused(lid_in):
                ci_a = st.cand_i[tops]
                vsz = self._vec_sizes_arr
                vidx = jnp.sum(jnp.maximum(jnp.max(cs), 1)
                               > vsz).astype(jnp.int32)
                vidx = jnp.minimum(vidx,
                                   len(self._stall_vec_branches_all) - 1)
                return lax.switch(
                    vidx, self._stall_vec_branches_all, bins_p, w_p,
                    lid_in, spans[:, 0], cs, tops, ci_a[:, CI_FEAT],
                    ci_a[:, CI_THR], (ci_a[:, CI_FLAGS] & 1) == 1,
                    (ci_a[:, CI_FLAGS] & 2) == 2, st.cand_b[tops],
                    l0s, r0s)

            lid_p, lc_s, c_s = lax.cond(top_fits, part_fused,
                                        part_two_stage, lid_p)
        else:
            lid_p, lc_s, c_s = part_two_stage(lid_p)
        # ONE count sync (the sharded learners psum the (K,) pair once
        # instead of per member)
        lc_a, c_a = self._sync_counts(lc_s, c_s)
        left_small = lc_a <= (c_a - lc_a)
        sm_slot = jnp.where(left_small, l0s, r0s)
        # Histogram stage — ONE segment-kernel pass over every member's
        # smaller child (same machinery as the wave member hists), then
        # batched sibling subtraction from the parents' pooled histograms
        st2 = st._replace(lid_p=lid_p)
        if self._use_pallas:
            t_cap = K * (self._rows_len() // self._seg_rb + 2) + 1
            h_small = self._reduce_hist_batch(self._segment_hists(
                st2, sm_slot, spans[:, 0], cs, bvalid, t_cap=t_cap))
        else:
            # stack the K member histograms and reduce ONCE — the sharded
            # seam exchanges one (K, F, B, 3) collective per correction
            # event, matching _wave_member_hists' single psum_scatter per
            # wave (a per-member loop issued K collectives per event)
            h_small = self._reduce_hist_batch(jnp.stack([
                lax.switch(
                    self._bucket_idx(jnp.maximum(cs[i], 1)),
                    self._hist_branches, bins_p, w_p, lid_p, spans[i, 0],
                    cs[i], sm_slot[i])
                for i in range(K)]))
        h_par = st.hist_pool[phs]                     # (K, F, B, 3)
        h_large = h_par - h_small
        lsm = left_small[:, None, None, None]
        hl = jnp.where(lsm, h_small, h_large)
        hr = jnp.where(lsm, h_large, h_small)
        hists2 = jnp.stack([hl, hr], 1).reshape((2 * K,) + h_t.shape)
        # ONE masked pool write outside the loop (the pool never rides
        # the loop carry)
        i2 = jnp.stack([jnp.where(bvalid, phs, OOBH),
                        jnp.where(bvalid, rhs, OOBH)], 1).reshape(-1)
        st = st._replace(
            lid_p=lid_p,
            hist_pool=st.hist_pool.at[i2].set(hists2, mode="drop"))
        return self._children_bookkeeping(
            st, tops, bvalid, l0s, r0s, lc_a, c_a, spans, spans, phs, rhs,
            hists2, feature_mask)

    # -- exact greedy replay --------------------------------------------------

    def _replay(self, st: WaveState, feature_mask):
        """Re-derive the exact best-first pop order over the grown forest
        (`serial_tree_learner.cpp:185-218`), splitting on demand when the
        replay reaches a leaf the growth never split.

        Two-level loop; the INNER sim pops a whole BATCH per iteration
        instead of one leaf.  Every grown node's children's gains are
        already known (``cand_f``), so after sorting the available set by
        (gain desc, leaf-index asc) — the reference's pop priority — the
        leading prefix can pop at once as long as each member's gain
        strictly exceeds every child gain revealed by the members before it
        (such a child could never jump ahead of them); gain TIES against a
        revealed child stop the prefix, deferring to the next iteration
        where the child is available with its leaf index assigned, so the
        lowest-leaf-index tie-break (`serial_tree_learner.cpp:505-520`) is
        preserved exactly.  Real trees pop in a few descending-gain runs,
        so ~254 sequential pops (~28 ms of tiny-op latency on the real
        chip) become ~a dozen batched iterations.

        The OUTER loop — one iteration per speculation miss, usually zero
        total — re-enters after performing a missing split."""
        if self._stall_batch > 1:
            self._stall_mask_branches = [self._make_stall_mask_branch(S)
                                         for S in self._win_sizes]
            vec_sizes = [S for S in self._win_sizes if S <= self._vec_cap]
            if not vec_sizes:
                vec_sizes = [self._win_sizes[0]]
            self._vec_sizes_arr = jnp.asarray(vec_sizes, dtype=jnp.int32)
            self._stall_vec_branches = [
                self._make_stall_vec_branch(S, self._stall_batch - 1)
                for S in vec_sizes]
            if self._stall_fuse_top:
                # K-wide variant for events whose TOP also fits the vec
                # cap: the whole correction partitions in ONE masked pass
                self._stall_vec_branches_all = [
                    self._make_stall_vec_branch(S, self._stall_batch)
                    for S in vec_sizes]
        M, budget = self.M, self.budget
        OOB = jnp.int32(M + 7)
        NEG = jnp.finfo(jnp.float32).min

        def outer_cond(carry):
            return carry[-1] == 0  # 0 = need (another) sim pass

        def outer_body(carry):
            (st, avail_n, refidx, pops, leaf_cnt, poprec, stalls, extras,
             _) = carry
            gains = st.cand_f[:, CF_GAIN].astype(self._acc)
            split_m = st.split_m
            child0 = st.child0
            iota = jnp.arange(M, dtype=jnp.int32)
            # ONE gain-priority sort per pass (gains are fixed within a
            # pass; only availability changes between iterations) — the
            # slot-ascending secondary key is only a stand-in for the
            # refidx tie-break, so batches containing an exact gain tie
            # fall back to a single exact-priority pop
            _, _, order = lax.sort([-gains, iota, iota], num_keys=2,
                                   is_stable=True)
            g_o = gains[order]
            sp_o = split_m[order]
            c0_o = child0[order]
            cg_o = jnp.where(sp_o,
                             jnp.maximum(gains[c0_o], gains[c0_o + 1]),
                             NEG)

            # ---- inner sim: flag 0 = running, 1 = stall, 2 = done
            def icond(ic):
                return ic[-2] == 0

            def ibody(ic):
                avail_n, refidx, pops, leaf_cnt, poprec, _, _ = ic
                cand = avail_n[order]
                gc = jnp.where(cand, g_o, NEG)
                # exclusive running max of revealed-child gains over the
                # available candidates
                pmax = lax.cummax(jnp.concatenate(
                    [jnp.full((1,), NEG, cg_o.dtype),
                     jnp.where(cand, cg_o, NEG)[:-1]]))
                apos = jnp.cumsum(cand.astype(jnp.int32)) - 1
                ok = cand & (g_o > 0.0) & sp_o & (g_o > pmax) & \
                    (apos < budget - pops)
                alive = jnp.cumprod((ok | ~cand).astype(jnp.int32)) == 1
                inb = ok & alive
                # ANY exact gain tie among available candidates -> single
                # exact pop (covers batch-internal ties AND a tie between a
                # prefix member and a blocked/unsplit candidate with lower
                # refidx; a plateau of duplicated-feature gains degrades to
                # sequential pops, which is the exact semantics)
                pa = lax.cummax(jnp.concatenate(
                    [jnp.full((1,), -1, jnp.int32),
                     jnp.where(cand, iota, -1)[:-1]]))
                tie = jnp.any(cand & (pa >= 0) & (g_o > 0.0) &
                              (g_o == g_o[jnp.maximum(pa, 0)]))
                g0 = jnp.max(gc)
                # exact-priority top: lowest refidx among max-gain avail
                tb = jnp.where(cand & (g_o == g0), refidx[order],
                               jnp.int32(1 << 30))
                pstar = jnp.argmin(tb).astype(jnp.int32)
                proceed0 = (g0 > 0.0) & (pops < budget)
                # single-pop mode: a gain tie inside the prefix, or an
                # empty prefix while the exact top is poppable (a same-gain
                # unsplit node ahead of it blocked the prefix)
                npop0 = jnp.sum(inb.astype(jnp.int32))
                single = tie | ((npop0 == 0) & proceed0 & sp_o[pstar])
                inb = jnp.where(single, (iota == pstar) & sp_o[pstar], inb)
                npop = jnp.sum(inb.astype(jnp.int32)).astype(jnp.int32)
                flag = jnp.where(
                    npop > 0, jnp.int32(0),
                    jnp.where(proceed0 & ~sp_o[pstar], jnp.int32(1),
                              jnp.int32(2)))
                top = order[pstar]
                tie = single
                # ---- execute the batch (apos == pop position: the prefix
                # property makes every earlier available node popped; in
                # tie mode the single pop is position 0 by construction)
                bpos = jnp.where(tie, 0, apos)
                nd = jnp.where(inb, order, OOB)
                c0b = jnp.where(inb, c0_o, OOB)
                ref_nd = refidx[jnp.where(inb, order, 0)]
                poprec = poprec.at[jnp.where(inb, pops + bpos,
                                             jnp.int32(budget + 7))].set(
                    jnp.stack([nd, ref_nd], axis=1), mode="drop")
                refidx = refidx.at[c0b].set(ref_nd, mode="drop") \
                               .at[c0b + 1].set(leaf_cnt + bpos,
                                                mode="drop")
                avail_n = avail_n.at[nd].set(False, mode="drop") \
                                 .at[c0b].set(True, mode="drop") \
                                 .at[c0b + 1].set(True, mode="drop")
                return (avail_n, refidx, pops + npop, leaf_cnt + npop,
                        poprec, flag, top)

            ic = lax.while_loop(
                icond, ibody,
                (avail_n, refidx, pops, leaf_cnt, poprec,
                 jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32)))
            avail_n, refidx, pops, leaf_cnt, poprec, flag, top = ic

            Kb = self._stall_batch
            if Kb == 1:
                def do_stall1(s):
                    sort_c = (s.node_i[top, 1]
                              > jnp.int32(self._stall_cutoff))
                    with scope("stall"):
                        s2 = self._stall_split(s, top, feature_mask)
                    if s2.telem is not None:
                        s2 = s2._replace(
                            telem=s2.telem.at[TEL_STALL_SORT_MODE].add(
                                sort_c.astype(jnp.int32)))
                    return s2, jnp.int32(1)

                st, nsp = lax.cond(flag == 1, do_stall1,
                                   lambda s: (s, jnp.int32(0)), st)
                return (st, avail_n, refidx, pops, leaf_cnt, poprec,
                        stalls + nsp, extras,
                        jnp.where(flag == 1, jnp.int32(0), flag))

            def do_stall(s):
                # split the top-Kb REPLAY-PRIORITY (gain desc, refidx asc)
                # available unsplit leaves at once.  The first is provably
                # the sim's stalled top — flag==1 means the min-refidx
                # max-gain available node is unsplit, and restricting the
                # min to the unsplit subset it belongs to can't change it —
                # so it stays available with its unchanged gain and the
                # next pass pops it; later members are the likeliest
                # upcoming stalls
                cand_u = avail_n & ~s.split_m & (gains > 0.0)
                gk = jnp.where(cand_u, -gains, jnp.inf)
                rk = jnp.where(cand_u, refidx, jnp.int32(1 << 30))
                _, _, osel = lax.sort([gk, rk, iota], num_keys=2,
                                      is_stable=True)
                tops_k = osel[:Kb]
                bv = cand_u[tops_k]
                # EXTRAS (members beyond the top) count against the
                # dedicated _stall_extras_cap reserve and must fit the
                # vectorized partition's slice cap; the top itself is
                # always safe — each top maps to a distinct pop, which the
                # budget-sized share of the reserve covers
                head = (extras + jnp.arange(-1, Kb - 1, dtype=jnp.int32)) \
                    < jnp.int32(self._extras_cap)
                # the gate must be REPLICATED: phys_i spans are local
                # window geometry in the row-sharded learners, and a leaf
                # whose local span straddles the cap on only some shards
                # would otherwise diverge bv (and with it num_nodes /
                # split_m / the extras counter) across devices
                fits = self._replicated_spans(s.phys_i[tops_k, 1]) \
                    <= jnp.int32(self._vec_cap)
                bv = bv & ((head & fits) | (jnp.arange(Kb) == 0))
                with scope("stall"):
                    s2 = self._stall_split_batch(s, tops_k, bv,
                                                 feature_mask,
                                                 top_fits=fits[0])
                nsp = jnp.sum(bv, dtype=jnp.int32).astype(jnp.int32)
                return s2, nsp, nsp - bv[0].astype(jnp.int32)

            st, nsp, nex = lax.cond(
                flag == 1, do_stall,
                lambda s: (s, jnp.int32(0), jnp.int32(0)), st)
            # stall -> another sim pass (flag back to 0); done stays 2
            return (st, avail_n, refidx, pops, leaf_cnt, poprec,
                    stalls + nsp, extras + nex,
                    jnp.where(flag == 1, jnp.int32(0), flag))

        avail0 = jnp.zeros(M, bool).at[0].set(True)
        init = (st, avail0,
                jnp.full(M, -1, jnp.int32).at[0].set(0),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(1, jnp.int32),
                jnp.zeros((budget, 2), jnp.int32),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32))
        (st, avail_n, refidx, pops, leaf_cnt, poprec, stalls, _extras,
         _) = lax.while_loop(outer_cond, outer_body, init)
        if st.telem is not None:
            st = st._replace(telem=st.telem
                             .at[TEL_STALL_SPLITS].set(stalls)
                             .at[TEL_STALL_EXTRAS].set(_extras)
                             .at[TEL_POPS].set(pops))
        pop_nodes, pop_ref = poprec[:, 0], poprec[:, 1]
        # final frontier = revealed (root or child of a popped node) and
        # never popped — reconstructed from the pop list
        vp = jnp.arange(budget) < pops
        ndw = jnp.where(vp, pop_nodes, OOB)
        c0p = jnp.where(vp, st.child0[jnp.where(vp, pop_nodes, 0)], OOB)
        revealed = jnp.zeros(M, bool).at[0].set(True) \
            .at[c0p].set(True, mode="drop") \
            .at[c0p + 1].set(True, mode="drop")
        popped = jnp.zeros(M, bool).at[ndw].set(True, mode="drop")
        avail = revealed & ~popped
        return st, avail, refidx, pops, pop_nodes, pop_ref, stalls

    # -- whole tree -----------------------------------------------------------

    def _train_tree_wave(self, bins_p, grad, hess, bag, feature_mask):
        self._ledger.begin_trace()
        self._use_fused = self._fused_ok()
        self._hist_branches = [self._make_hist_branch(S)
                               for S in self._win_sizes]
        self._stall_branches = [
            self._make_stall_branch(S, sort_mode=S > self._stall_cutoff)
            for S in self._win_sizes]
        with scope("root"):
            st = self._init_root_wave(bins_p, grad, hess, bag, feature_mask)
        return self._emit_tree_wave(self._grow_tree(st, feature_mask),
                                    feature_mask)

    def _grow_tree(self, st: WaveState, feature_mask) -> WaveState:
        """A rooted tree grown to its budget: the opening, then the growth
        waves (the serial and the data-parallel learners' one loop)."""
        # level-wise opening: the first L levels grow unsorted (level d has
        # at most 2^d leaves to split) and leave their keys PENDING, as a
        # deferring wave does: the first growth wave's sort (``sort_now =
        # st.pending``) compacts every level's windows with its own.  Without
        # deferral nothing downstream sorts a pending key set, so the
        # opening materializes itself.  A level with nothing to split is an
        # exact no-op
        with scope("opening"):
            # (a body a level: sharing one body cost more on the device
            # than its traces cost the host, through a ``fori_loop``'s
            # carries, 27 ms a tree, or an inner jit's call boundaries, 42:
            # my chip runs, PR 31, calls 38 and 40)
            for d in range(self.open_levels):
                st = self._wave_body(st, feature_mask,
                                     width=min(1 << d, self.W),
                                     opening=True)
            if self.open_levels > 0 and not self._defer_sorts:
                st = lax.cond(st.pending, self._materialize_sort,
                              lambda s: s, st)

        def gcond(s):
            return (s.num_splits < self.grow_budget) & \
                (jnp.max(self._pool_gains(s)) > 0.0)

        with scope("grow"):
            st = lax.while_loop(
                gcond, lambda s: self._wave_step(s, feature_mask), st)
            if self._defer_sorts and self._stall_batch == 1:
                # the growth loop may exit on a deferring wave — the K=1
                # replay's stall splits slice PHYSICAL windows, so
                # materialize first.  Batched (K>1) corrections mask
                # through phys_i covering spans instead, so they skip
                # this sort
                st = lax.cond(st.pending, self._materialize_sort,
                              lambda s: s, st)
        return st

    def _emit_tree_wave(self, st: WaveState, feature_mask):
        """Exact greedy replay + host-record emission + speculative-leaf
        mapping (shared by the serial and sharded wave learners — the
        replay operates on replicated node state only)."""
        if st.telem is not None:
            st = st._replace(
                telem=st.telem.at[TEL_GROW_SPLITS].set(st.num_splits))
        with scope("replay"):
            st, avail, refidx, pops, pop_nodes, pop_ref, _stalls = \
                self._replay(st, feature_mask)
        if st.telem is not None:
            st = st._replace(
                telem=st.telem.at[TEL_TOTAL_SPLITS].set(st.num_splits))

        with scope("emit"):
            # ---- emit host records in pop order
            budget = self.budget
            vp = jnp.arange(budget) < pops
            nd = jnp.where(vp, pop_nodes, 0)
            cf = st.cand_f[nd].astype(jnp.float32)
            ci = st.cand_i[nd]
            nf = st.node_f[nd].astype(jnp.float32)
            rec_f = jnp.stack([
                vp.astype(jnp.float32),
                pop_ref.astype(jnp.float32),
                ci[:, CI_FEAT].astype(jnp.float32),
                ci[:, CI_THR].astype(jnp.float32),
                (ci[:, CI_FLAGS] & 1).astype(jnp.float32),
                cf[:, CF_GAIN],
                cf[:, CF_LOUT], cf[:, CF_ROUT],
                cf[:, CF_LCNT], cf[:, CF_RCNT],
                nf[:, LF_OUT], nf[:, LF_CNT],
                cf[:, CF_LSH], cf[:, CF_RSH],
                cf[:, CF_LSG], cf[:, CF_RSG],
                ((ci[:, CI_FLAGS] & 2) >> 1).astype(jnp.float32)], axis=1)
            assert rec_f.shape[1] == NUM_REC_FIELDS
            rec_i = st.cnt_i[nd]
            rec_cat = st.cand_b[nd]

            # ---- map speculative leaves to their final ancestors
            final = avail  # revealed and never popped
            iota = jnp.arange(self.M, dtype=jnp.int32)
            T = jnp.where(final, iota, st.parent)
            # pointer-jump doubling: k iterations cover chains of 2^k; chain
            # depth is bounded by the node count M
            for _ in range(max(1, (self.M - 1).bit_length())):
                T = T[T]
            slot2ref = jnp.where(final[T], refidx[T], 0)
            # chunked lookup: the (rows, M_pad) one-hot transient is bounded to
            # ~2^17 rows per step regardless of N (at 10.5M rows an unchunked
            # one-hot would be ~24 GB)
            Cl = 1
            while self._rows_len() // Cl > (1 << 17) and Cl < 1024 \
                    and self._rows_len() % (Cl * 2) == 0:
                Cl *= 2
            if Cl == 1:
                leaf_ref = lookup_int(slot2ref, st.lid_p)
            else:
                leaf_ref = lax.map(
                    lambda lid_c: lookup_int(slot2ref, lid_c),
                    st.lid_p.reshape(Cl, self._rows_len() // Cl)).reshape(-1)
            leaf_id = to_row_order(st.rid_p, leaf_ref, self.num_leaves)
            leaf_out = jnp.zeros(self.num_leaves, jnp.float32).at[
                jnp.where(final, refidx, self.num_leaves + 7)].set(
                    st.node_f[:, LF_OUT].astype(jnp.float32))
            if self._quant and self._q_raw is not None:
                # leaf-output RENEWAL (the quantized-training recipe's
                # accuracy anchor): per-leaf sums re-accumulated from the
                # RETAINED f32 gradients over the final leaf assignment, so
                # leaf values carry no discretization error — only the split
                # STRUCTURE sees quantized sums.  Patches both the score
                # update (leaf_out) and the host records' child outputs.
                from .ops.split import calculate_leaf_output
                gb, hb = self._q_raw
                self._q_raw = None
                L = self.num_leaves
                kw = self._split_kwargs
                # FIXED-POINT accumulation: the renewed outputs feed the score,
                # and the next round's stochastic rounding keys on the score's
                # BIT PATTERN — a 1-ulp f32 summation-order difference between
                # serial and sharded would re-roll the rounding and fork the
                # tree stream.  Rounding each row to a pow2 grid and summing
                # int32 makes the reduction exact at any shard order; the grid
                # leaves k = 30 - ceil_log2(N) bits per row (>= 9 bits under
                # the F32_EXACT_ROWS gate), noise far below the quantization
                # the splits already tolerate.
                sg, sh = self._q_scales
                kb = max(30 - int(self.n_pad - 1).bit_length(), 1)
                qg = sg * jnp.float32(2.0 ** (3 - kb))    # sg·GMAX <= sg·2^3
                qh = sh * jnp.float32(2.0 ** (4 - kb))    # sh·HMAX <= sh·2^4
                rg = jnp.rint(gb / qg).astype(jnp.int32)
                rh = jnp.rint(hb / qh).astype(jnp.int32)
                lgh = jnp.zeros((2, L), jnp.int32) \
                    .at[0, leaf_id].add(rg).at[1, leaf_id].add(rh)
                lgh = self._global_scalar(lgh)
                lg = lgh[0].astype(jnp.float32) * qg
                lh = lgh[1].astype(jnp.float32) * qh
                has_h = lh > 0.0
                refined = jnp.where(
                    has_h,
                    calculate_leaf_output(
                        lg, lh, kw["lambda_l1"], kw["lambda_l2"],
                        kw["max_delta_step"]).astype(jnp.float32),
                    0.0)
                leaf_out = jnp.where(has_h, refined, leaf_out)
                # pop i's left child keeps ref pop_ref[i]; its right child is
                # ref 1 + i (the replay's leaf numbering)
                lref = jnp.clip(pop_ref, 0, L - 1)
                rref = jnp.minimum(jnp.arange(budget, dtype=jnp.int32) + 1,
                                   L - 1)
                from .learner import REC_LEFT_OUT, REC_RIGHT_OUT
                rec_f = rec_f \
                    .at[:, REC_LEFT_OUT].set(
                        jnp.where(vp & has_h[lref], refined[lref],
                                  rec_f[:, REC_LEFT_OUT])) \
                    .at[:, REC_RIGHT_OUT].set(
                        jnp.where(vp & has_h[rref], refined[rref],
                                  rec_f[:, REC_RIGHT_OUT]))
        if st.telem is not None:
            return rec_f, rec_i, rec_cat, leaf_id, leaf_out, st.telem
        return rec_f, rec_i, rec_cat, leaf_id, leaf_out

    # -- host orchestration ---------------------------------------------------

    def memory_gauges(self) -> dict:
        """Working-set byte breakdown for the telemetry report — the SAME
        formula the eligibility gate uses (``wave_transient_bytes``), over
        this learner's actual (bundled / local-shard) dimensions."""
        return wave_transient_bytes(self.cfg, self._rows_len(),
                                    self.fw * 4, self._hist_nbins)

    def _pop_telem(self, out):
        """Strip the trailing device counter vector off a tree program's
        outputs (stashed for ``take_telemetry``); identity when telemetry
        is off, so every caller keeps its 5-tuple contract."""
        if self._telemetry:
            self._last_telem = out[5]
            return out[:5]
        return out

    def train_async(self, grad: jax.Array, hess: jax.Array, bag: jax.Array,
                    feature_mask: Optional[jax.Array] = None):
        if feature_mask is None:
            feature_mask = jnp.ones(self.num_features, dtype=bool)
        out = self._jit_tree_w(
            self.bins_packed(), grad, hess, bag, feature_mask)
        if getattr(self, "_tree_w_bitcast", False):
            # undo the donation landing-slot bitcast (see __init__):
            # leaf_id rides out of the donating jit as f32 bits
            leaf_id = jax.lax.bitcast_convert_type(out[3], jnp.int32)
            out = out[:3] + (leaf_id,) + out[4:]
        return self._pop_telem(out)


def wave_transient_bytes(cfg: Config, n_pad: int, f_pad: int, b: int
                         ) -> dict:
    """Working-set byte breakdown of the wave learner (``n_pad`` is the
    PER-DEVICE row count for sharded use).  Single source of truth for
    ``wave_budget_reason``'s gate AND the telemetry memory gauge
    (``WaveTPUTreeLearner.memory_gauges``) — the budget decision and the
    reported gauge can never disagree."""
    budget = max(int(cfg.num_leaves), 2) - 1
    W = min(int(cfg.tpu_wave_width), budget)
    grow = min(budget + int(np.ceil(budget
                                    * _resolve_overshoot(cfg, n_pad))),
               2 * budget)
    corr = _correction_reserve(cfg, budget)
    M = 1 + 2 * (grow + corr)
    h_bytes = (grow + corr + 2) * f_pad * b * 3 * 4
    scan_bytes = 2 * W * f_pad * b * 3 * 4
    # per-wave transients (round-3 advisor): the (rows, W) f32 wave-member
    # mask is CHUNKED to 2^20 rows (lax.map in _wave_body) and the
    # leaf-ref lookup one-hot to 2^17 rows, so neither scales with N; the
    # (N,) derived per-row columns do
    m_pad = ((M + 127) // 128) * 128
    mask_bytes = min(n_pad, 1 << 20) * W * 4 + n_pad * 12
    lookup_bytes = min(n_pad, 1 << 17) * m_pad * 4
    # the growth sort's operands, in and out
    sort_bytes = 2 * growth_sort_operands(f_pad // 4) * n_pad * 4
    # batched replay correction: the vectorized partition stacks the K-1
    # extras' (fw, S) bin-word + (3, S) weight + (S,) lid slices, S up to
    # the vec cap — on wide datasets (fw in the hundreds) this per-event
    # transient is material and must count against the budget (round-5
    # advisor, low)
    k = _resolve_stall_batch(cfg)
    vc = int(getattr(cfg, "tpu_wave_vec_cap", -1))
    if vc <= 0:
        vc = WaveTPUTreeLearner._VEC_CAP
    # k (not k-1) slices: the fused-top path stacks every member's slice
    stall_vec_bytes = 0 if k == 1 else \
        k * min(vc, n_pad) * (f_pad // 4 + 4) * 4
    out = {"hist_pool_bytes": h_bytes, "child_scan_bytes": scan_bytes,
           "wave_mask_bytes": mask_bytes, "leaf_lookup_bytes": lookup_bytes,
           "sort_buffer_bytes": sort_bytes,
           "stall_vec_bytes": stall_vec_bytes}
    out["total_bytes"] = sum(out.values())
    return out


def wave_budget_reason(cfg: Config, n_pad: int, f_pad: int, b: int
                       ) -> Optional[str]:
    """Shape/byte-budget gates shared by the serial and sharded wave
    learners (``n_pad`` is the PER-DEVICE row count for sharded use)."""
    if f_pad // 4 > 64:
        return f"{f_pad} padded columns > 256 (per-row word extraction is " \
               "a masked sum over words)"
    total = wave_transient_bytes(cfg, n_pad, f_pad, b)["total_bytes"]
    if total > int(cfg.tpu_wave_max_bytes):
        return "estimated working set %.1f GB > tpu_wave_max_bytes %.1f GB" \
            % (total / 2**30, int(cfg.tpu_wave_max_bytes) / 2**30)
    return None


def wave_ineligible_reason(cfg: Config, data: _ConstructedDataset
                           ) -> Optional[str]:
    """Why the wave learner cannot run this config (None = eligible).
    Sizing uses the BUNDLED (EFB) column layout when a bundle exists —
    that is what the learner actually runs on."""
    if cfg.tree_learner != "serial":
        return f"tree_learner={cfg.tree_learner} (wave is serial-only)"
    if data.max_num_bin > 256:
        return f"max_num_bin={data.max_num_bin} > 256 (bin codes must pack " \
               "4-per-word)"
    bundle = getattr(data, "bundle", None)
    if bundle is not None:
        from .dataset import _round_up
        f_pad = _round_up(bundle.num_groups, data.FEATURE_TILE)
        b = max(int(data.max_num_bin), int(bundle.max_group_bin))
        if b > 256:
            return f"EFB bundle max bin {b} > 256"
    else:
        f_pad = data.bins.shape[0]
        b = int(data.max_num_bin)
    return wave_budget_reason(cfg, int(data.num_data_padded), f_pad, b)


def wave_eligible(cfg: Config, data: _ConstructedDataset) -> bool:
    return wave_ineligible_reason(cfg, data) is None
