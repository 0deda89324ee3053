"""Pallas TPU fused split-scan kernel (the reference's second kernel).

The OpenCL reference pairs its histogram kernels with a split-scan kernel
that walks the cumulative histogram and reduces the best threshold per
feature on-device; this port fuses the same stages for the wave learner's
batched child scans: for a (leaves, features, bins, 3) histogram cube, ONE
kernel computes both missing-direction cumulative scans (as triangular
MXU contractions — the exact matrices ``ops/split.py`` uses on its
``_scan_by_dot`` path), evaluates the reference gain formula with the
validity masks, and reduces the per-feature best (gain, threshold,
direction, child aggregates) — replacing the XLA scan+argmax chain whose
~15 intermediate (K·F·B) arrays round-trip HBM between fused ops.

``fused_child_scans`` goes one launch further for the quantized wave
step: it takes each wave member's SMALLER-child histogram plus the
parent's pooled histogram and performs sibling subtraction, left/right
selection, the per-child ``FixHistogram`` default-bin rebuild, and BOTH
children's split scans inside the same kernel — the hist→subtract→fix→
scan chain that previously spanned one Pallas launch plus ~10 fused XLA
ops with (2K·F·B) HBM round-trips between them.  The raw (unfixed)
child histograms are emitted as secondary outputs so the histogram pool
keeps the same contents as the unfused path (the fix is scan-local,
exactly as in ``_cand_rows_batch``).

Semantics are ``find_best_splits``'s exactly (missing-left/right scan
exclusions, L1/L2/max_delta_step gain math, min_data/min_hessian
feasibility, the largest-threshold tie-break missing-left and smallest
missing-right, strict-> override); monotone constraints, categorical
features and feature penalties keep the XLA path (the learner gates).
Golden parity vs ``find_best_splits`` on dyadic inputs is bit-exact
(tests/test_partition.py); on arbitrary f32 inputs the two paths differ
only by summation-order ulps, the same accepted regime as the
``_scan_by_dot`` fast path (`docs/GPU-Performance.rst:137-141`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from .split import (K_EPSILON, K_MIN_SCORE, SplitCandidates,
                    calculate_leaf_output, leaf_split_gain,
                    leaf_split_gain_given_output)

#: output columns: gain, threshold, default_left, lg, lh(+eps), lc, lo, ro
N_OUT = 8


def _scan_body(hg, hh, hc, total_g, total_h, total_n, nb, mtype, d_bin, *,
               b: int, f: int, lambda_l1: float, lambda_l2: float,
               max_delta_step: float, min_data_in_leaf: int,
               min_sum_hessian_in_leaf: float, min_gain_to_split: float):
    """One leaf's (F, B) split scan — shared by the batched scan kernel
    and the fused child-scan kernel.  ``total_h`` arrives with the
    2·K_EPSILON carry already added; hg/hh/hc are (F, B) channel planes;
    nb/mtype/d_bin are the (F, 1) per-feature metadata columns.  Every
    intermediate stays 2-D with features in sublanes (per-feature
    reductions keep their lane axis as size 1), so nothing asks Mosaic
    for a 1-D layout or a sublane<->lane relayout.
    Returns the (F, N_OUT) output columns."""
    l1, l2, mds = lambda_l1, lambda_l2, max_delta_step
    iota_b = lax.broadcasted_iota(jnp.int32, (f, b), 1)
    two = (nb > 2) & (mtype != MISSING_NONE)
    is_zero = mtype == MISSING_ZERO
    is_nan = mtype == MISSING_NAN

    gain_shift = leaf_split_gain(total_g, total_h, l1, l2, mds)
    min_gain_shift = gain_shift + min_gain_to_split

    def split_gains(lg, lh, rg, rh):
        lo = calculate_leaf_output(lg, lh, l1, l2, mds)
        ro = calculate_leaf_output(rg, rh, l1, l2, mds)
        gain = (leaf_split_gain_given_output(lg, lh, l1, l2, lo)
                + leaf_split_gain_given_output(rg, rh, l1, l2, ro))
        return gain, lo, ro

    def tri_dot(keep, lower_strict):
        """Σ_b hist[..., b]·M[b, t] — the same triangular matrices (and
        HIGHEST-precision contraction) as ops/split.py's dot path; 2D
        operands only (Mosaic's dot support)."""
        io0 = lax.broadcasted_iota(jnp.int32, (b, b), 0)
        io1 = lax.broadcasted_iota(jnp.int32, (b, b), 1)
        m = (io0 > io1) if lower_strict else (io0 <= io1)
        m = m.astype(jnp.float32)
        # one contraction per channel: F is not sublane-aligned, so a
        # stacked (3F, B) operand would need unaligned concat + slices
        return tuple(
            lax.dot_general(x * keep, m, (((1,), (0,)), ((), ())),
                            precision=lax.Precision.HIGHEST)
            for x in (hg, hh, hc))

    # ---- missing-left scan (suffix sums over bins > t)
    excl_m1 = (two & is_zero & (iota_b == d_bin)) | \
              (two & is_nan & (iota_b >= nb - 1)) | (iota_b >= nb)
    keep = (~excl_m1).astype(jnp.float32)
    rg_m1, rh_m1, rc_m1 = tri_dot(keep, lower_strict=True)
    rh_m1 = rh_m1 + K_EPSILON
    lg_m1 = total_g - rg_m1
    lh_m1 = total_h - rh_m1
    lc_m1 = total_n - rc_m1
    thr_hi = jnp.where(two & is_nan, nb - 3, nb - 2)
    valid_m1 = (iota_b <= thr_hi)
    valid_m1 &= ~(two & is_zero & (iota_b == d_bin - 1))
    valid_m1 &= (rc_m1 >= min_data_in_leaf) & (lc_m1 >= min_data_in_leaf)
    valid_m1 &= (rh_m1 >= min_sum_hessian_in_leaf) & \
        (lh_m1 >= min_sum_hessian_in_leaf)
    g_m1, lo_m1, ro_m1 = split_gains(lg_m1, lh_m1, rg_m1, rh_m1)
    g_m1 = jnp.where(valid_m1 & (g_m1 > min_gain_shift), g_m1, K_MIN_SCORE)
    best_g_m1 = jnp.max(g_m1, axis=1, keepdims=True)       # (F, 1)
    # largest threshold wins ties (right-to-left scan with strict >)
    thr_m1 = jnp.max(jnp.where(g_m1 == best_g_m1, iota_b, -1),
                     axis=1, keepdims=True)

    # ---- missing-right scan (prefix sums over bins <= t)
    excl_p1 = (is_zero & (iota_b == d_bin)) | \
              (is_nan & (iota_b >= nb - 1)) | (iota_b >= nb)
    keep_p = (~excl_p1).astype(jnp.float32)
    lg_p1, lh_p1, lc_p1 = tri_dot(keep_p, lower_strict=False)
    lh_p1 = lh_p1 + K_EPSILON
    rg_p1 = total_g - lg_p1
    rh_p1 = total_h - lh_p1
    rc_p1 = total_n - lc_p1
    valid_p1 = two & (iota_b <= nb - 2)
    valid_p1 &= ~(is_zero & (iota_b == d_bin))
    valid_p1 &= (lc_p1 >= min_data_in_leaf) & (rc_p1 >= min_data_in_leaf)
    valid_p1 &= (lh_p1 >= min_sum_hessian_in_leaf) & \
        (rh_p1 >= min_sum_hessian_in_leaf)
    g_p1, lo_p1, ro_p1 = split_gains(lg_p1, lh_p1, rg_p1, rh_p1)
    g_p1 = jnp.where(valid_p1 & (g_p1 > min_gain_shift), g_p1, K_MIN_SCORE)
    best_g_p1 = jnp.max(g_p1, axis=1, keepdims=True)
    # smallest threshold wins (left-to-right scan with strict >)
    thr_p1 = jnp.min(jnp.where(g_p1 == best_g_p1, iota_b, b),
                     axis=1, keepdims=True)

    # ---- combine (missing-right overrides on strictly greater gain)
    use_p1 = best_g_p1 > best_g_m1
    best_t = jnp.where(use_p1, thr_p1, thr_m1)
    best_g = jnp.where(use_p1, best_g_p1, best_g_m1)
    # (boolean algebra, not a select: Mosaic cannot legalize
    # arith.select on i1 vectors)
    dleft = ~use_p1 & ~(~two & is_nan)

    def take(a_m1, a_p1):
        sel = iota_b == best_t
        pick = lambda a: jnp.sum(jnp.where(sel, a, 0.0), axis=1,
                                 keepdims=True)
        return jnp.where(use_p1, pick(a_p1), pick(a_m1))

    cols = [best_g, best_t.astype(jnp.float32), dleft.astype(jnp.float32),
            take(lg_m1, lg_p1), take(lh_m1, lh_p1), take(lc_m1, lc_p1),
            take(lo_m1, lo_p1), take(ro_m1, ro_p1)]
    # place column j in lane j by select (no unaligned lane concat)
    lane = lax.broadcasted_iota(jnp.int32, (f, N_OUT), 1)
    out = jnp.zeros((f, N_OUT), jnp.float32)
    for j, col in enumerate(cols):
        out = jnp.where(lane == j, col, out)
    return out


def _scan_kernel(hist_ref, tot_ref, meta_ref, out_ref, *, b: int, f: int,
                 **scan_kw):
    i = pl.program_id(0)
    out_ref[0] = _scan_body(
        hist_ref[0, 0], hist_ref[0, 1], hist_ref[0, 2],
        tot_ref[0, i], tot_ref[1, i] + 2.0 * K_EPSILON, tot_ref[2, i],
        meta_ref[0], meta_ref[1], meta_ref[2], b=b, f=f, **scan_kw)


def _feature_meta(num_bin, missing_type, default_bin):
    """(3, F, 1) int32 per-feature metadata: features in sublanes, the
    layout ``_scan_body`` broadcasts against the (F, B) planes."""
    return jnp.stack([num_bin, missing_type, default_bin]) \
        .astype(jnp.int32)[:, :, None]


# Small operands, in forms Mosaic accepts: per-leaf totals ride whole in
# SMEM (leaf index = program id, K in the minor axis so SMEM's padding of
# the major axis stays small), feature metadata is one whole-array VMEM
# block (constant index map: fetched once).
_SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)


def _meta_spec(f: int):
    return pl.BlockSpec((3, f, 1), lambda i: (0, 0, 0))


@functools.partial(jax.jit, static_argnames=(
    "lambda_l1", "lambda_l2", "max_delta_step", "min_data_in_leaf",
    "min_sum_hessian_in_leaf", "min_gain_to_split", "interpret"))
def find_best_splits_batched(hist, sum_gradients, sum_hessians, num_data,
                             num_bin, missing_type, default_bin,
                             feature_mask, *, lambda_l1: float = 0.0,
                             lambda_l2: float = 0.0,
                             max_delta_step: float = 0.0,
                             min_data_in_leaf: int = 20,
                             min_sum_hessian_in_leaf: float = 1e-3,
                             min_gain_to_split: float = 0.0,
                             interpret: bool = False) -> SplitCandidates:
    """Batched ``find_best_splits`` through the fused Pallas kernel.

    hist : (K, F, B, 3) f32 — one leaf per K slot (already FixHistogram'd
           / unbundled by the caller); sum_* / num_data (K,); feature
           meta (F,) int32.  Returns a (K, F)-batched SplitCandidates —
    the same post-shift gain / epsilon-carry conventions as the XLA path.
    """
    k, f, b, _ = hist.shape
    hist_t = hist.transpose(0, 3, 1, 2)           # (K, 3, F, B): B in lanes
    totals = jnp.stack([sum_gradients, sum_hessians, num_data]) \
        .astype(jnp.float32)                                  # (3, K)
    out = pl.pallas_call(
        functools.partial(
            _scan_kernel, b=b, f=f, lambda_l1=lambda_l1,
            lambda_l2=lambda_l2, max_delta_step=max_delta_step,
            min_data_in_leaf=min_data_in_leaf,
            min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
            min_gain_to_split=min_gain_to_split),
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, 3, f, b), lambda i: (i, 0, 0, 0)),
            _SMEM_WHOLE,
            _meta_spec(f),
        ],
        out_specs=pl.BlockSpec((1, f, N_OUT), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, f, N_OUT), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="find_best_splits_batched",
    )(hist_t, totals, _feature_meta(num_bin, missing_type, default_bin))
    return _candidates(out, sum_gradients, sum_hessians, num_data,
                       feature_mask, lambda_l1=lambda_l1,
                       lambda_l2=lambda_l2, max_delta_step=max_delta_step,
                       min_gain_to_split=min_gain_to_split)


def _candidates(out, sum_gradients, sum_hessians, num_data, feature_mask, *,
                lambda_l1, lambda_l2, max_delta_step, min_gain_to_split):
    """(K, F, N_OUT) kernel output columns -> (K, F) SplitCandidates."""
    dt = out.dtype
    total_g = sum_gradients.astype(dt)
    total_h = sum_hessians.astype(dt) + 2.0 * K_EPSILON
    total_n = num_data.astype(dt)
    best_g = out[:, :, 0]
    best_t = jnp.rint(out[:, :, 1]).astype(jnp.int32)
    dleft = out[:, :, 2] > 0.5
    lg_b, lh_b, lc_b = out[:, :, 3], out[:, :, 4], out[:, :, 5]
    lo_b, ro_b = out[:, :, 6], out[:, :, 7]
    gain_shift = leaf_split_gain(total_g, total_h, lambda_l1, lambda_l2,
                                 max_delta_step)
    min_gain_shift = (gain_shift + min_gain_to_split)[:, None]
    invalid = jnp.isneginf(best_g) | ~feature_mask[None, :]
    tg, th, tn = total_g[:, None], total_h[:, None], total_n[:, None]
    return SplitCandidates(
        gain=jnp.where(invalid, K_MIN_SCORE, best_g - min_gain_shift),
        threshold=best_t,
        default_left=dleft,
        left_sum_g=lg_b, left_sum_h=lh_b - K_EPSILON, left_cnt=lc_b,
        right_sum_g=tg - lg_b, right_sum_h=th - lh_b - K_EPSILON,
        right_cnt=tn - lc_b,
        left_output=lo_b, right_output=ro_b)


def _fused_kernel(hsm_ref, hpar_ref, lsm_ref, tot_ref, meta_ref, hl_ref,
                  hr_ref, out_ref, *, b: int, f: int, **scan_kw):
    """One wave member's full child-scan chain: sibling subtraction,
    left/right selection, per-child FixHistogram, both split scans."""
    i = pl.program_id(0)
    h_small = hsm_ref[0]                         # (3, F, B)
    h_large = hpar_ref[0] - h_small
    lsm = lsm_ref[i] > 0
    hl = jnp.where(lsm, h_small, h_large)
    hr = jnp.where(lsm, h_large, h_small)
    # RAW (unfixed) child histograms back to the pool — identical pool
    # contents to the unfused path; the default-bin fix is scan-local
    hl_ref[0] = hl
    hr_ref[0] = hr
    nb, mtype, db = meta_ref[0], meta_ref[1], meta_ref[2]  # (F, 1)
    iota_b = lax.broadcasted_iota(jnp.int32, (f, b), 1)
    dbm = (iota_b == db) & (db > 0)                        # (F, B)
    keep = (~dbm).astype(jnp.float32)
    for c, hch in ((0, hl), (1, hr)):
        tg = tot_ref[0, 2 * i + c]
        th_raw = tot_ref[1, 2 * i + c]
        tn = tot_ref[2, 2 * i + c]
        # Dataset::FixHistogram (`src/io/dataset.cpp:923-941`): rebuild
        # the default-bin entry as child totals minus the other bins
        fixed = [
            jnp.where(dbm, tot - jnp.sum(hch[ch] * keep, axis=1,
                                         keepdims=True), hch[ch])
            for ch, tot in enumerate((tg, th_raw, tn))]
        out_ref[0, c] = _scan_body(
            *fixed, tg, th_raw + 2.0 * K_EPSILON, tn, nb, mtype, db,
            b=b, f=f, **scan_kw)


@functools.partial(jax.jit, static_argnames=(
    "lambda_l1", "lambda_l2", "max_delta_step", "min_data_in_leaf",
    "min_sum_hessian_in_leaf", "min_gain_to_split", "interpret"))
def fused_child_scans(h_small, h_par, left_small, sum_g2, sum_h2, num2,
                      num_bin, missing_type, default_bin, feature_mask, *,
                      lambda_l1: float = 0.0, lambda_l2: float = 0.0,
                      max_delta_step: float = 0.0,
                      min_data_in_leaf: int = 20,
                      min_sum_hessian_in_leaf: float = 1e-3,
                      min_gain_to_split: float = 0.0,
                      interpret: bool = False):
    """Fused subtract→select→fix→scan for all K wave members.

    h_small    : (K, F, B, 3) f32 — each member's SMALLER-child histogram.
    h_par      : (K, F, B, 3) f32 — the member's pooled parent histogram.
    left_small : (K,) bool — whether the smaller child is the left child.
    sum_g2/sum_h2/num2 : (2K,) f32 — per-child totals, interleaved
                 [l0, r0, l1, r1, …] exactly as ``_children_bookkeeping``
                 builds them.
    Returns (cands, hl, hr): a (2K, F)-batched SplitCandidates in the
    same interleaved child order, plus the RAW left/right child
    histograms (K, F, B, 3) for the caller's pool writes.
    """
    k, f, b, _ = h_small.shape
    hs_t = h_small.transpose(0, 3, 1, 2)          # (K, 3, F, B)
    hp_t = h_par.transpose(0, 3, 1, 2)
    totals = jnp.stack([sum_g2, sum_h2, num2]).astype(jnp.float32)  # (3, 2K)
    kern = functools.partial(
        _fused_kernel, b=b, f=f, lambda_l1=lambda_l1, lambda_l2=lambda_l2,
        max_delta_step=max_delta_step, min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
        min_gain_to_split=min_gain_to_split)
    hist_spec = pl.BlockSpec((1, 3, f, b), lambda i: (i, 0, 0, 0))
    hl_t, hr_t, out = pl.pallas_call(
        kern,
        grid=(k,),
        in_specs=[hist_spec, hist_spec, _SMEM_WHOLE, _SMEM_WHOLE,
                  _meta_spec(f)],
        out_specs=[
            hist_spec, hist_spec,
            pl.BlockSpec((1, 2, f, N_OUT), lambda i: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, 3, f, b), jnp.float32),
            jax.ShapeDtypeStruct((k, 3, f, b), jnp.float32),
            jax.ShapeDtypeStruct((k, 2, f, N_OUT), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="fused_child_scans",
    )(hs_t, hp_t, left_small.astype(jnp.int32), totals,
      _feature_meta(num_bin, missing_type, default_bin))
    cands = _candidates(out.reshape(2 * k, f, N_OUT), sum_g2, sum_h2, num2,
                        feature_mask, lambda_l1=lambda_l1,
                        lambda_l2=lambda_l2, max_delta_step=max_delta_step,
                        min_gain_to_split=min_gain_to_split)
    return cands, hl_t.transpose(0, 2, 3, 1), hr_t.transpose(0, 2, 3, 1)


def scan_ineligible_reason(f: int, b: int, has_monotone: bool,
                           has_categorical: bool, has_penalty: bool,
                           hist_dp: bool):
    """Why the fused scan cannot serve this learner (None = eligible)."""
    if has_monotone:
        return "monotone constraints need the per-leaf bound plumbing"
    if has_categorical:
        return "categorical candidates merge through the XLA path"
    if has_penalty:
        return "feature_contri penalties apply on the XLA path"
    if hist_dp:
        return "f64 histograms (gpu_use_dp analogue) stay on XLA"
    if b > 512:
        return f"{b} bins > 512 (triangular scan block)"
    if f * b * 12 > (1 << 22):
        return "histogram block exceeds the 4MB VMEM budget"
    return None


def fused_scan_ineligible_reason(f: int, b: int):
    """Extra VMEM gate for ``fused_child_scans`` on top of
    ``scan_ineligible_reason``: the fused kernel holds four (3, F, B)
    histogram blocks (small, parent, left, right) plus the scan
    transients at once."""
    if f * b * 12 * 6 > (1 << 22):
        return "fused child-scan blocks exceed the 4MB VMEM budget"
    return None
