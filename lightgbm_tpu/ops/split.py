"""Vectorized best-split search over (feature, bin) histograms.

TPU-native re-design of ``FeatureHistogram::FindBestThreshold*``
(`src/treelearner/feature_histogram.hpp:75-232,501-645`).  The reference runs
two sequential scans per feature (missing-values-left and missing-values-right)
with early-exit bookkeeping; here both scans become prefix/suffix cumsums over
the bin axis evaluated for every feature at once, with validity masks standing
in for the reference's ``continue``/``break`` conditions (which are monotone in
the threshold, so masking is exact).

Scan semantics preserved exactly (`feature_histogram.hpp:83-107`):
  * missing None  — single missing-left scan, thresholds 0..nb-2.
  * missing Zero  (nb>2) — both scans skip the zero bin ``d``; the zero mass
    implicitly joins the side opposite the scan; threshold ``d-1`` is never
    evaluated missing-left, ``d`` never missing-right.
  * missing NaN   (nb>2) — last bin is the NaN bin; missing-left thresholds
    0..nb-3 (NaN mass joins left), missing-right thresholds 0..nb-2 (NaN joins
    right; threshold nb-2 = "split missing vs non-missing").
  * nb<=2 or None — single scan; for NaN-with-2-bins default_left=false
    (`feature_histogram.hpp:100-103`).
  * the missing-right scan overrides only on strictly greater gain; within the
    missing-left scan ties keep the LARGEST threshold (scan order is
    right-to-left with strict >), within missing-right the smallest.

Gain math is the reference's exactly (`feature_histogram.hpp:439-498`):
L1 thresholding, L2, max_delta_step clipping, and the
``min_data_in_leaf`` / ``min_sum_hessian_in_leaf`` / ``min_gain_to_split``
feasibility limits with their epsilon conventions.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from .histogram import _on_tpu

K_EPSILON = 1e-15   # `meta.h:38`
K_MIN_SCORE = -np.inf


def _scan_by_dot(dt, b: int) -> bool:
    """On TPU, bin-axis prefix/suffix sums run as triangular-matrix MXU
    contractions: XLA's cumsum lowers to an O(B)-depth scan that costs
    ~1.8 ms per million elements on v5e (round-5 chip reading)
    while the equivalent (.., B)x(B, B) dot is ~free.  The summation
    ORDER differs from the reference's sequential accumulation, so
    near-tie thresholds can flip vs the CPU path — the same accepted
    regime as the bf16-term histograms (`docs/GPU-Performance.rst:137-141`
    documents the identical CPU-vs-GPU deltas for the reference);
    accuracy/accuracy_tpu.py records the measured effect.  CPU keeps the
    sequential order (and with it bit-parity with the reference CLI)."""
    return _on_tpu() and dt == jnp.float32 and b <= 1024


def _prefix_dot(xs, incl_mat):
    """Σ_b xs[..., b] · M[b, t] with full f32 accuracy on the MXU."""
    return jax.lax.dot_general(
        xs, incl_mat, (((xs.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)


class SplitCandidates(NamedTuple):
    """Per-feature best split (the vector analogue of ``SplitInfo``,
    `src/treelearner/split_info.hpp`)."""
    gain: jax.Array          # (F,) raw_gain - min_gain_shift; -inf if invalid
    threshold: jax.Array     # (F,) int32 bin threshold (left: bin <= thr)
    default_left: jax.Array  # (F,) bool
    left_sum_g: jax.Array    # (F,)
    left_sum_h: jax.Array    # (F,)
    left_cnt: jax.Array      # (F,) f32 integer-valued
    right_sum_g: jax.Array
    right_sum_h: jax.Array
    right_cnt: jax.Array
    left_output: jax.Array
    right_output: jax.Array


def threshold_l1(s, l1):
    reg = jnp.maximum(0.0, jnp.abs(s) - l1)
    return jnp.sign(s) * reg


def calculate_leaf_output(sum_g, sum_h, l1, l2, max_delta_step):
    """``CalculateSplittedLeafOutput`` (`feature_histogram.hpp:443-450`)."""
    ret = -threshold_l1(sum_g, l1) / (sum_h + l2)
    if max_delta_step <= 0.0:
        return ret
    return jnp.clip(ret, -max_delta_step, max_delta_step)


def leaf_split_gain_given_output(sum_g, sum_h, l1, l2, output):
    sg_l1 = threshold_l1(sum_g, l1)
    return -(2.0 * sg_l1 * output + (sum_h + l2) * output * output)


def leaf_split_gain(sum_g, sum_h, l1, l2, max_delta_step):
    """``GetLeafSplitGain`` (`feature_histogram.hpp:490-494`)."""
    out = calculate_leaf_output(sum_g, sum_h, l1, l2, max_delta_step)
    return leaf_split_gain_given_output(sum_g, sum_h, l1, l2, out)


def _split_gains(lg, lh, rg, rh, l1, l2, mds, min_c=None, max_c=None,
                 monotone=None):
    """``GetSplitGains`` (`feature_histogram.hpp:453-466`): outputs clipped
    to the leaf's [min_c, max_c] value constraint; a monotone violation
    (increasing but left>right, or decreasing but left<right) zeroes the
    gain."""
    lo = calculate_leaf_output(lg, lh, l1, l2, mds)
    ro = calculate_leaf_output(rg, rh, l1, l2, mds)
    if min_c is not None:
        lo = jnp.clip(lo, min_c, max_c)
        ro = jnp.clip(ro, min_c, max_c)
    gain = (leaf_split_gain_given_output(lg, lh, l1, l2, lo)
            + leaf_split_gain_given_output(rg, rh, l1, l2, ro))
    if monotone is not None:
        violated = ((monotone > 0) & (lo > ro)) | ((monotone < 0) & (lo < ro))
        gain = jnp.where(violated, 0.0, gain)
    return gain, lo, ro


@functools.partial(
    jax.jit,
    static_argnames=("lambda_l1", "lambda_l2", "max_delta_step",
                     "min_data_in_leaf", "min_sum_hessian_in_leaf",
                     "min_gain_to_split", "skip_missing_scan"))
def find_best_splits(hist: jax.Array, sum_gradients: jax.Array,
                     sum_hessians: jax.Array, num_data: jax.Array,
                     num_bin: jax.Array, missing_type: jax.Array,
                     default_bin: jax.Array, feature_mask: jax.Array,
                     monotone=None, min_constraint=None, max_constraint=None,
                     *, lambda_l1: float = 0.0, lambda_l2: float = 0.0,
                     max_delta_step: float = 0.0, min_data_in_leaf: int = 20,
                     min_sum_hessian_in_leaf: float = 1e-3,
                     min_gain_to_split: float = 0.0,
                     skip_missing_scan: bool = False) -> SplitCandidates:
    """Best numerical split per feature for one leaf.

    hist          : (F, B, 3) f32 — (sum_grad, sum_hess, cnt) per bin
    sum_gradients : () leaf Σg   (bagged)
    sum_hessians  : () leaf Σh   (bagged; caller does NOT pre-add epsilons)
    num_data      : () leaf count (bagged, f32 or int)
    num_bin/missing_type/default_bin : (F,) static per-feature metadata
    feature_mask  : (F,) bool — usable features this tree (feature_fraction)
    """
    f, b, _ = hist.shape
    dt = hist.dtype
    bins_i = jnp.arange(b, dtype=jnp.int32)[None, :]         # (1, B)
    nb = num_bin[:, None]                                     # (F, 1)
    d_bin = default_bin[:, None]
    mtype = missing_type[:, None]
    total_g = sum_gradients.astype(dt)
    total_h = sum_hessians.astype(dt) + 2.0 * K_EPSILON
    total_n = num_data.astype(dt)

    two_scan = (num_bin > 2) & (missing_type != MISSING_NONE)   # (F,)
    is_zero = mtype == MISSING_ZERO
    is_nan = mtype == MISSING_NAN
    two = two_scan[:, None]

    gain_shift = leaf_split_gain(total_g, total_h, lambda_l1, lambda_l2,
                                 max_delta_step)
    min_gain_shift = gain_shift + min_gain_to_split

    hg, hh, hc = hist[..., 0], hist[..., 1], hist[..., 2]      # (F, B)

    # ---- missing-left scan (reference dir == -1) -------------------------
    # Exclusions from the accumulating (right) side: default bin for Zero,
    # NaN bin(s) for NaN — the excluded mass implicitly lands on the left.
    excl_m1 = (two & is_zero & (bins_i == d_bin)) | \
              (two & is_nan & (bins_i >= nb - 1)) | (bins_i >= nb)
    keep = (~excl_m1).astype(dt)
    # right(t) = suffix sum over bins > t
    if _scan_by_dot(dt, b):
        gt = jnp.asarray(np.tril(np.ones((b, b), np.float32), -1))
        sums = _prefix_dot(jnp.stack([hg * keep, hh * keep, hc * keep],
                                     axis=-2), gt)              # (F, 3, B)
        rg_m1 = sums[..., 0, :]
        rh_m1 = sums[..., 1, :] + K_EPSILON
        rc_m1 = sums[..., 2, :]
    else:
        cg = jnp.cumsum((hg * keep)[:, ::-1], axis=1)[:, ::-1]
        ch = jnp.cumsum((hh * keep)[:, ::-1], axis=1)[:, ::-1]
        cc = jnp.cumsum((hc * keep)[:, ::-1], axis=1)[:, ::-1]
        zero_col = jnp.zeros((f, 1), dtype=dt)
        rg_m1 = jnp.concatenate([cg[:, 1:], zero_col], axis=1)  # (F, B) at thr=t
        rh_m1 = jnp.concatenate([ch[:, 1:], zero_col], axis=1) + K_EPSILON
        rc_m1 = jnp.concatenate([cc[:, 1:], zero_col], axis=1)
    lg_m1 = total_g - rg_m1
    lh_m1 = total_h - rh_m1
    lc_m1 = total_n - rc_m1

    thr_hi_m1 = jnp.where(two_scan & is_nan[:, 0], num_bin - 3, num_bin - 2)[:, None]
    valid_m1 = (bins_i <= thr_hi_m1) & (bins_i >= 0)
    valid_m1 &= ~(two & is_zero & (bins_i == d_bin - 1))       # skipped thr
    valid_m1 &= (rc_m1 >= min_data_in_leaf) & (lc_m1 >= min_data_in_leaf)
    valid_m1 &= (rh_m1 >= min_sum_hessian_in_leaf) & (lh_m1 >= min_sum_hessian_in_leaf)

    mono_b = None if monotone is None else monotone[:, None]
    g_m1, lo_m1, ro_m1 = _split_gains(lg_m1, lh_m1, rg_m1, rh_m1,
                                      lambda_l1, lambda_l2, max_delta_step,
                                      min_constraint, max_constraint, mono_b)
    g_m1 = jnp.where(valid_m1 & (g_m1 > min_gain_shift), g_m1, K_MIN_SCORE)

    # tie-break: largest threshold wins (right-to-left scan with strict >)
    best_t_m1 = (b - 1) - jnp.argmax(g_m1[:, ::-1], axis=1)
    best_g_m1 = jnp.max(g_m1, axis=1)

    if skip_missing_scan:
        # caller guarantees every feature is MISSING_NONE (single-scan):
        # the missing-right scan can contribute nothing
        take = lambda a, t: jnp.take_along_axis(a, t[:, None], axis=1)[:, 0]
        best_t = best_t_m1.astype(jnp.int32)
        lg_b = take(lg_m1, best_t)
        lh_b = take(lh_m1, best_t)
        lc_b = take(lc_m1, best_t)
        lo_b = take(lo_m1, best_t)
        ro_b = take(ro_m1, best_t)
        invalid = jnp.isneginf(best_g_m1) | ~feature_mask
        return SplitCandidates(
            gain=jnp.where(invalid, K_MIN_SCORE, best_g_m1 - min_gain_shift),
            threshold=best_t,
            default_left=jnp.ones(f, bool),
            left_sum_g=lg_b, left_sum_h=lh_b - K_EPSILON, left_cnt=lc_b,
            right_sum_g=total_g - lg_b,
            right_sum_h=total_h - lh_b - K_EPSILON,
            right_cnt=total_n - lc_b,
            left_output=lo_b, right_output=ro_b)

    # ---- missing-right scan (reference dir == +1), two-scan features only --
    excl_p1 = (is_zero & (bins_i == d_bin)) | \
              (is_nan & (bins_i >= nb - 1)) | (bins_i >= nb)
    keep_p = (~excl_p1).astype(dt)
    if _scan_by_dot(dt, b):
        le = jnp.asarray(np.triu(np.ones((b, b), np.float32)))
        sums_p = _prefix_dot(jnp.stack([hg * keep_p, hh * keep_p,
                                        hc * keep_p], axis=-2), le)
        lg_p1 = sums_p[..., 0, :]
        lh_p1 = sums_p[..., 1, :] + K_EPSILON
        lc_p1 = sums_p[..., 2, :]
    else:
        lg_p1 = jnp.cumsum(hg * keep_p, axis=1)                # left(t): bins<=t
        lh_p1 = jnp.cumsum(hh * keep_p, axis=1) + K_EPSILON
        lc_p1 = jnp.cumsum(hc * keep_p, axis=1)
    rg_p1 = total_g - lg_p1
    rh_p1 = total_h - lh_p1
    rc_p1 = total_n - lc_p1

    valid_p1 = two & (bins_i <= nb - 2)
    valid_p1 &= ~(is_zero & (bins_i == d_bin))
    valid_p1 &= (lc_p1 >= min_data_in_leaf) & (rc_p1 >= min_data_in_leaf)
    valid_p1 &= (lh_p1 >= min_sum_hessian_in_leaf) & (rh_p1 >= min_sum_hessian_in_leaf)

    g_p1, lo_p1, ro_p1 = _split_gains(lg_p1, lh_p1, rg_p1, rh_p1,
                                      lambda_l1, lambda_l2, max_delta_step,
                                      min_constraint, max_constraint, mono_b)
    g_p1 = jnp.where(valid_p1 & (g_p1 > min_gain_shift), g_p1, K_MIN_SCORE)
    best_t_p1 = jnp.argmax(g_p1, axis=1)                       # smallest thr
    best_g_p1 = jnp.max(g_p1, axis=1)

    # ---- combine scans (missing-right overrides on strictly greater gain) --
    use_p1 = best_g_p1 > best_g_m1
    best_t = jnp.where(use_p1, best_t_p1, best_t_m1).astype(jnp.int32)
    best_g = jnp.where(use_p1, best_g_p1, best_g_m1)
    # for the NaN 2-bin case the reference forces default right
    # (`feature_histogram.hpp:100-103`)
    default_left = jnp.where(use_p1, False,
                             ~((~two_scan) & (missing_type == MISSING_NAN)))

    take = lambda a, t: jnp.take_along_axis(a, t[:, None], axis=1)[:, 0]
    lg_b = jnp.where(use_p1, take(lg_p1, best_t), take(lg_m1, best_t))
    lh_b = jnp.where(use_p1, take(lh_p1, best_t), take(lh_m1, best_t))
    lc_b = jnp.where(use_p1, take(lc_p1, best_t), take(lc_m1, best_t))
    lo_b = jnp.where(use_p1, take(lo_p1, best_t), take(lo_m1, best_t))
    ro_b = jnp.where(use_p1, take(ro_p1, best_t), take(ro_m1, best_t))

    invalid = jnp.isneginf(best_g) | ~feature_mask
    gain_out = jnp.where(invalid, K_MIN_SCORE, best_g - min_gain_shift)

    return SplitCandidates(
        gain=gain_out,
        threshold=best_t,
        default_left=default_left,
        left_sum_g=lg_b, left_sum_h=lh_b - K_EPSILON, left_cnt=lc_b,
        right_sum_g=total_g - lg_b,
        right_sum_h=total_h - lh_b - K_EPSILON,
        right_cnt=total_n - lc_b,
        left_output=lo_b, right_output=ro_b)


def forced_split_info(hrow: jax.Array, sum_g: jax.Array, sum_h: jax.Array,
                      cnt: jax.Array, *, threshold: int, num_bin: int,
                      missing_type: int, default_bin: int, is_cat: bool,
                      lambda_l1: float, lambda_l2: float,
                      max_delta_step: float, min_gain_to_split: float):
    """Split info at a FORCED (feature, threshold) —
    ``FeatureHistogram::GatherInfoForThreshold``
    (`src/treelearner/feature_histogram.hpp:273-413`).

    hrow: (B, 3) histogram row of the forced feature; threshold/metadata are
    STATIC (the forced-split tree is fixed at config time).  Feasibility
    limits (min_data / min_hessian) are BYPASSED like the reference; only
    the gain-vs-no-split check applies (gain <= shift ⇒ the forced split is
    refused and the whole remaining forced queue aborts,
    `serial_tree_learner.cpp:612-616`).

    Returns (gain, left_g, left_h_eps, left_cnt, right_g, right_h_eps,
    right_cnt, left_out, right_out, valid); *_h_eps carry the same epsilon
    convention as ``find_best_splits``'s packed rows (raw + K_EPSILON is
    subtracted back by the caller's storage convention).
    """
    dt = hrow.dtype
    total_g = sum_g.astype(dt)
    total_h = sum_h.astype(dt) + 2.0 * K_EPSILON
    total_n = cnt.astype(dt)
    gain_shift = leaf_split_gain(total_g, total_h, lambda_l1, lambda_l2,
                                 max_delta_step)
    min_gain_shift = gain_shift + min_gain_to_split
    b_idx = np.arange(hrow.shape[0])
    if is_cat:
        # one-hot categorical forced split (`feature_histogram.hpp:359-413`)
        lg = hrow[threshold, 0]
        lh = hrow[threshold, 1] + K_EPSILON
        lc = hrow[threshold, 2]
        rg = total_g - lg
        rh = total_h - lh
        rc = total_n - lc
        # NOTE: the reference computes the left term of the gain check with
        # the RIGHT hessian (`feature_histogram.hpp:389-394`) — mirrored
        # verbatim so forced-categorical acceptance matches
        cur = leaf_split_gain(rg, rh, lambda_l1, lambda_l2, max_delta_step) \
            + leaf_split_gain(lg, rh, lambda_l1, lambda_l2, max_delta_step)
        ok = threshold < num_bin
    else:
        # right = bins >= threshold, never bin 0, skipping the default bin
        # for MissingType::Zero and the NaN bin for MissingType::NaN
        # (`feature_histogram.hpp:284-322`)
        m = (b_idx >= max(int(threshold), 1)) & (b_idx < num_bin)
        if missing_type == MISSING_ZERO:
            m &= b_idx != default_bin
        elif missing_type == MISSING_NAN:
            m &= b_idx <= num_bin - 2
        mv = jnp.asarray(m, dt)
        rg = jnp.sum(hrow[:, 0] * mv)
        rh = jnp.sum(hrow[:, 1] * mv) + K_EPSILON
        rc = jnp.sum(hrow[:, 2] * mv)
        lg = total_g - rg
        lh = total_h - rh
        lc = total_n - rc
        cur = leaf_split_gain(lg, lh, lambda_l1, lambda_l2, max_delta_step) \
            + leaf_split_gain(rg, rh, lambda_l1, lambda_l2, max_delta_step)
        ok = True
    valid = ok & ~jnp.isnan(cur) & (cur > min_gain_shift)
    lo = calculate_leaf_output(lg, lh, lambda_l1, lambda_l2, max_delta_step)
    ro = calculate_leaf_output(rg, rh, lambda_l1, lambda_l2, max_delta_step)
    gain = cur - min_gain_shift
    return gain, lg, lh, lc, rg, rh, rc, lo, ro, valid


def best_over_features(cands: SplitCandidates):
    """argmax over features; first (lowest-index) feature wins ties, matching
    the serial learner's in-order strict-> merge
    (`serial_tree_learner.cpp:505-520`)."""
    best_f = jnp.argmax(cands.gain)
    pick = lambda a: a[best_f]
    return best_f, jax.tree_util.tree_map(pick, cands)
