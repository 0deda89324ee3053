"""Pallas partition / split-scan kernel parity (round 6).

The partition kernel must reproduce the stable sort's permutation BIT-
EXACTLY (it is default-on on TPU only because of this property), and the
fused split-scan must match ``find_best_splits`` — exactly on dyadic
inputs (where every summation order is lossless), to summation-order ulps
on arbitrary f32.  Off-TPU both kernels run in Pallas interpret mode.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.partition_pallas import (apply_partition,
                                               exclusive_cumsum_i32,
                                               partition_ineligible_reason)


def _rand_payload(rng, fw, n):
    bins = rng.randint(-2**31, 2**31 - 1, size=(fw, n)) \
        .astype(np.int64).astype(np.int32)
    w_p = rng.randn(3, n).astype(np.float32)
    rid = np.arange(n, dtype=np.int32)
    lid = rng.randint(0, 1000, size=n).astype(np.int32)
    return bins, w_p, rid, lid


def _run_partition(n, windows, seed=0, left_bias=None):
    """Drive the kernel directly on synthetic split windows; reference is
    the inverse-permutation gather of the analytically known dests."""
    rng = np.random.RandomState(seed)
    w_slots = 8
    bins, w_p, rid, lid = _rand_payload(rng, 2, n)
    go_left = rng.rand(n) < (rng.rand() if left_bias is None else left_bias)
    ps = np.zeros(w_slots, np.int32)
    cw = np.zeros(w_slots, np.int32)
    active = np.zeros(w_slots, bool)
    # scatter the windows over arbitrary member slots (the wave's top-k
    # order is position-independent — the round-6 walk bug regression)
    slots = rng.permutation(w_slots)[:len(windows)]
    gl = np.zeros(n, bool)
    gr = np.zeros(n, bool)
    lc = np.zeros(w_slots, np.int32)
    for slot, (s, c) in zip(slots, windows):
        ps[slot], cw[slot], active[slot] = s, c, True
        gl[s:s + c] = go_left[s:s + c]
        gr[s:s + c] = ~go_left[s:s + c]
        lc[slot] = gl[s:s + c].sum()
    mvd = (gl | gr).astype(np.int32)
    cum = np.asarray(exclusive_cumsum_i32(
        jnp.asarray(np.stack([gl, gr]).astype(np.int32))))
    cl, cr = cum[0], cum[1]
    dest = np.arange(n, dtype=np.int32)
    for slot, (s, c) in zip(slots, windows):
        base_l = s - cl[s]
        base_r = s + lc[slot] - cr[s]
        seg = slice(s, s + c)
        dest[seg] = np.where(gl[seg], base_l + cl[seg], base_r + cr[seg])
    out = apply_partition(
        jnp.asarray(bins), jnp.asarray(w_p), jnp.asarray(rid),
        jnp.asarray(lid), jnp.asarray(dest), jnp.asarray(mvd),
        jnp.asarray(ps), jnp.asarray(lc), jnp.asarray(cw),
        jnp.asarray(active), jnp.asarray(cl), jnp.asarray(cr),
        jnp.asarray(cl[ps]), jnp.asarray(cr[ps]), interpret=True)
    inv = np.zeros(n, np.int64)
    inv[dest] = np.arange(n)
    assert np.array_equal(np.asarray(out[0]), bins[:, inv])
    assert np.array_equal(np.asarray(out[1]).view(np.int32),
                          w_p[:, inv].view(np.int32))
    assert np.array_equal(np.asarray(out[2]), rid[inv])
    assert np.array_equal(np.asarray(out[3]), lid[inv])


def test_partition_kernel_windows():
    _run_partition(2048, [(0, 700), (900, 1000)], seed=1)


def test_partition_kernel_whole_array():
    _run_partition(1024, [(0, 1024)], seed=2)


def test_partition_kernel_odd_adjacent():
    _run_partition(4096, [(1, 1023), (1024, 2048), (3500, 596)], seed=3)


def test_partition_kernel_tiny_window():
    _run_partition(1024, [(100, 3)], seed=4)


def test_partition_kernel_empty():
    _run_partition(1024, [], seed=5)


def test_partition_kernel_all_one_side():
    _run_partition(1024, [(128, 512)], seed=6, left_bias=1.1)
    _run_partition(1024, [(128, 512)], seed=7, left_bias=-0.1)


def test_exclusive_cumsum_exact():
    rng = np.random.RandomState(0)
    for n in (512, 2048, 3072):
        f = (rng.rand(2, n) < 0.3).astype(np.int32)
        got = np.asarray(exclusive_cumsum_i32(jnp.asarray(f)))
        assert np.array_equal(got, np.cumsum(f, axis=1) - f)


def test_partition_ineligible_reasons():
    assert partition_ineligible_reason(1 << 20, 1024, 0) is None
    assert "rows" in partition_ineligible_reason((1 << 24) + 1, 10, 0)
    assert "slots" in partition_ineligible_reason(1 << 20, 1 << 17, 0)
    assert "opening" in partition_ineligible_reason(1 << 20, 10, 2)


# ---------------------------------------------------------------------------
# End-to-end: partition-vs-sort record-exact trees (the gate workload
# shape: small binary train, both learners driven through the Booster).
# ---------------------------------------------------------------------------


def _gate_data(n=2048, f=10, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.2 * rng.randn(n) > 0).astype(float)
    return X, y


_GATE_PARAMS = {
    "objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
    "verbosity": -1, "metric": "none",
    # shrink the cutoffs so CI-sized windows actually partition
    "tpu_wave_sort_cutoff": 256, "tpu_sort_cutoff": 128,
    # partition mode runs without sort-deferral; the baseline must match
    # the row-accumulation order or member hists drift by ulps
    "tpu_wave_defer_sorts": False,
}


def _train_text(X, y, params, iters):
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(iters):
        bst.update()
    return bst.gbdt.save_model_to_string(), bst


def test_partition_record_exact_trees():
    X, y = _gate_data()
    s_sort, _ = _train_text(X, y, dict(_GATE_PARAMS,
                                       tpu_wave_pallas_partition="off"), 2)
    s_part, b = _train_text(X, y, dict(_GATE_PARAMS,
                                       tpu_wave_pallas_partition="on"), 2)
    assert b.gbdt.learner._use_partition
    assert s_sort == s_part


def test_partition_record_exact_with_bagging():
    X, y = _gate_data(seed=9)
    p = dict(_GATE_PARAMS, bagging_fraction=0.8, bagging_freq=1)
    s_sort, _ = _train_text(X, y, dict(p, tpu_wave_pallas_partition="off"),
                            2)
    s_part, _ = _train_text(X, y, dict(p, tpu_wave_pallas_partition="on"),
                            2)
    assert s_sort == s_part


# ---------------------------------------------------------------------------
# Fused split-scan golden parity vs ops/split.py.
# ---------------------------------------------------------------------------


def _dyadic(rng, shape, scale=64.0):
    """Floats of the form k/2^6 with |k| < 2^12 — every partial sum any
    scan order produces is exact in f32."""
    return (rng.randint(-(1 << 12), 1 << 12, size=shape) / scale) \
        .astype(np.float32)


def _scan_case(rng, k=6, f=9, b=32, dyadic=True):
    from lightgbm_tpu.binning import (MISSING_NAN, MISSING_NONE,
                                      MISSING_ZERO)
    gen = (lambda s: _dyadic(rng, s)) if dyadic else \
        (lambda s: rng.randn(*s).astype(np.float32))
    hg = gen((k, f, b))
    hh = np.abs(gen((k, f, b))) + 0.25
    hc = rng.randint(0, 50, size=(k, f, b)).astype(np.float32)
    hist = np.stack([hg, hh, hc], axis=-1)
    num_bin = rng.randint(2, b + 1, size=f).astype(np.int32)
    missing = rng.choice([MISSING_NONE, MISSING_ZERO, MISSING_NAN],
                         size=f).astype(np.int32)
    default_bin = (rng.randint(0, 100, size=f) % num_bin).astype(np.int32)
    # zero out bins past num_bin like real histograms
    bm = np.arange(b)[None, :] < num_bin[:, None]
    hist *= bm[None, :, :, None]
    sum_g = hist[..., 0].sum(axis=(1, 2)) / f
    sum_h = np.abs(hist[..., 1]).sum(axis=(1, 2)) / f
    cnt = hist[..., 2].sum(axis=(1, 2)) / f
    return hist, sum_g, sum_h, cnt, num_bin, missing, default_bin


@pytest.mark.parametrize("dyadic", [True, False])
def test_split_scan_parity(dyadic):
    from lightgbm_tpu.ops.scan_pallas import find_best_splits_batched
    from lightgbm_tpu.ops.split import find_best_splits

    rng = np.random.RandomState(17 if dyadic else 23)
    hist, sg, sh, cn, nb, mt, db = _scan_case(rng, dyadic=dyadic)
    k, f = hist.shape[:2]
    fmask = np.ones(f, bool)
    kw = dict(lambda_l1=0.1 if not dyadic else 0.0, lambda_l2=0.5,
              max_delta_step=0.0, min_data_in_leaf=3,
              min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0)
    got = find_best_splits_batched(
        jnp.asarray(hist), jnp.asarray(sg), jnp.asarray(sh),
        jnp.asarray(cn), jnp.asarray(nb), jnp.asarray(mt),
        jnp.asarray(db), jnp.asarray(fmask), interpret=True, **kw)
    for i in range(k):
        want = find_best_splits(
            jnp.asarray(hist[i]), jnp.asarray(sg[i]), jnp.asarray(sh[i]),
            jnp.asarray(cn[i]), jnp.asarray(nb), jnp.asarray(mt),
            jnp.asarray(db), jnp.asarray(fmask), **kw)
        gw = np.asarray(want.gain)
        gg = np.asarray(got.gain)[i]
        if dyadic:
            # dyadic inputs make every SUM exact in any order, so the
            # thresholds, child aggregates and leaf outputs below are
            # bitwise.  The gain is not a sum: g^2/(h+l2) terms round,
            # and XLA:CPU contracts mul+add into FMA differently in the
            # two separately compiled programs (kernel body vs
            # find_best_splits) — 1 ulp either way on this JAX
            np.testing.assert_array_max_ulp(gw, gg, maxulp=1)
            assert np.array_equal(np.asarray(want.threshold),
                                  np.asarray(got.threshold)[i]), i
            assert np.array_equal(np.asarray(want.default_left),
                                  np.asarray(got.default_left)[i]), i
            for fld in ("left_sum_g", "left_sum_h", "left_cnt",
                        "right_sum_g", "right_sum_h", "right_cnt",
                        "left_output", "right_output"):
                assert np.array_equal(np.asarray(getattr(want, fld)),
                                      np.asarray(getattr(got, fld))[i]), \
                    (i, fld)
        else:
            both = np.isneginf(gw) == np.isneginf(gg)
            assert both.all(), i
            fin = ~np.isneginf(gw)
            np.testing.assert_allclose(gw[fin], gg[fin], rtol=2e-5,
                                       atol=2e-5)


def test_split_scan_trains_same_structure():
    """End-to-end: scan-on trees pick the same split features (values may
    drift by summation-order ulps off-TPU, where the XLA reference path
    is the sequential cumsum rather than the triangular dot)."""
    import re
    X, y = _gate_data(seed=21)
    p = dict(_GATE_PARAMS)
    del p["tpu_wave_defer_sorts"]
    s_off, _ = _train_text(X, y, dict(p, tpu_wave_pallas_scan="off"), 2)
    s_on, b = _train_text(X, y, dict(p, tpu_wave_pallas_scan="on"), 2)
    assert b.gbdt.learner._use_scan
    assert re.findall(r"split_feature=[^\n]*", s_off) == \
        re.findall(r"split_feature=[^\n]*", s_on)


# ---------------------------------------------------------------------------
# Vectorized host assembly parity + rolling-flush parity.
# ---------------------------------------------------------------------------


def test_vec_assemble_and_flush_depth_parity():
    X, y = _gate_data(n=2048, f=9, seed=11)
    p = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
         "verbosity": -1, "metric": "none", "bagging_fraction": 0.7,
         "bagging_freq": 1, "max_depth": 7}
    texts = []
    boosters = []
    for variant in (dict(tpu_vec_assemble=False),
                    dict(tpu_vec_assemble=True),
                    dict(tpu_pipeline_flush_depth=0),
                    dict(tpu_pipeline_flush_depth=2)):
        s, b = _train_text(X, y, dict(p, **variant), 5)
        texts.append(s)
        boosters.append(b)
    assert len(set(texts)) == 1
    # leaf-index predictions exercise child links and depths
    p0 = boosters[0].gbdt.predict(X[:200], pred_leaf=True)
    p1 = boosters[1].gbdt.predict(X[:200], pred_leaf=True)
    assert np.array_equal(p0, p1)


def test_stall_fuse_top_record_exact():
    """The one-masked-pass replay correction (fused top) must reproduce
    the two-stage flow exactly; the workload is sized so real stalls
    occur (telemetry counters assert that)."""
    X, y = _gate_data(n=4096, f=10, seed=13)
    p = {"objective": "binary", "num_leaves": 63, "min_data_in_leaf": 5,
         "verbosity": -1, "metric": "none", "tpu_wave_sort_cutoff": 256,
         "tpu_sort_cutoff": 128, "tpu_wave_width": 8, "telemetry": True}
    s_two, b_two = _train_text(X, y,
                               dict(p, tpu_wave_stall_fuse_top=False), 3)
    s_one, _ = _train_text(X, y, dict(p, tpu_wave_stall_fuse_top=True), 3)
    counters = b_two.gbdt.get_telemetry().get("counters", {})
    assert counters.get("stall_splits", 0) > 0, \
        "workload produced no replay stalls — the fused path was idle"
    assert s_two == s_one


def test_stall_batch_auto_resolves():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner_wave import _resolve_stall_batch
    assert _resolve_stall_batch(Config.from_params({})) == 4
    assert _resolve_stall_batch(
        Config.from_params({"tpu_wave_stall_batch": 1})) == 1
    assert _resolve_stall_batch(
        Config.from_params({"tpu_wave_stall_batch": 99})) == 16
