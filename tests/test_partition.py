"""Split-scan kernel parity, and the record-exactness of the host assembly,
the rolling flush and the fused replay correction (round 6).

The fused split-scan must match ``find_best_splits`` — exactly on dyadic
inputs (where every summation order is lossless), to summation-order ulps
on arbitrary f32.  Off-TPU the kernel runs in Pallas interpret mode.  (The
partition's own exactness is ``tests/test_wave.py``'s ``growth_sort`` tests.)
"""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb


def _gate_data(n=2048, f=10, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.2 * rng.randn(n) > 0).astype(float)
    return X, y


_GATE_PARAMS = {
    "objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
    "verbosity": -1, "metric": "none",
    # shrink the cutoffs so CI-sized windows actually sort
    "tpu_wave_sort_cutoff": 256, "tpu_sort_cutoff": 128,
}


def _train_text(X, y, params, iters):
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(iters):
        bst.update()
    return bst.gbdt.save_model_to_string(), bst


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
