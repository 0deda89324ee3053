"""End-to-end training tests — the port of the reference's test strategy
(`tests/python_package_test/test_engine.py`): train real models, assert
metric thresholds and exact predictions on crafted data."""

import pickle

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _binary_data(rng, n=600, f=8):
    X = rng.randn(n, f)
    logit = X[:, 0] * 1.2 + X[:, 1] * 0.7 - 0.3 * X[:, 2]
    y = (logit + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def test_binary(rng):
    """reference test_engine.py:29 — asserts final logloss threshold."""
    X, y = _binary_data(rng)
    ds = lgb.Dataset(X[:500], label=y[:500], params={"min_data_in_leaf": 5})
    dv = ds.create_valid(X[500:], label=y[500:])
    evals = {}
    lgb.train({"objective": "binary", "metric": "binary_logloss",
               "num_leaves": 15, "min_data_in_leaf": 5, "verbosity": -1},
              ds, 50, valid_sets=[dv], evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["binary_logloss"][-1] < 0.4


def test_regression(rng):
    """reference test_engine.py:76 — asserts MSE threshold."""
    X = rng.randn(600, 6)
    y = X[:, 0] * 3 + X[:, 1] ** 2 + 0.1 * rng.randn(600)
    ds = lgb.Dataset(X[:500], label=y[:500], params={"min_data_in_leaf": 5})
    dv = ds.create_valid(X[500:], label=y[500:])
    evals = {}
    lgb.train({"objective": "regression", "metric": "l2", "num_leaves": 31,
               "min_data_in_leaf": 5, "verbosity": -1},
              ds, 80, valid_sets=[dv], evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["l2"][-1] < 0.6


def test_missing_value_handle(rng):
    """reference test_engine.py:95 — label determined solely by NaN-ness."""
    X = np.zeros((1000, 1))
    y = np.zeros(1000)
    trues = rng.choice(1000, 200, replace=False)
    X[trues, 0] = np.nan
    y[trues] = 1
    ds = lgb.Dataset(X, label=y)
    dv = ds.create_valid(X, label=y)
    evals = {}
    bst = lgb.train({"metric": "l2", "verbosity": -1,
                     "boost_from_average": False, "objective": "regression"},
                    ds, 20, valid_sets=[dv], evals_result=evals,
                    verbose_eval=False)
    pred = bst.predict(X)
    mse = float(np.mean((pred - y) ** 2))
    assert mse < 0.005
    assert abs(evals["valid_0"]["l2"][-1] - mse) < 1e-5


def test_missing_value_handle_na():
    """reference test_engine.py:120 — exact predictions, NaN default dir."""
    x = [0, 1, 2, 3, 4, 5, 6, 7, np.nan]
    y = [1, 1, 1, 1, 0, 0, 0, 0, 1]
    X = np.array(x).reshape(-1, 1)
    ds = lgb.Dataset(X, label=y)
    dv = ds.create_valid(X, label=y)
    evals = {}
    bst = lgb.train({"objective": "regression", "metric": "auc",
                     "verbosity": -1, "boost_from_average": False,
                     "min_data": 1, "num_leaves": 2, "learning_rate": 1,
                     "min_data_in_bin": 1, "zero_as_missing": False},
                    ds, 1, valid_sets=[dv], evals_result=evals,
                    verbose_eval=False)
    pred = bst.predict(X)
    np.testing.assert_almost_equal(pred, y)
    assert evals["valid_0"]["auc"][-1] > 0.999


def test_missing_value_handle_zero():
    """reference test_engine.py:152 — zero_as_missing exact predictions."""
    x = [0, 1, 2, 3, 4, 5, 6, 7, np.nan]
    y = [0, 1, 1, 1, 0, 0, 0, 0, 0]
    X = np.array(x).reshape(-1, 1)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "regression", "metric": "auc",
                     "verbosity": -1, "boost_from_average": False,
                     "min_data": 1, "num_leaves": 2, "learning_rate": 1,
                     "min_data_in_bin": 1, "zero_as_missing": True},
                    ds, 1, verbose_eval=False)
    pred = bst.predict(X)
    np.testing.assert_almost_equal(pred, y)


def test_missing_value_handle_none():
    """reference test_engine.py:184 — use_missing=False folds NaN to zero."""
    x = [0, 1, 2, 3, 4, 5, 6, 7, np.nan]
    y = [0, 1, 1, 1, 0, 0, 0, 0, 0]
    X = np.array(x).reshape(-1, 1)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "regression", "metric": "auc",
                     "verbosity": -1, "boost_from_average": False,
                     "min_data": 1, "num_leaves": 2, "learning_rate": 1,
                     "min_data_in_bin": 1, "use_missing": False},
                    ds, 1, verbose_eval=False)
    pred = bst.predict(X)
    assert abs(pred[0] - pred[1]) < 1e-5   # 0 and 1 share the zero-ish side
    assert abs(pred[-1] - pred[0]) < 1e-5  # NaN folds to the zero bin


def test_multiclass(rng):
    """reference test_engine.py:291."""
    X = rng.randn(600, 6)
    y = np.argmax(X[:, :3] + 0.3 * rng.randn(600, 3), axis=1).astype(float)
    ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 5})
    dv = ds.create_valid(X, label=y)
    evals = {}
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "metric": "multi_logloss", "num_leaves": 15,
                     "min_data_in_leaf": 5, "verbosity": -1},
                    ds, 30, valid_sets=[dv], evals_result=evals,
                    verbose_eval=False)
    assert evals["valid_0"]["multi_logloss"][-1] < 0.35
    pred = bst.predict(X)
    assert pred.shape == (600, 3)
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, rtol=1e-5)
    assert (np.argmax(pred, 1) == y).mean() > 0.9


def test_early_stopping(rng):
    """reference test_engine.py:365."""
    X, y = _binary_data(rng)
    ds = lgb.Dataset(X[:400], label=y[:400], params={"min_data_in_leaf": 5})
    dv = ds.create_valid(X[400:], label=y[400:])
    bst = lgb.train({"objective": "binary", "metric": "binary_logloss",
                     "num_leaves": 31, "min_data_in_leaf": 5, "verbosity": -1},
                    ds, 200, valid_sets=[dv],
                    early_stopping_rounds=5, verbose_eval=False)
    assert bst.best_iteration > 0
    assert bst.best_iteration < 200


def test_continue_train(rng):
    """reference test_engine.py:396 — init_model from file and in-memory."""
    X, y = _binary_data(rng)
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbosity": -1}
    ds1 = lgb.Dataset(X, label=y, params=p)
    bst1 = lgb.train(p, ds1, 10, verbose_eval=False)
    pred1 = bst1.predict(X, raw_score=True)
    bst1.save_model("/tmp/lgbtpu_cont.txt")
    ds2 = lgb.Dataset(X, label=y, params=p)
    bst2 = lgb.train(p, ds2, 10, init_model="/tmp/lgbtpu_cont.txt",
                     verbose_eval=False)
    assert bst2.num_trees() == 20
    # continued model must start from the saved model's predictions
    pred2 = bst2.predict(X, raw_score=True)
    corr = np.corrcoef(pred1, pred2)[0, 1]
    assert corr > 0.9


def test_cv(rng):
    """reference test_engine.py:448."""
    X, y = _binary_data(rng)
    ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 5})
    res = lgb.cv({"objective": "binary", "metric": "binary_logloss",
                  "num_leaves": 7, "min_data_in_leaf": 5, "verbosity": -1},
                 ds, num_boost_round=8, nfold=3, verbose_eval=False)
    assert len(res["binary_logloss-mean"]) == 8
    assert res["binary_logloss-mean"][-1] < res["binary_logloss-mean"][0]


def test_pickling(rng):
    """reference test_engine.py:511."""
    X, y = _binary_data(rng, n=300)
    ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 5})
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "min_data_in_leaf": 5, "verbosity": -1}, ds, 5,
                    verbose_eval=False)
    blob = pickle.dumps(bst)
    bst2 = pickle.loads(blob)
    np.testing.assert_allclose(bst.predict(X), bst2.predict(X), rtol=1e-9)


def test_model_save_load_roundtrip(rng):
    X, y = _binary_data(rng, n=300)
    ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 5})
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "min_data_in_leaf": 5, "verbosity": -1}, ds, 5,
                    verbose_eval=False)
    s = bst.model_to_string()
    bst2 = lgb.Booster(model_str=s)
    np.testing.assert_allclose(bst.predict(X), bst2.predict(X), rtol=1e-12)
    # round-trips through text format identically
    assert bst2.model_to_string() == s


def test_custom_objective(rng):
    """custom fobj path (`basic.py:1890` __boost)."""
    X, y = _binary_data(rng, n=400)
    ds = lgb.Dataset(X, label=y, params={"min_data_in_leaf": 5})

    def logloss_obj(preds, dataset):
        labels = ds.get_label()
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - labels, p * (1 - p)

    bst = lgb.train({"num_leaves": 7, "min_data_in_leaf": 5,
                     "verbosity": -1, "objective": "none"},
                    ds, 15, fobj=logloss_obj, verbose_eval=False)
    pred = bst.predict(X)  # raw scores (no objective)
    acc = ((pred > 0) == y).mean()
    assert acc > 0.9


def test_weights_change_model(rng):
    X, y = _binary_data(rng, n=400)
    w = np.where(y > 0, 10.0, 1.0)
    p = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbosity": -1}
    b1 = lgb.train(p, lgb.Dataset(X, label=y, params=p), 5, verbose_eval=False)
    b2 = lgb.train(p, lgb.Dataset(X, label=y, weight=w, params=p), 5,
                   verbose_eval=False)
    assert not np.allclose(b1.predict(X), b2.predict(X))


def test_bagging_and_feature_fraction(rng):
    X, y = _binary_data(rng)
    p = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "bagging_fraction": 0.8, "bagging_freq": 2, "feature_fraction": 0.7,
         "verbosity": -1, "metric": "binary_logloss"}
    ds = lgb.Dataset(X, label=y, params=p)
    evals = {}
    lgb.train(p, ds, 30, valid_sets=[ds.create_valid(X, label=y)],
              evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["binary_logloss"][-1] < 0.3


def test_dart(rng):
    """reference test_engine.py:735."""
    X, y = _binary_data(rng, n=400)
    p = {"objective": "binary", "boosting": "dart", "num_leaves": 15,
         "min_data_in_leaf": 5, "verbosity": -1, "metric": "binary_logloss"}
    ds = lgb.Dataset(X, label=y, params=p)
    evals = {}
    bst = lgb.train(p, ds, 20, valid_sets=[ds.create_valid(X, label=y)],
                    evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["binary_logloss"][-1] < 0.4


def test_goss(rng):
    X, y = _binary_data(rng)
    p = {"objective": "binary", "boosting": "goss", "num_leaves": 15,
         "min_data_in_leaf": 5, "verbosity": -1, "metric": "binary_logloss",
         "learning_rate": 0.2}
    ds = lgb.Dataset(X, label=y, params=p)
    evals = {}
    lgb.train(p, ds, 20, valid_sets=[ds.create_valid(X, label=y)],
              evals_result=evals, verbose_eval=False)
    assert evals["valid_0"]["binary_logloss"][-1] < 0.35


def test_rf(rng):
    """reference test_engine.py:752."""
    X, y = _binary_data(rng)
    p = {"objective": "binary", "boosting": "rf", "num_leaves": 15,
         "min_data_in_leaf": 5, "bagging_fraction": 0.7, "bagging_freq": 1,
         "feature_fraction": 0.8, "verbosity": -1, "metric": "binary_logloss"}
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, 10, verbose_eval=False)
    pred = bst.predict(X)
    assert ((pred > 0.5) == y).mean() > 0.85


def test_constant_features(rng):
    """reference test_engine.py:769 — all-constant features yield the
    boost_from_average constant model."""
    X = np.full((100, 3), 7.0)
    y = np.concatenate([np.ones(70), np.zeros(30)])
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "verbosity": -1,
                     "min_data_in_leaf": 1}, ds, 2, verbose_eval=False)
    pred = bst.predict(X)
    np.testing.assert_allclose(pred, 0.7, atol=1e-6)


def test_lambdarank(rng):
    """reference lambdarank example shape: queries with graded labels."""
    nq, per = 30, 12
    n = nq * per
    X = rng.randn(n, 5)
    rel = X[:, 0] * 1.5 + rng.randn(n) * 0.3
    y = np.digitize(rel, np.percentile(rel, [50, 75, 90])).astype(float)
    group = np.full(nq, per)
    p = {"objective": "lambdarank", "metric": "ndcg", "eval_at": [3],
         "num_leaves": 7, "min_data_in_leaf": 2, "verbosity": -1,
         "min_sum_hessian_in_leaf": 1e-3}
    ds = lgb.Dataset(X, label=y, group=group, params=p)
    evals = {}
    bst = lgb.train(p, ds, 20, valid_sets=[
        ds.create_valid(X, label=y, group=group)], evals_result=evals,
        verbose_eval=False)
    ndcg = evals["valid_0"]["ndcg@3"]
    assert ndcg[-1] > 0.75
    assert ndcg[-1] >= ndcg[0] - 0.05


def test_objectives_smoke(rng):
    """objective×metric matrix (reference test_engine.py:841 test_metrics)."""
    X = rng.randn(300, 5)
    y_reg = np.abs(X[:, 0] * 2 + rng.randn(300) * 0.1) + 1.0
    for obj, metric in [("regression_l1", "l1"), ("huber", "huber"),
                        ("fair", "fair"), ("poisson", "poisson"),
                        ("quantile", "quantile"), ("mape", "mape"),
                        ("gamma", "gamma"), ("tweedie", "tweedie")]:
        ds = lgb.Dataset(X, label=y_reg, params={"min_data_in_leaf": 5})
        evals = {}
        lgb.train({"objective": obj, "metric": metric, "num_leaves": 7,
                   "min_data_in_leaf": 5, "verbosity": -1}, ds, 5,
                  valid_sets=[ds.create_valid(X, label=y_reg)],
                  evals_result=evals, verbose_eval=False)
        key = list(evals["valid_0"].keys())[0]
        vals = evals["valid_0"][key]
        assert np.isfinite(vals).all(), obj
    y_bin = (X[:, 0] > 0).astype(float)
    for obj in ["cross_entropy", "cross_entropy_lambda"]:
        ds = lgb.Dataset(X, label=y_bin, params={"min_data_in_leaf": 5})
        bst = lgb.train({"objective": obj, "num_leaves": 7,
                         "min_data_in_leaf": 5, "verbosity": -1}, ds, 5,
                        verbose_eval=False)
        assert np.isfinite(bst.predict(X)).all(), obj


def test_cv_early_stopping_aggregated(rng):
    """cv() runs folds in lockstep and stops on the AGGREGATED mean
    (reference cv + _agg_cv_result semantics), truncating at the best
    aggregated iteration."""
    import lightgbm_tpu as lgb
    X = rng.randn(600, 5)
    y = X[:, 0] * 2 + rng.randn(600) * 2.0   # noisy: early stopping bites
    res = lgb.cv({"objective": "regression", "num_leaves": 7,
                  "verbosity": -1, "min_data_in_leaf": 10,
                  "learning_rate": 0.3, "metric": "l2"},
                 lgb.Dataset(X, label=y), num_boost_round=200,
                 nfold=3, early_stopping_rounds=5, stratified=False,
                 seed=7)
    means = res["l2-mean"]
    assert 0 < len(means) < 200, "early stopping never triggered"
    # truncated AT the aggregated best (last entry is the minimum)
    assert means[-1] == min(means)
    assert len(res["l2-stdv"]) == len(means)


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """`use_compile_cache`: JAX_COMPILATION_CACHE_DIR decides when set (no
    directory is set in code); otherwise the fixed <checkout>/.jax_tpu_cache
    — never a temporary name."""
    import os
    import jax
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert lgb.use_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(lgb.__file__))), ".jax_tpu_cache")
        assert lgb.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert lgb.use_compile_cache() == want          # fixed, idempotent
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
