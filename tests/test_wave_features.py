"""The wave learner's feature suite against the sequential compact learner,
on both of ``test_wave._pair``'s paths: ``reference`` (the bit-exact base)
and ``shipped`` (no ``tpu_wave_*`` / ``tpu_sort_cutoff`` override: the
combination every benchmark cell runs).  A file of its own so that the
suite's last file, ``test_wave.py``, does not grow by it.
"""

import numpy as np
import pytest

from test_wave import _make, _models_equal, _pair

PATHS = ["reference", "shipped"]


@pytest.mark.parametrize("path", PATHS)
def test_wave_bagging_feature_fraction(path):
    X, y = _make()
    pa, pb = _pair(path, bagging_fraction=0.6, bagging_freq=1,
                   feature_fraction=0.7, seed=7)
    _models_equal(pa, pb, X, y, exact=path == "reference")


@pytest.mark.parametrize("path", PATHS)
def test_wave_regression_l1_and_leaf_partition(path):
    # regression_l1 renews leaf outputs through the learner's leaf_id
    # partition — exercises the wave learner's speculative-leaf remap
    rng = np.random.RandomState(5)
    X = rng.randn(8000, 8)
    y = X[:, 0] * 2 + np.abs(X[:, 1]) + 0.1 * rng.randn(8000)
    pa, pb = _pair(path, objective="regression_l1", num_leaves=63)
    _models_equal(pa, pb, X, y, exact=path == "reference")


@pytest.mark.parametrize("path", PATHS)
def test_wave_monotone(path):
    rng = np.random.RandomState(11)
    X = rng.randn(6000, 5)
    y = 2 * X[:, 0] - X[:, 1] + 0.2 * rng.randn(6000)
    pa, pb = _pair(path, objective="regression",
                   monotone_constraints=[1, -1, 0, 0, 0])
    _models_equal(pa, pb, X, y, exact=path == "reference")


@pytest.mark.parametrize("path", PATHS)
def test_wave_categorical(path):
    rng = np.random.RandomState(13)
    n = 12000
    Xn = rng.randn(n, 3)
    c1 = rng.randint(0, 12, n)
    c2 = rng.randint(0, 40, n)
    X = np.column_stack([Xn, c1, c2])
    y = ((c1 % 3 == 0).astype(float) * 1.5 + Xn[:, 0]
         + (c2 > 20) + 0.3 * rng.randn(n) > 1).astype(float)
    pa, pb = _pair(path, max_cat_to_onehot=8)
    _models_equal(pa, pb, X, y, categorical_feature=[3, 4],
                  exact=path == "reference")


@pytest.mark.parametrize("path", PATHS)
def test_wave_efb_bundles(path):
    rng = np.random.RandomState(17)
    n = 10000
    dense = rng.randn(n, 2)
    # mutually exclusive sparse block -> bundled by EFB
    sparse = np.zeros((n, 6))
    which = rng.randint(0, 6, n)
    rows = np.arange(n)
    sparse[rows, which] = rng.rand(n)
    sparse[rng.rand(n) < 0.5, :] = 0.0
    X = np.column_stack([dense, sparse])
    y = (dense[:, 0] + sparse.sum(1) + 0.2 * rng.randn(n) > 0.5).astype(float)
    pa, pb = _pair(path, enable_bundle=True)
    # shipped: tree 1, node 1 splits at bin 29 (compact) or 30 (wave)
    a, b = _models_equal(pa, pb, X, y, exact=path == "reference",
                         thresholds=False)
    assert b.gbdt.learner._bundle is not None  # EFB actually active


@pytest.mark.parametrize("path", PATHS)
def test_wave_multiclass(path):
    rng = np.random.RandomState(19)
    X = rng.randn(9000, 6)
    y = (X[:, 0] + X[:, 1] > 0).astype(int) + (X[:, 2] > 0.5).astype(int)
    pa, pb = _pair(path, objective="multiclass", num_class=3, num_leaves=15)
    _models_equal(pa, pb, X, y, rounds=3, exact=path == "reference")


@pytest.mark.parametrize("path", PATHS)
def test_wave_goss_dart(path):
    X, y = _make(12000)
    for boosting in ("goss", "dart"):
        pa, pb = _pair(path, boosting=boosting, seed=3)
        # shipped: tree 2's root splits at bin 30 (compact) or 29 (wave)
        _models_equal(pa, pb, X, y, rounds=4, exact=path == "reference",
                      thresholds=False)


@pytest.mark.parametrize("path", PATHS)
def test_wave_exhausts_splits_early(path):
    # more leaves than splittable data: growth stops on no positive gain
    rng = np.random.RandomState(23)
    X = rng.randn(400, 4)
    y = (X[:, 0] > 0).astype(float)
    pa, pb = _pair(path, num_leaves=255, min_data_in_leaf=30)
    a, b = _models_equal(pa, pb, X, y, rounds=3, exact=path == "reference")
    assert a.gbdt._models[0].num_leaves < 255


@pytest.mark.parametrize("path", PATHS)
def test_wave_tiny_num_leaves(path):
    X, y = _make(4000)
    pa, pb = _pair(path, num_leaves=2)
    _models_equal(pa, pb, X, y, rounds=3, exact=path == "reference")


@pytest.mark.parametrize("path", PATHS)
def test_wave_max_depth(path):
    X, y = _make(10000)
    pa, pb = _pair(path, max_depth=4, num_leaves=63)
    _models_equal(pa, pb, X, y, exact=path == "reference")
