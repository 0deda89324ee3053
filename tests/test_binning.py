"""BinMapper unit tests — bin-boundary semantics are the root of numeric
parity (reference `src/io/bin.cpp:72-420`)."""

import numpy as np
import pytest

from lightgbm_tpu.binning import (BIN_CATEGORICAL, MISSING_NAN, MISSING_NONE,
                                  MISSING_ZERO, BinMapper, greedy_find_bin)

pytestmark = pytest.mark.fast


def _fit(values, total=None, max_bin=255, min_data_in_bin=3, min_split=20,
         **kw):
    m = BinMapper()
    values = np.asarray(values, dtype=np.float64)
    m.find_bin(values, total_sample_cnt=total or len(values), max_bin=max_bin,
               min_data_in_bin=min_data_in_bin, min_split_data=min_split, **kw)
    return m


def test_distinct_values_fit_in_bins():
    vals = np.repeat([1.0, 2.0, 3.0, 4.0], 25)
    m = _fit(vals, min_data_in_bin=1, min_split=1)
    assert m.num_bin >= 4
    assert m.value_to_bin(1.0) != m.value_to_bin(2.0)
    assert m.value_to_bin(3.9) == m.value_to_bin(4.0)
    assert m.value_to_bin(3.4) == m.value_to_bin(3.0)
    # upper bound of last bin is +inf
    assert np.isinf(m.bin_upper_bound[-1])


def test_zero_gets_own_bin():
    # FindBinWithZeroAsOneBin: (-1e-35, 1e-35] is a dedicated bin
    vals = np.concatenate([np.zeros(50), np.linspace(-5, 5, 50)])
    m = _fit(vals, min_data_in_bin=1, min_split=1)
    zb = m.value_to_bin(0.0)
    assert m.value_to_bin(1e-40) == zb
    assert m.value_to_bin(0.1) != zb
    assert m.value_to_bin(-0.1) != zb
    assert m.default_bin == zb


def test_missing_nan_reserves_last_bin():
    vals = np.array([0, 1, 2, 3, 4, 5, 6, 7, np.nan])
    m = _fit(vals, min_data_in_bin=1, min_split=1)
    assert m.missing_type == MISSING_NAN
    assert m.value_to_bin(np.nan) == m.num_bin - 1
    # non-nan values don't land in the nan bin
    for v in range(8):
        assert m.value_to_bin(v) < m.num_bin - 1


def test_use_missing_false():
    vals = np.array([0, 1, 2, np.nan])
    m = _fit(vals, min_data_in_bin=1, min_split=1, use_missing=False)
    assert m.missing_type == MISSING_NONE
    # NaN folds to zero bin
    assert m.value_to_bin(np.nan) == m.value_to_bin(0.0)


def test_zero_as_missing():
    vals = np.array([0, 0, 1, 2, 3, 4.0])
    m = _fit(vals, min_data_in_bin=1, min_split=1, zero_as_missing=True)
    assert m.missing_type == MISSING_ZERO


def test_trivial_feature():
    m = _fit(np.full(100, 3.14), min_split=20)
    assert m.is_trivial


def test_values_to_bins_vectorized_matches_scalar():
    rng = np.random.RandomState(0)
    vals = np.concatenate([rng.randn(500), [np.nan] * 10, np.zeros(30)])
    m = _fit(vals, min_data_in_bin=1, min_split=1)
    vec = m.values_to_bins(vals)
    scalar = np.array([m.value_to_bin(v) for v in vals])
    np.testing.assert_array_equal(vec, scalar)


def test_categorical_count_sorted():
    vals = np.concatenate([np.full(50, 2.0), np.full(30, 0.0), np.full(20, 7.0)])
    m = _fit(vals, min_data_in_bin=1, min_split=1, bin_type=BIN_CATEGORICAL)
    # most frequent category first, except bin 0 never holds category 0
    assert m.bin_2_categorical[0] == 2
    assert m.value_to_bin(2) == 0
    assert m.value_to_bin(999) == m.num_bin - 1  # unseen -> last bin


def test_greedy_find_bin_min_data():
    dv = np.arange(10, dtype=np.float64)
    ct = np.full(10, 5)
    bounds = greedy_find_bin(dv, ct, max_bin=255, total_cnt=50,
                             min_data_in_bin=10)
    # every bin must hold >= 10 samples -> at most 5 bounds
    assert len(bounds) <= 6


def test_serialization_roundtrip():
    vals = np.concatenate([np.random.RandomState(1).randn(200), [np.nan] * 5])
    m = _fit(vals, min_data_in_bin=1, min_split=1)
    m2 = BinMapper.from_dict(m.to_dict())
    assert m2.num_bin == m.num_bin
    np.testing.assert_array_equal(m2.bin_upper_bound, m.bin_upper_bound)
    assert m2.value_to_bin(0.5) == m.value_to_bin(0.5)


def _distinct_loop(values, zero_cnt):
    """`src/io/bin.cpp:236-270` value by value: what ``distinct_with_zero``
    does in array passes."""
    values = np.sort(values, kind="stable")
    dv, ct = [], []
    if len(values) == 0 or (values[0] > 0.0 and zero_cnt > 0):
        dv.append(0.0)
        ct.append(zero_cnt)
    if len(values) > 0:
        dv.append(float(values[0]))
        ct.append(1)
    for prev, cur in zip(values[:-1], values[1:]):
        if cur <= np.nextafter(prev, np.inf):
            dv[-1] = float(cur)
            ct[-1] += 1
            continue
        if prev < 0.0 and cur > 0.0:
            dv.append(0.0)
            ct.append(zero_cnt)
        dv.append(float(cur))
        ct.append(1)
    if len(values) > 0 and values[-1] < 0.0 and zero_cnt > 0:
        dv.append(0.0)
        ct.append(zero_cnt)
    return dv, ct


def _column(kind, n=3000):
    rng = np.random.RandomState(7)
    x = rng.randn(n)
    if kind == "both_signs":
        return x
    if kind == "positive":
        return np.abs(x) + 8.0
    if kind == "negative":
        return -np.abs(x) - 0.5
    if kind == "ties":
        return np.round(x, 1)
    if kind == "one_ulp_apart":
        return np.concatenate([x, np.nextafter(x, np.inf),
                               np.nextafter(np.nextafter(x, np.inf), np.inf)])
    if kind == "empty":
        return x[:0]
    raise ValueError(kind)


@pytest.mark.parametrize("zero_cnt", [0, 41])
@pytest.mark.parametrize("kind", ["both_signs", "positive", "negative", "ties",
                                  "one_ulp_apart", "empty"])
def test_distinct_values_match_the_reference_loop(kind, zero_cnt):
    from lightgbm_tpu.binning import distinct_with_zero

    col = _column(kind)
    col = col[col != 0.0]
    dv, ct = distinct_with_zero(col, zero_cnt)
    want_dv, want_ct = _distinct_loop(col, zero_cnt)
    assert dv.tolist() == want_dv
    assert ct.tolist() == want_ct


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_float32_rows_bin_as_their_float64_copy(dtype, monkeypatch):
    """A float32 matrix is never widened whole (``_float_matrix``): blocks of
    it are, inside ``_bin_all``, to the same mappers and the same bins."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import dataset

    rng = np.random.RandomState(3)
    X = rng.randn(5000, 6).astype(np.float32)
    X[rng.rand(*X.shape) < 0.05] = np.nan
    X[rng.rand(*X.shape) < 0.2] = 0.0
    y = (rng.rand(5000) < 0.5).astype(np.float32)
    handed = []
    inner = dataset._ConstructedDataset._bin_all
    monkeypatch.setattr(dataset._ConstructedDataset, "_bin_all",
                        lambda self, mat, *a, **k: (handed.append(mat),
                                                    inner(self, mat, *a, **k))[1])
    params = {"objective": "binary", "verbosity": -1, "min_data_in_bin": 1}
    got = lgb.Dataset(X.astype(dtype), label=y, params=params).construct()
    assert handed[0].dtype == dtype
    want = lgb.Dataset(X.astype(np.float64), label=y, params=params).construct()
    np.testing.assert_array_equal(got._constructed.bins, want._constructed.bins)
    for a, b in zip(got._constructed.bin_mappers, want._constructed.bin_mappers):
        np.testing.assert_array_equal(a.bin_upper_bound, b.bin_upper_bound)


@pytest.mark.parametrize("rows", [0, 1, 1000, (1 << 18) + 77])
def test_host_packing_equals_the_device_packing(rows):
    """``pack_bin_words_host`` writes byte s of word k in cache-sized blocks of
    rows, from a strided view as ``sharded_bins`` hands one over."""
    from lightgbm_tpu.ops.hist_pallas import pack_bin_words, pack_bin_words_host

    table = np.random.RandomState(rows % 97).randint(
        0, 256, (16, 2 * rows + 5)).astype(np.uint8)
    view = table[4:12, rows:2 * rows]
    got = pack_bin_words_host(view)
    assert got.dtype == np.int32 and got.shape == (2, rows)
    np.testing.assert_array_equal(got, np.asarray(pack_bin_words(view)))
