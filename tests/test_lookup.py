"""Per-row small-table lookups (`lightgbm_tpu/ops/lookup.py`): each one-hot
contraction equals ``table[idx]``, the f32 ones bit for bit, and the score
update's helper picks its path by the backend alone.

The contractions are called directly, so they run on the CPU here; what the
chip's compiler makes of the score update at 10,500,096 rows is in
``tests/test_chip_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import lookup

FLT_MAX = np.finfo(np.float32).max
# bit patterns a sum of products would lose: signed zero, denormals, the
# largest finite values, and neighbours one ulp apart
_SPECIALS = np.array(
    [-0.0, 0.0, -1.5, 1e-45, -1e-45, 1.1754942e-38, -3e-39, FLT_MAX, -FLT_MAX,
     np.nextafter(np.float32(FLT_MAX), np.float32(0)), 1.0,
     np.nextafter(np.float32(1), np.float32(2)), -0.1, 3.4e38, np.inf,
     -np.inf], dtype=np.float32)


def _f32_table(rng, m):
    t = (rng.randn(m) * 10.0 ** rng.randint(-30, 30, m)).astype(np.float32)
    k = min(m, len(_SPECIALS))
    t[rng.permutation(m)[:k]] = _SPECIALS[:k]
    return t


def _bits(a):
    return np.asarray(a).view(np.int32)


# 20,512 = 2^5 x 641: the form of the benchmark's 10,500,096 = 2^11 x 5,127
# padded rows, a multiple of no chunk below
@pytest.mark.parametrize("m", [2, 255, 256, 257])
@pytest.mark.parametrize("kind,n,chunk", [
    ("f32", 20512, 1024),      # 21 steps, the last one overlapping
    ("f32", 20512, 3000),      # a chunk that is no multiple of 128
    ("f32", 4096, 1024),       # whole chunks
    ("f32", 1025, 1024),       # the second chunk re-reads 1,023 rows
    ("f32", 777, 1024),        # fewer rows than one chunk: no loop
    ("f32", 164096, None),     # 2^8 x 641 rows: the default chunk, two steps
    ("int", 2053, None),
    ("rows_f32", 2053, None),
])
def test_contraction_equals_gather(rng, kind, m, n, chunk):
    idx = rng.randint(0, m, n).astype(np.int32)
    idx[:2] = (0, m - 1)
    if kind == "f32":
        table = _f32_table(rng, m)
        got = lookup.lookup_f32(jnp.asarray(table), jnp.asarray(idx), chunk)
        assert np.array_equal(_bits(got), _bits(table[idx]))
    elif kind == "int":
        # |values| < 2^24, the contract's range, both ends included
        table = rng.randint(-(1 << 24) + 1, 1 << 24, m).astype(np.int32)
        table[:2] = ((1 << 24) - 1, -(1 << 24) + 1)
        got = lookup.lookup_int(jnp.asarray(table), jnp.asarray(idx))
        assert got.dtype == jnp.int32
        assert np.array_equal(np.asarray(got), table[idx])
    else:
        table = rng.randn(m, 3).astype(np.float32)
        got = lookup.lookup_rows_f32(jnp.asarray(table), jnp.asarray(idx))
        assert got.shape == (n, 3)
        assert np.array_equal(np.asarray(got), table[idx])


def _primitives(table, idx):
    from test_phases import _iter_eqns

    # a new function each time: tracing is cached by the function's identity
    jx = jax.make_jaxpr(lambda t, i: lookup.lookup_leaf_values(t, i))(
        table, idx)
    return {e.primitive.name for e in _iter_eqns(jx.jaxpr)}


def test_leaf_value_lookup_picks_its_path_by_the_backend(rng, monkeypatch):
    """On the CPU the helper IS the gather (the program every CPU trace and
    the analysis gate's pins hold); with the backend check answering "TPU"
    it is the chunked contraction, and holds no gather."""
    table = jnp.asarray(_f32_table(rng, 255))
    idx = jnp.asarray(rng.randint(0, 255, 5000).astype(np.int32))
    prims = _primitives(table, idx)
    assert "gather" in prims and "dot_general" not in prims
    want = _bits(lookup.lookup_leaf_values(table, idx))
    monkeypatch.setattr(lookup, "_on_tpu", lambda: True)
    monkeypatch.setattr(lookup, "_ONE_HOT_ELEMS", 1024 * 256)
    prims = _primitives(table, idx)
    assert {"dot_general", "scan"} <= prims and "gather" not in prims
    assert np.array_equal(_bits(lookup.lookup_leaf_values(table, idx)), want)


@pytest.mark.parametrize("path", ["fused", "pipelined", "synchronous"])
def test_training_with_the_contraction_builds_the_gathers_model(
        rng, monkeypatch, path):
    """Five iterations through each of the three score-update sites
    (``_fused_iter_fn.step``, ``_score_add_leaf``, ``add_by_leaf_id``) with
    the helper's contraction branch forced and its chunks cut to 2,048 rows
    build the model the gather builds, to the last character."""
    n = 5000
    X = rng.randn(n, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(n) > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
              "verbosity": -1, "learning_rate": 0.3}
    if path == "pipelined":      # GOSS reorders around the gradients
        params.update(boosting="goss", top_rate=0.3, other_rate=0.2)
    calls = []
    real = lookup.lookup_f32

    def train():
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.Booster(params, ds)
        if path == "synchronous":       # a validation set ends the pipeline
            bst.add_valid(ds.create_valid(X[:500], label=y[:500]), "v")
        g = bst.gbdt
        assert g._can_fuse() == (path == "fused")
        assert g._can_pipeline() == (path != "synchronous")
        for _ in range(5):
            bst.update()
        return bst.model_to_string()

    want = train()
    monkeypatch.setattr(lookup, "_on_tpu", lambda: True)
    monkeypatch.setattr(lookup, "_ONE_HOT_ELEMS", 1024 * 256)
    monkeypatch.setattr(
        lookup, "lookup_f32",
        lambda t, i, c=None: calls.append(i.shape) or real(t, i, c))
    from lightgbm_tpu.boosting.gbdt import _score_add_leaf
    _score_add_leaf.clear_cache()       # a module-level jit: trace it anew
    try:
        assert train() == want
    finally:
        _score_add_leaf.clear_cache()
    assert calls and all(s[0] >= n for s in calls), calls
