"""``tree_learner=data`` at the shape of the benchmark's four-chip cell
(``criteo-v5e128-share``: 67 columns, so 72 padded and 18 packed words, a
shape no other test has), on four of the CPU's virtual devices.

The semantics of ``tree_learner=data`` are the serial learner's: the same
rows give the same trees.  Float32 sums over four shards round otherwise than
one sum over all rows, so the comparison is exact in everything an integer
or a choice decides (features, thresholds, children, row counts) and close in
what a float32 sum decides (values, gains)."""

import re

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb

FEATURES = 67
_BASE = {"objective": "binary", "num_leaves": 31, "learning_rate": 0.1,
         "max_bin": 255, "min_data_in_leaf": 20,
         "min_sum_hessian_in_leaf": 1e-3, "verbosity": -1, "metric": "none"}
_EXACT = ("num_leaves", "split_feature", "threshold", "decision_type",
          "left_child", "right_child", "leaf_count", "internal_count")
_CLOSE = ("split_gain", "leaf_value", "internal_value")


def _rows(seed, n=24000):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, FEATURES).astype(np.float32) + 8.0
    z = X - 8.0
    logit = 1.2 * z[:, 0] + 0.8 * z[:, 13] + 0.5 * z[:, 1] * z[:, 2] \
        + np.sin(z[:, 3]) - 0.6 * z[:, 27] + 0.5 * rng.randn(n)
    return X, (logit > 0).astype(np.float32)


def _trees(model_text):
    """{key: [values]} of every tree of a model text, in order."""
    trees = []
    for block in model_text.split("\nTree=")[1:]:
        fields = dict(line.split("=", 1) for line in block.split("\n")
                      if "=" in line)
        trees.append(fields)
    return trees


def _same_trees(a, b):
    """Exact in what an integer or a choice decides, close in what a float32
    sum decides."""
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        for key in _EXACT:
            assert ta[key] == tb[key], key
        for key in _CLOSE:
            np.testing.assert_allclose(
                np.array(tb[key].split(), float),
                np.array(ta[key].split(), float), rtol=2e-4, atol=2e-5,
                err_msg=key)


def _train(X, y, iters, **extra):
    params = dict(_BASE, **extra)
    return lgb.train(params, lgb.Dataset(X, label=y, params=params), iters)


@pytest.mark.parametrize("bagging", [False, True])
def test_data_parallel_builds_the_serial_learners_trees_at_67_columns(bagging):
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    X, y = _rows(7)
    extra = dict(bagging_fraction=0.7, bagging_freq=1, bagging_seed=3) \
        if bagging else {}
    serial = _train(X, y, 4, **extra)
    mesh = _train(X, y, 4, tree_learner="data", parallel_mesh="4", **extra)
    assert type(serial.gbdt.learner).__name__ == "WaveTPUTreeLearner"
    learner = mesh.gbdt.learner
    assert type(learner).__name__ == "ShardedWaveLearner"
    assert (learner.f_pad, learner.fw, learner.D) == (72, 18, 4)
    a, b = _trees(serial.model_to_string()), _trees(mesh.model_to_string())
    assert len(a) == len(b) == 4
    _same_trees(a, b)
    np.testing.assert_allclose(mesh.predict(X[:2000]),
                               serial.predict(X[:2000]), rtol=0, atol=2e-5)


@pytest.mark.parametrize("mode", ["data", "voting"])
def test_shard_kernels_build_the_one_hot_paths_trees(mode, monkeypatch):
    """On a TPU every sharded wave learner of a 1-D mesh runs the Pallas
    histogram kernels over its shard (the CPU runs the XLA one-hot).  The
    TPU branch, its kernels interpreted here, builds the trees of the
    one-hot branch at 18 words: for ``tree_learner=data`` and for voting."""
    from jax.experimental.pallas import tpu as pltpu

    from lightgbm_tpu import learner_compact, learner_wave
    from lightgbm_tpu.ops import histogram
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    X, y = _rows(5, n=4096)
    extra = dict(tree_learner=mode, parallel_mesh="4", num_leaves=6)
    one_hot = _train(X, y, 1, **extra)
    assert not one_hot.gbdt.learner._use_pallas
    for mod in (histogram, learner_compact, learner_wave):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        kernels = _train(X, y, 1, **extra)
    assert kernels.gbdt.learner._use_pallas
    assert type(kernels.gbdt.learner) is type(one_hot.gbdt.learner)
    _same_trees(_trees(one_hot.model_to_string()),
                _trees(kernels.model_to_string()))


def test_a_full_wave_hands_its_exchange_every_padded_column():
    """The learner's ``CollectiveLedger`` (on with ``telemetry``): a full
    wave hands W x 72 columns x bins x (g, h, count) float32 to its
    reduce-scatter, whatever the rows."""
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    X, y = _rows(11, n=8000)
    learner = _train(X, y, 1, tree_learner="data", parallel_mesh="4",
                     telemetry=True).gbdt.learner
    waves = [site["bytes_per_call"] for site in learner._ledger.sites()
             if site["op"] == "psum_scatter" and site["cadence"] == "wave"]
    assert max(waves) == learner.W * 72 * learner.num_bins_padded * 3 * 4


def test_every_count_that_crosses_the_mesh_is_an_integer():
    """No CPU test can hold the 2^24 rows past which a float32 count skips
    odd numbers (the cell's four shards sum to 53,125,000), so the traced
    ``wave_sharded_data`` program is read instead, as ``analysis/spmd.py``
    reads its collectives: a float goes through a ``psum`` / ``pmax`` only
    as one of the root's two totals (gradients, hessians); every other one
    (the root's bagged rows, each wave's member counts, the stall gate's
    spans and counts) is an integer.  Histograms (reduce-scatter) and
    candidate rows (all-gather) are float32 by design: their count lane
    gates ``min_data_in_leaf`` and is never written into a tree."""
    from lightgbm_tpu.analysis import jaxpr_lint, spmd
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    seq = spmd.extract_sequence(jaxpr_lint._trace_wave_sharded("data", ndev=4))
    reduced = [e for e in seq if e["prim"] in ("psum", "pmax", "pmin")]
    assert len(reduced) >= 6, seq
    floats = [e for e in reduced if not re.match(r"u?int", e["dtype"])]
    assert [(e["dtype"], e["shape"]) for e in floats] == \
        [("float32", []), ("float32", [])], floats
    assert any(e["shape"] == [] for e in reduced
               if re.match(r"u?int", e["dtype"]))     # the root's bagged rows
