"""The lambdarank objective's pair layout (`lightgbm_tpu/rank_objective.py`):
queries bucketed by their own length class, against the plain float64
reference the benchmark judges with (`benchmark/reference/
lambdarank_plain.py`), inside the fused step as jit arguments, and on the
wave learner's fused path against the compact learner."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from benchmark.reference import lambdarank_plain as ref
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Metadata
from lightgbm_tpu.learner_wave import WaveTPUTreeLearner
from lightgbm_tpu.rank_objective import LambdarankNDCG, pair_layout

from test_wave import _models_equal, _pair


def _sizes(rng, nq=160):
    """Heavy-tailed lengths with the edge cases in place: single-document
    queries, and ONE query alone in the top length class (600 documents,
    class 1024; every other query is at most 256 long)."""
    sizes = np.clip(np.exp(rng.normal(3.0, 1.0, nq)).astype(np.int64), 1, 256)
    sizes[:4] = [1, 1, 2, 600]
    return sizes


def _objective(sizes, labels, n_pad, **params):
    md = Metadata(int(sizes.sum()))
    md.set_label(labels)
    md.set_group(sizes)
    obj = LambdarankNDCG(Config.from_params(dict(objective="lambdarank",
                                                 **params)))
    obj.init(md, md.num_data, n_pad)
    return obj


def _grads(obj, score):
    def f(s, arrs):
        with obj.bind(arrs):
            return obj.get_gradients(s)
    with jax.enable_x64(False):
        g, h = jax.jit(f)(jnp.asarray(score, jnp.float32),
                          obj.device_arrays())
    return np.asarray(g), np.asarray(h)


@pytest.mark.parametrize("max_position", [20, 3])
def test_bucketed_gradients_match_the_plain_reference(max_position):
    rng = np.random.RandomState(0)
    sizes = _sizes(rng)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    n = int(sizes.sum())
    y = rng.randint(0, 5, n).astype(np.float32)
    y[bounds[4]:bounds[5]] = 0                    # all labels zero
    score = np.zeros(n + 29, np.float32)          # 29 padded rows
    score[:n] = rng.randn(n).astype(np.float32)
    score[bounds[5]:bounds[6]] = 0.5              # all scores equal
    score[bounds[6]:bounds[6] + 4] = 0.25         # ties inside a query
    score[bounds[3]:bounds[3] + 300] = np.round(score[bounds[3]:bounds[3] + 300])
    obj = _objective(sizes, y, n + 29, max_position=max_position)
    assert obj.pair_layout["classes"] == len(np.unique(
        np.maximum(8, 2 ** np.ceil(np.log2(sizes)))))
    g, h = _grads(obj, score)
    want_g, want_h = ref.grad_hess(score[:n].astype(np.float64), y, bounds,
                                   max_position)
    # float32 lambdas against float64: each is a sum over one row of its
    # query's pair matrix (at most 600 terms here, each a product of five
    # float32-rounded factors), so the error is a few units in the 7th
    # digit of the largest lambda (measured 1.2e-7 of it); 1e-5 of it
    # leaves room, and a dropped or doubled pair moves a lambda by far more
    for got, want in ((g[:n], want_g), (h[:n], want_h)):
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
    # no pair: one document, all labels equal; padded rows get nothing
    for q in (0, 1, 4):
        assert not g[bounds[q]:bounds[q + 1]].any()
        assert not h[bounds[q]:bounds[q + 1]].any()
    assert not g[n:].any() and not h[n:].any()


def test_layout_counters_against_sums_by_hand():
    sizes = np.array([1, 2, 8, 9, 16, 17, 100, 600, 33, 3])
    classes, c = pair_layout(sizes)
    assert c["real_pairs"] == sum(int(s) * int(s) for s in sizes)
    # classes 8 (1, 2, 8, 3), 16 (9, 16), 32 (17), 64 (33), 128 (100),
    # 1024 (600): one batch each under the element budget
    assert [Q for Q, *_ in classes] == [8, 16, 32, 64, 128, 1024]
    assert c["computed_pairs"] == 4 * 64 + 2 * 256 + 1024 + 4096 \
        + 16384 + 1024 * 1024
    assert c["classes"] == 6 and c["longest_query"] == 600
    assert c["slots"] == 4 * 8 + 2 * 16 + 32 + 64 + 128 + 1024
    # a class too large for one batch: batches of equal size, the fewest
    # empty queries
    classes, c = pair_layout(np.full(10, 4096), budget=3 * 4096 * 4096)
    (Q, qb, nb, _, _, queries), = classes
    assert (Q, qb, nb, len(queries)) == (4096, 3, 4, 10)
    assert c["computed_pairs"] == 12 * 4096 * 4096
    assert c["real_pairs"] == 10 * 4096 * 4096


def test_second_labels_and_layout_of_the_same_shapes_reuse_the_step(rng):
    """The fused step holds no label or layout constant: one step, called
    with a second label vector and a second query layout of the same class
    shapes, compiles once; two boosters on different labels lower to the
    same program text, so the persistent cache serves the second."""
    nq = 40
    sizes = np.full(nq, 20)
    n = int(sizes.sum())
    X = rng.randn(n, 5)
    params = {"objective": "lambdarank", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5, "min_sum_hessian_in_leaf": 1e-3}

    def booster(seed, group):
        y = np.random.RandomState(seed).randint(0, 4, n).astype(float)
        return lgb.Booster(params, lgb.Dataset(X, label=y, group=group,
                                               params=params)).gbdt

    with jax.enable_x64(False):
        a = booster(1, sizes)
        # 17 + 23 documents: another layout, the same class (32)
        b = booster(2, np.tile([17, 23], nq // 2))
        step = a._fused_iter_fn()
        fmask, lr = a._feature_sample(), jnp.float32(0.1)

        def call(g):
            out = step(a.train_score.score + 0.0, a.learner.bins_packed(),
                       a._bag_mask, fmask, lr, g.objective.device_arrays())
            jax.block_until_ready(out)
        call(a)
        call(b)
        assert step._cache_size() == 1

        def text(g):
            return g._fused_iter_fn().lower(
                g.train_score.score, g.learner.bins_packed(), g._bag_mask,
                fmask, lr, g.objective.device_arrays()).as_text()
        assert text(a) == text(b)


@pytest.mark.parametrize("path", ["reference", "shipped"])
def test_lambdarank_on_the_fused_wave_path_matches_compact(path):
    rng = np.random.RandomState(7)
    sizes = np.clip(np.exp(rng.normal(4.0, 0.9, 120)).astype(int), 2, 400)
    n = int(sizes.sum())
    X = rng.randn(n, 6)
    rel = X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(n)
    y = np.digitize(rel, np.percentile(rel, [60, 80, 93, 98])).astype(float)
    pa, pb = _pair(path, objective="lambdarank", num_leaves=15,
                   min_sum_hessian_in_leaf=1e-3)
    a, b = _models_equal(pa, pb, X, y, rounds=4, exact=path == "reference",
                         thresholds=path == "reference", group=sizes)
    assert b.gbdt._can_fuse() and isinstance(b.gbdt.learner,
                                             WaveTPUTreeLearner)
