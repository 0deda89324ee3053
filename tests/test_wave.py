"""Frontier-wave learner ≡ sequential compact learner.

The wave learner (`learner_wave.py`) batches leaf-wise growth into
speculative frontier waves and trims back to exact best-first semantics
with a greedy replay.  With ``tpu_sort_cutoff=0`` the sequential compact
learner compacts every window too, and the two must agree BIT-EXACTLY
(same split sequence, same histograms, same leaf values); with the default
cutoff the physical row alignment differs so agreement is to float
tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import lightgbm_tpu as lgb
from lightgbm_tpu.learner_wave import WaveTPUTreeLearner


def _train(params, X, y, rounds=5, **dskw):
    ds = lgb.Dataset(X, label=y, params=params, **dskw)
    bst = lgb.Booster(params, ds)
    for _ in range(rounds):
        bst.update()
    return bst


def _models_equal(pa, pb, X, y, rounds=5, exact=True, thresholds=True,
                  **dskw):
    """``thresholds=False`` (not ``exact``): a threshold may sit on either
    side of a bin that holds none of the node's rows (PERF.md section 7:
    one gain, rounded two ways), so the rows each node and leaf holds are
    compared in the thresholds' place."""
    a = _train(pa, X, y, rounds, **dskw)
    b = _train(pb, X, y, rounds, **dskw)
    assert isinstance(b.gbdt.learner, WaveTPUTreeLearner), \
        type(b.gbdt.learner).__name__
    if exact:
        assert a.model_to_string() == b.model_to_string()
    else:
        a.model_to_string(), b.model_to_string()  # flush lazy assembly
        for ta, tb in zip(a.gbdt._models, b.gbdt._models):
            np.testing.assert_array_equal(ta.split_feature, tb.split_feature)
            if thresholds:
                np.testing.assert_array_equal(ta.threshold_in_bin,
                                              tb.threshold_in_bin)
            else:
                np.testing.assert_array_equal(ta.internal_count,
                                              tb.internal_count)
                np.testing.assert_array_equal(ta.leaf_count, tb.leaf_count)
            np.testing.assert_allclose(
                ta.leaf_value[:ta.num_leaves], tb.leaf_value[:tb.num_leaves],
                rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(a.predict(X), b.predict(X), rtol=1e-4,
                               atol=1e-5)
    return a, b


def _pair(path="reference", **over):
    """Params of the (compact, wave) pair.  ``path="shipped"`` overrides no
    ``tpu_wave_*`` / ``tpu_sort_cutoff`` option: the wave learner runs as
    every benchmark cell runs it (sort deferral, batched stall corrections
    with the fused top member, default cut-offs) and agrees with the
    compact learner in structure and to float tolerance
    (``exact=False``).  ``path="reference"`` is the base below, under which
    the two agree bit for bit."""
    # opening OFF for the bit-exact contract: the compact comparator keeps
    # canonical (leaf-compacted) row order at every step, while opening
    # sums the first levels' histograms in ROOT row order — same splits,
    # last-ulp f32 differences (dedicated opening tests below)
    # stall_batch=1 for the same reason: batched (K>1) replay corrections
    # histogram the stalled leaf through its parent's covering span with a
    # lid mask (parent row order) instead of a compacted child window —
    # same rows, last-ulp f32 summation differences (dedicated tolerance
    # test below)
    base = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
            "min_data_in_leaf": 20, "verbosity": -1, "metric": "none"}
    if path == "reference":
        base.update({"tpu_sort_cutoff": 0, "tpu_wave_sort_cutoff": 0,
                     "tpu_wave_open_levels": 0,
                     "tpu_wave_defer_sorts": False,
                     "tpu_wave_stall_batch": 1})
    else:
        assert path == "shipped", path
    base.update(over)
    return dict(base, tpu_learner="compact"), dict(base, tpu_learner="wave")


def _make(n=20000, f=10, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0).astype(float)
    return X, y


def test_wave_binary_exact():
    X, y = _make()
    _models_equal(*_pair(), X=X, y=y)


def test_wave_default_cutoff_tolerance():
    # with the default sort cutoff the compact learner's small windows are
    # mask-mode (different summation alignment) — same splits, float-level
    # leaf values
    X, y = _make()
    pa, pb = _pair()
    for p in (pa, pb):
        del p["tpu_sort_cutoff"], p["tpu_wave_sort_cutoff"]
    _models_equal(pa, pb, X, y, exact=False)


@pytest.mark.parametrize("defer", [False, True])
def test_wave_stall_batch_tolerance(defer):
    # batched replay corrections (the tpu_wave_stall_batch=4 default) mask
    # the stalled leaf's histogram through its parent's span instead of a
    # compacted window — same split structure, float-level value drift;
    # low overshoot forces plenty of stalls so the batch path really runs.
    # defer=True covers the SHIPPED default combination, where batched
    # corrections read phys_i covering spans of sort-deferred children and
    # the pre-replay materialization sort is skipped
    X, y = _make()
    _, pb = _pair(tpu_wave_overshoot=0.0, tpu_wave_defer_sorts=defer)
    pb2 = dict(pb, tpu_wave_stall_batch=4)
    del pb2["tpu_sort_cutoff"], pb2["tpu_wave_sort_cutoff"]
    del pb["tpu_sort_cutoff"], pb["tpu_wave_sort_cutoff"]
    _models_equal(pb, pb2, X, y, exact=False)


def test_wave_width_invariance():
    # the trimmed tree must not depend on the wave width
    X, y = _make(8000)
    _, p1 = _pair(tpu_wave_width=4)
    _, p2 = _pair(tpu_wave_width=64)
    a = _train(p1, X, y)
    b = _train(p2, X, y)
    assert a.model_to_string() == b.model_to_string()


def test_segment_hist_kernel_interpret():
    # the wave learner's one-call-per-wave histogram kernel vs a bincount
    # oracle, in Pallas interpret mode (runs on CPU)
    from lightgbm_tpu.ops.hist_pallas import (build_histogram_segments,
                                              pack_bin_words)

    rng = np.random.RandomState(31)
    n, f, b = 4096, 8, 64
    bins = rng.randint(0, b, (f, n)).astype(np.uint8)
    w = rng.randn(3, n).astype(np.float32)
    lid = np.zeros(n, np.int32)
    # three disjoint windows with distinct lids, misaligned starts
    wins = [(100, 700, 5), (1000, 900, 9), (2500, 1500, 11)]
    for s, c, leaf in wins:
        lid[s:s + c] = leaf
    rb = 512
    slot_t, block_t, leaf_t = [], [], []
    for k, (s, c, leaf) in enumerate(wins):
        for blk in range(s // rb, (s + c - 1) // rb + 1):
            slot_t.append(k)
            block_t.append(blk)
            leaf_t.append(leaf)
    T = n // rb + 4
    while len(slot_t) < T:
        slot_t.append(3)
        block_t.append(0)
        leaf_t.append(-1)
    out = build_histogram_segments(
        pack_bin_words(jnp.asarray(bins)), jnp.asarray(w),
        jnp.asarray(lid), jnp.asarray(slot_t, dtype=jnp.int32),
        jnp.asarray(block_t, dtype=jnp.int32),
        jnp.asarray(leaf_t, dtype=jnp.int32),
        num_bins=b, n_slots=3, row_block=rb, nterms=0, interpret=True)
    out = np.asarray(out)
    assert out.shape == (3, f, b, 3)
    for k, (s, c, leaf) in enumerate(wins):
        m = (lid == leaf).astype(np.float64)
        for fi in range(f):
            for ch in range(3):
                ref = np.bincount(bins[fi], weights=w[ch] * m,
                                  minlength=b)[:b]
                np.testing.assert_allclose(out[k, fi, :, ch], ref,
                                           rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("params,rows,multislot,want", [
    ({}, 10_500_096, True, "auto"),             # serial, Pallas: the cells
    ({}, 1 << 22, True, "auto"),
    ({}, (1 << 22) - 1024, True, 0),            # a sort is cheap there
    ({}, 1_000_448, True, 0),
    ({}, 10_500_096, False, 0),                 # CPU, f64, sharded seams
    ({"tpu_wave_open_levels": 0}, 10_500_096, True, 0),     # explicit: kept
    ({"tpu_wave_open_levels": 2}, 4096, True, 2),
    ({"tpu_wave_open_levels": 6}, 4096, False, 6),
])
def test_open_levels_auto_rule(params, rows, multislot, want):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner_wave import (_AUTO_OPEN_LEVELS,
                                           _resolve_open_levels)
    assert _AUTO_OPEN_LEVELS == 5
    got = _resolve_open_levels(Config.from_params(params), rows, multislot)
    assert got == (_AUTO_OPEN_LEVELS if want == "auto" else want)


@pytest.mark.parametrize("capacity,width,opened,want", [
    # the Higgs cells' growth waves: 10,500,096 rows in blocks of 2048
    (5576, 64, False, [5576, 2788, 1394, 697, 348, 174, 128]),
    (5576, 64, True, [5576, 2788, 1394, 697, 348, 256]),
    (5144, 8, True, [5144, 2572, 1286, 643, 321, 256]),    # corrections
    (5144, 8, False, [5144, 2572, 1286, 643, 321, 160, 80, 40, 20, 16]),
    (191, 63, True, [191]),                 # under the floor: one grid
    (191, 63, False, [191, 126]),
    (512, 200, True, [512, 400]),           # 2W over the floor: 2W rules
])
def test_segment_grid_buckets(capacity, width, opened, want):
    """The segment kernel's grid sizes at a call site: the parent's ladder
    down to 2W wherever the ramp is not opened (voting, the 2-D learner, the
    CPU, small inputs), none under 256 chunks where it is."""
    from lightgbm_tpu.learner_wave import _segment_grid_buckets
    assert _segment_grid_buckets(capacity, width, opened) == want


@pytest.mark.parametrize("learner,opens", [
    ("serial-cpu", False), ("serial-tpu", True), ("serial-tpu-quant", False),
    ("serial-tpu-f64", False), ("data4", True), ("data4-cpu", False),
    ("voting4", False)])
def test_open_levels_auto_by_learner(learner, opens, monkeypatch):
    """Auto opens the ramp only where ``_opening_hists`` takes the
    multi-slot kernel: the serial and the data-parallel learner steered
    onto their TPU branch (the latter on a shard's rows), not on the CPU,
    not with quantized gradients (no chip reading), not with float64
    histograms, and not in the voting learner, whose pool stays local."""
    from lightgbm_tpu import learner_compact, learner_wave
    from lightgbm_tpu.ops import histogram
    mesh = learner in ("data4", "data4-cpu", "voting4")
    if mesh and len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    if learner not in ("serial-cpu", "data4-cpu"):
        for mod in (histogram, learner_compact, learner_wave):
            monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    data4 = {"tree_learner": "data", "parallel_mesh": "4"}
    extra = {"serial-tpu-quant": {"tpu_quantized_grad": "on"},
             "serial-tpu-f64": {"gpu_use_dp": True},
             "data4": data4, "data4-cpu": data4,
             "voting4": {"tree_learner": "voting", "parallel_mesh": "4"}}
    # the rule's row floor, lowered to the test's size: a shard's rows
    monkeypatch.setattr(learner_wave, "_AUTO_OPEN_MIN_ROWS", 1024)
    X, y = _make(n=4096)
    params = dict(_pair("shipped", num_leaves=255)[1],
                  **extra.get(learner, {}))
    if mesh:
        del params["tpu_learner"]       # the factory's choice under a mesh
    g = lgb.Booster(params, lgb.Dataset(X, label=y, params=params)).gbdt
    assert isinstance(g.learner, WaveTPUTreeLearner)
    if mesh:
        assert g.learner.n_local == 1024
    assert g.learner.open_levels == (
        learner_wave._AUTO_OPEN_LEVELS if opens else 0)
    assert g.learner._multislot_opening() == (
        learner in ("serial-tpu", "serial-tpu-quant", "data4"))


# the depth auto resolves to on the chip, and one less
_OPEN_DEPTHS = [5, 4]


@pytest.mark.parametrize("defer", [False, True])
@pytest.mark.parametrize("levels", _OPEN_DEPTHS)
def test_wave_opening_first_tree_bit_exact(levels, defer):
    """Opening vs no-opening, ONE boosting round: the first iteration's
    gradients are dyadic rationals (grad ±0.5, hess 0.25 at score 0 —
    boost_from_average off), so f32 histogram sums are EXACT in any
    summation order — the two flows must emit bit-identical models.  Without
    deferral the opening ends in its own materialisation sort; with it the
    levels' keys stay pending into the first growth wave's sort (and, under
    the reference base's ``stall_batch=1``, into the sort before the
    replay where no wave follows)."""
    X, y = _make()
    _, pb = _pair(boost_from_average=False)
    p_open = dict(pb, tpu_wave_open_levels=levels,
                  tpu_wave_defer_sorts=defer)
    a = _train(pb, X, y, rounds=1)
    b = _train(p_open, X, y, rounds=1)
    assert isinstance(b.gbdt.learner, WaveTPUTreeLearner)
    assert b.gbdt.learner.open_levels == 4     # 31 leaves hold 4 levels
    assert b.gbdt.learner._defer_sorts == defer
    assert a.model_to_string() == b.model_to_string()


@pytest.mark.parametrize("levels", _OPEN_DEPTHS)
def test_wave_opening_matches_no_opening(levels):
    """Multi-round: behaviorally equivalent models (opening changes the f32
    histogram summation ORDER for the first levels, so a near-tie split can
    legitimately flip by one bin in later trees — the first-tree test above
    pins exactness where sums are exact)."""
    X, y = _make()
    _, pb = _pair()
    p_open = dict(pb, tpu_wave_open_levels=levels)
    a = _train(pb, X, y, rounds=5)
    b = _train(p_open, X, y, rounds=5)
    a.model_to_string(), b.model_to_string()
    for ta, tb in zip(a.gbdt._models, b.gbdt._models):
        assert ta.num_leaves == tb.num_leaves
    np.testing.assert_allclose(a.predict(X), b.predict(X), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("levels", _OPEN_DEPTHS)
def test_wave_opening_with_default_cutoffs_and_bagging(levels):
    """Opening under the DEFAULT sort cutoffs + bagging + feature_fraction
    (the bench configuration's flow) stays structurally identical to the
    sequential compact learner."""
    X, y = _make()
    pa, pb = _pair(bagging_fraction=0.7, bagging_freq=1, bagging_seed=5,
                   feature_fraction=0.8)
    del pa["tpu_sort_cutoff"], pa["tpu_wave_sort_cutoff"]
    del pb["tpu_sort_cutoff"], pb["tpu_wave_sort_cutoff"]
    pb["tpu_wave_open_levels"] = levels
    _models_equal(pa, pb, X, y, exact=False)


@pytest.mark.parametrize("leaves,levels", [(63, 5), (63, 4), (16, 4)])
def test_wave_opening_on_the_shipped_options(leaves, levels):
    """What the cells run since PR 31: the opened levels, then sort
    deferral, batched stall corrections and the default cut-offs (no
    ``tpu_wave_*`` override but the depth, which the CPU's auto leaves at
    0).  63 leaves grow on after the opening, so its pending keys meet the
    first wave's sort; 16 leaves are spent inside four levels, so the
    replay corrects on rows that never moved: the compact learner's
    structure either way, values to float tolerance."""
    X, y = _make()
    pa, pb = _pair("shipped", num_leaves=leaves,
                   tpu_wave_open_levels=levels, bagging_fraction=0.7,
                   bagging_freq=1, bagging_seed=5)
    _, b = _models_equal(pa, pb, X, y, rounds=3, exact=False,
                         thresholds=False)
    learner = b.gbdt.learner
    assert learner._defer_sorts and learner._stall_batch > 1
    assert learner.open_levels == min(levels, leaves.bit_length() - 1)


@pytest.mark.parametrize("levels", _OPEN_DEPTHS)
def test_wave_opening_deep_tree_and_tiny_budget(levels):
    # budget smaller than a full opening (num_leaves=4 -> 2 levels), and a
    # deeper-than-opening tree; both must replay to exact best-first
    X, y = _make(n=6000)
    for leaves in (4, 88):
        _, pb = _pair(num_leaves=leaves)
        p_open = dict(pb, tpu_wave_open_levels=levels)
        a = _train(pb, X, y, rounds=2)
        b = _train(p_open, X, y, rounds=2)
        a.model_to_string(), b.model_to_string()
        for ta, tb in zip(a.gbdt._models, b.gbdt._models):
            assert ta.num_leaves == tb.num_leaves
        np.testing.assert_allclose(a.predict(X), b.predict(X), rtol=2e-3,
                                   atol=2e-3)


def test_wave_defer_sorts_first_tree_bit_exact():
    """Sort-deferral alternation vs per-wave sorting, ONE round with
    dyadic gradients (boost_from_average off): f32 sums are exact in any
    order, so the models must be bit-identical."""
    X, y = _make()
    _, pb = _pair(boost_from_average=False)
    p_defer = dict(pb, tpu_wave_defer_sorts=True)
    a = _train(pb, X, y, rounds=1)
    b = _train(p_defer, X, y, rounds=1)
    assert a.model_to_string() == b.model_to_string()


def test_wave_defer_sorts_matches_multi_round():
    """Multi-round behavioral equivalence under the DEFAULT cutoffs +
    bagging (deferral changes histogram summation order — near-tie bin
    flips allowed, models must stay equivalent)."""
    X, y = _make()
    _, pb = _pair(bagging_fraction=0.7, bagging_freq=1, bagging_seed=5)
    del pb["tpu_sort_cutoff"], pb["tpu_wave_sort_cutoff"]
    p_defer = dict(pb, tpu_wave_defer_sorts=True)
    a = _train(pb, X, y, rounds=5)
    b = _train(p_defer, X, y, rounds=5)
    a.model_to_string(), b.model_to_string()
    for ta, tb in zip(a.gbdt._models, b.gbdt._models):
        assert ta.num_leaves == tb.num_leaves
    np.testing.assert_allclose(a.predict(X), b.predict(X), rtol=2e-3,
                               atol=2e-3)


def test_wave_defer_sorts_deep_tree():
    X, y = _make(n=30000)
    _, pb = _pair(num_leaves=127, boost_from_average=False)
    p_defer = dict(pb, tpu_wave_defer_sorts=True)
    a = _train(pb, X, y, rounds=1)
    b = _train(p_defer, X, y, rounds=1)
    assert a.model_to_string() == b.model_to_string()


def test_multislot_hist_kernel_interpret():
    # the opening-phase full-pass kernel (K leaves in one pass, slot routing
    # in the weight operand) vs a bincount oracle, Pallas interpret mode
    from lightgbm_tpu.ops.hist_pallas import (build_histogram_multislot,
                                              pack_bin_words)

    rng = np.random.RandomState(37)
    n, f, b, K = 4096, 8, 64, 4
    bins = rng.randint(0, b, (f, n)).astype(np.uint8)
    # channel 2 is the BAG MASK ({0,1}) by kernel contract — the mixed term
    # expansion gives it a single exact bf16 term
    bag = (rng.rand(n) < 0.7).astype(np.float32)
    w = np.stack([rng.randn(n).astype(np.float32) * bag,
                  rng.randn(n).astype(np.float32) * bag, bag])
    # interleaved slots incl. masked rows (slot == K) — root-order layout
    slot = rng.randint(0, K + 1, n).astype(np.int32)
    # interpret-mode dots carry ~single-bf16-term precision regardless of
    # nterms (a simulator artifact — the real MXU path measures ~1e-6 at
    # nterms=3), so g/h tolerances are loose at nterms=3; counts and the
    # nterms=0 (f32 HIGHEST) path must be tight
    for nterms, tol in ((0, dict(rtol=1e-5, atol=1e-3)),
                        (3, dict(rtol=2e-2, atol=5e-2))):
        out = np.asarray(build_histogram_multislot(
            pack_bin_words(jnp.asarray(bins)), jnp.asarray(w),
            jnp.asarray(slot), num_bins=b, n_slots=K, row_block=512,
            nterms=nterms, interpret=True))
        assert out.shape == (K, f, b, 3)
        for k in range(K):
            m = (slot == k).astype(np.float64)
            for fi in range(f):
                for ch in range(3):
                    ref = np.bincount(bins[fi], weights=w[ch] * m,
                                      minlength=b)[:b]
                    np.testing.assert_allclose(out[k, fi, :, ch], ref,
                                               **tol)
            np.testing.assert_array_equal(
                out[k, :, :, 2], np.rint(out[k, :, :, 2]))  # counts exact


def _check_multislot_exact(n_slots, bins, words, quant=False, seed=5):
    """One multi-slot pass against a bincount, bit for bit: weights that
    one bfloat16 term holds exactly (interpret mode keeps one term whatever
    ``nterms`` says), rows outside [0, n_slots) contributing nowhere."""
    from lightgbm_tpu.ops.hist_pallas import (build_histogram_multislot,
                                              pack_bin_words)

    rng = np.random.RandomState(seed)
    n, f = 2048, 4 * words
    codes = rng.randint(0, bins - 1, (f, n)).astype(np.uint8)
    bag = (rng.rand(n) < 0.7).astype(np.float32)
    w = np.stack([rng.randint(-8, 9, n).astype(np.float32) * bag * 0.5,
                  rng.randint(0, 9, n).astype(np.float32) * bag * 0.25, bag])
    slot = rng.randint(-1, n_slots + 2, n).astype(np.int32)
    out = np.asarray(build_histogram_multislot(
        pack_bin_words(jnp.asarray(codes)), jnp.asarray(w),
        jnp.asarray(slot), num_bins=bins, n_slots=n_slots, row_block=512,
        nterms=3, quant=quant, interpret=True))
    assert out.shape == (n_slots, f, bins, 3)
    # quantized, the count channel carries the hessian lane
    lanes = (0, 1, 1) if quant else (0, 1, 2)
    for k in range(n_slots):
        m = (slot == k).astype(np.float64)
        for fi in range(f):
            for ch in range(3):
                ref = np.bincount(codes[fi], weights=w[lanes[ch]] * m,
                                  minlength=bins)[:bins]
                np.testing.assert_array_equal(out[k, fi, :, ch], ref)


@pytest.mark.parametrize("words", [2, 8, 18, 3])
@pytest.mark.parametrize("bins", [256, 64])
@pytest.mark.parametrize("n_slots", [1, 2, 4, 8, 16, 12, 3])
def test_multislot_radix_formulation_is_exact(n_slots, bins, words):
    """The two-level formulation of the multi-slot pass (bin = 32 hi + lo,
    slot = 4 row group + column slot: ``_radix_word_slots``) against a
    bincount: every width an opening level uses (16 is the widest of
    auto's five), one that is no power of two, one with no split (3: the
    plain formulation), at 2 words (the loop of two words a body runs
    once), 8 (the Higgs cells' word tile), 18 (the four-chip cell's: a word
    tile of 18, not 8) and 3 (an odd tile: its last word outside the
    loop), so that each word of a body lands in its own block."""
    from lightgbm_tpu.ops.hist_pallas import _multislot_split

    assert (_multislot_split(n_slots) is None) == (n_slots == 3)
    _check_multislot_exact(n_slots, bins, words, seed=5 + n_slots)


def test_multislot_radix_formulation_is_exact_quantized():
    """``tpu_quantized_grad=on`` runs the same word loop with one term a
    lane and no count row."""
    _check_multislot_exact(16, 256, 8, quant=True, seed=7)


# the cells' precision (three bfloat16 terms: the RADIX formulation of the
# multi-slot kernel) and full float32 (its PLAIN formulation).  A first
# tree's gradients (+-0.5, 0.25, the bag's 0 / 1) fit one bfloat16 term, so
# interpret mode, which keeps one term whatever ``nterms`` says, is exact
_PRECISIONS = ["bf16x3", "highest"]


def _spy_formulation(monkeypatch, seen):
    """Record which formulation each multi-slot kernel body is traced in."""
    from lightgbm_tpu.ops import hist_pallas
    real = hist_pallas._hist_kernel_multislot

    def kernel(*refs, **kw):
        seen.add("radix" if kw["radix"] else "plain")
        return real(*refs, **kw)

    monkeypatch.setattr(hist_pallas, "_hist_kernel_multislot", kernel)


@pytest.mark.parametrize("precision", _PRECISIONS)
def test_opening_multislot_branch_matches_the_fallback(precision,
                                                       monkeypatch):
    """The branch of ``_opening_hists`` that the chip runs (rows routed to
    member slots, ONE ``build_histogram_multislot`` pass a level), taken on
    the CPU in interpret mode: on a first tree, whose float32 sums are exact
    in any order, it builds the fallback's model bit for bit, with one pass
    a level at the level's width, in the formulation the precision picks."""
    from lightgbm_tpu.ops import hist_pallas

    X, y = _make(n=8192)
    _, pb = _pair(boost_from_average=False, tpu_hist_precision=precision,
                  tpu_wave_open_levels=3)
    a = _train(pb, X, y, rounds=1)
    widths = []
    real = hist_pallas.build_histogram_multislot

    def interpreted(*args, **kw):
        widths.append(kw["n_slots"])
        return real(*args, **dict(kw, interpret=True))

    monkeypatch.setattr(hist_pallas, "build_histogram_multislot",
                        interpreted)
    monkeypatch.setattr(WaveTPUTreeLearner, "_multislot_opening",
                        lambda self: True)
    forms = set()
    _spy_formulation(monkeypatch, forms)
    b = _train(pb, X, y, rounds=1)
    assert widths == [1, 2, 4]
    assert forms == {"radix" if precision == "bf16x3" else "plain"}
    assert a.model_to_string() == b.model_to_string()


@pytest.mark.parametrize("precision", _PRECISIONS)
def test_the_chips_histogram_branch_builds_the_fallbacks_first_tree(
        precision, monkeypatch):
    """The whole histogram path a TPU takes (packed root pass, five opened
    levels through the multi-slot kernel with their keys left pending, the
    segment kernel for the waves and the replay's corrections), its three
    kernels in interpret mode, at the cells' precision (the radix
    formulation, its slots put back in order inside ``_opening_hists``) and
    at full float32: on a first tree, whose float32 sums are exact in any
    order, the model of the CPU's path without the opening, bit for bit.
    The segment kernel is built once a call site: an opened program keeps
    no grid under 256 chunks, and both capacities are smaller here."""
    from lightgbm_tpu import learner_compact
    from lightgbm_tpu.ops import hist_pallas

    seen = {"multislot": [], "segments": [], "packed": 0}

    def interpreted(name):
        real = getattr(hist_pallas, name)

        def call(*args, **kw):
            if name == "build_histogram_multislot":
                seen["multislot"].append(kw["n_slots"])
            elif name == "build_histogram_segments":
                seen["segments"].append(args[3].shape[0])   # chunk capacity
            else:
                seen["packed"] += 1
            return real(*args, **dict(kw, interpret=True))
        return call

    X, y = _make(n=8192)
    _, pb = _pair("shipped", num_leaves=63, boost_from_average=False,
                  tpu_hist_precision=precision, tpu_wave_sort_cutoff=256)
    a = _train(dict(pb, tpu_wave_open_levels=0), X, y, rounds=1)
    for name in ("build_histogram_packed", "build_histogram_segments",
                 "build_histogram_multislot"):
        monkeypatch.setattr(hist_pallas, name, interpreted(name))
    monkeypatch.setattr(learner_compact, "build_histogram_packed",
                        hist_pallas.build_histogram_packed)
    params = dict(pb, tpu_wave_open_levels=5)
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    bst.gbdt.learner._use_pallas = True
    assert bst.gbdt.learner._multislot_opening()
    forms = set()
    _spy_formulation(monkeypatch, forms)
    bst.update()
    assert seen["multislot"] == [1, 2, 4, 8, 16]
    assert forms == {"radix" if precision == "bf16x3" else "plain"}
    assert seen["packed"] >= 1 and len(seen["segments"]) >= 2
    # (one grid size a call site at this size: the waves' and the
    # corrections' whole capacities, both under 256 chunks)
    assert len(set(seen["segments"])) == len(seen["segments"]) == 2
    assert a.model_to_string() == bst.model_to_string()


def test_wave_exact_counts():
    X, y = _make(15000)
    _, pb = _pair(bagging_fraction=0.5, bagging_freq=1, seed=9)
    b = _train(pb, X, y, rounds=2)
    b.model_to_string()  # flush lazy assembly
    for t in b.gbdt._models:
        ni = t.num_leaves - 1
        lc = np.asarray(t.internal_count[:ni])
        for nd in range(ni):
            l, r = t.left_child[nd], t.right_child[nd]
            lcnt = t.leaf_count[~l] if l < 0 else t.internal_count[l]
            rcnt = t.leaf_count[~r] if r < 0 else t.internal_count[r]
            assert lc[nd] == lcnt + rcnt


def test_wave_chunked_rows_exact(monkeypatch):
    """The lax.map'd per-row chunk path (large-N transient bound) is
    bit-identical to the single-pass path."""
    X, y = _make(n=8192, f=6)
    pa, pb = _pair(num_leaves=15)
    import lightgbm_tpu.learner_wave as lw
    a = _train(pb, X, y)          # wave, single-pass (n < _row_chunk)
    orig = lw.WaveTPUTreeLearner.__init__

    def patched(self, *args, **kw):
        orig(self, *args, **kw)
        self._row_chunk = 1024    # force Cm > 1

    monkeypatch.setattr(lw.WaveTPUTreeLearner, "__init__", patched)
    b = _train(pb, X, y)
    assert b.gbdt.learner._row_chunk == 1024
    assert a.model_to_string() == b.model_to_string()


# -- the partition's sorts carry only operands that hold information (PR 29) --

def _stable_one_key_sort(key_p, bins_p, w_p, rid_p, lid_p, num_slots):
    """The growth sort as it stood until PR 29, kept as the reference:
    every payload its own operand, one key, stable."""
    fw = bins_p.shape[0]
    ops = ([key_p] + [bins_p[i] for i in range(fw)]
           + [w_p[0], w_p[1], w_p[2], rid_p, lid_p])
    sd = lax.sort(ops, num_keys=1, is_stable=True)
    return (sd[0], jnp.stack(sd[1:1 + fw]), jnp.stack(sd[1 + fw:4 + fw]),
            sd[4 + fw], sd[5 + fw])


def _pending_state(n, fw, seed, slots=1021):
    """Row payloads of a wave about to sort: 12 materialised windows with
    row ids ascending inside each; a third of them untouched, a third split
    (their rows keyed to either child's start, interleaved), a third frozen
    (one key, the two children's node slots interleaved in a shared span)."""
    rng = np.random.RandomState(seed)
    win_of_id = rng.randint(0, 12, n)
    rid = np.argsort(win_of_id, kind="stable").astype(np.int32)
    size = np.bincount(win_of_id, minlength=12)
    start = np.concatenate([[0], np.cumsum(size)[:-1]])
    win = np.repeat(np.arange(12), size)
    right = rng.rand(n) < 0.4
    n_left = np.bincount(win, weights=~right, minlength=12).astype(np.int64)
    split, frozen = win % 3 == 1, win % 3 == 2
    key = 2 * (start[win] + np.where(split & right, n_left[win], 0))
    lid = np.where(split | frozen, 100 + 2 * win + right, win)
    lid[::97] = slots - 1
    w = rng.randn(3, n).astype(np.float32)
    w[2] = rng.rand(n) < 0.7
    bins = rng.randint(0, 2**31 - 1, (fw, n)).astype(np.int32)
    return (key.astype(np.int32), bins, w, rid, lid.astype(np.int32))


@pytest.mark.parametrize("fw", [7, 18])
def test_growth_sort_is_the_stable_one_key_sort(fw):
    from lightgbm_tpu.learner_wave import (growth_sort,
                                           growth_sort_operands)
    assert growth_sort_operands(fw) == fw + 5
    for seed in (0, 1):
        state = _pending_state(5000, fw, seed)
        assert 0.0 < state[2][2].mean() < 1.0      # bag of 0 and 1 mixed
        got = jax.jit(lambda *a: growth_sort(*a, 1021))(*state)
        want = jax.jit(lambda *a: _stable_one_key_sort(*a, 1021))(*state)
        assert not np.array_equal(want[3], state[3])   # the sort moves rows
        for g, w_ in zip(got, want):
            assert g.dtype == w_.dtype and g.shape == w_.shape
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


def test_growth_sort_refuses_node_slots_that_reach_the_bag_bit():
    from lightgbm_tpu.learner_wave import growth_sort
    state = _pending_state(64, 1, 0)
    with pytest.raises(AssertionError):
        growth_sort(*state, (1 << 30) + 1)


@pytest.mark.parametrize("n,bound,packed", [
    (5000, 255, True), (5000, 300, True), (1, 255, True),
    ((1 << 23) + 8, 256, True),     # 24 + 8 bits: the word's top bit is used
    ((1 << 23) + 8, 300, False),    # 24 + 9 bits
    (5000, 1 << 20, False)])        # 13 + 20 bits
def test_to_row_order_packed_word_fallback_and_scatter_agree(n, bound,
                                                             packed):
    from lightgbm_tpu.learner_compact import to_row_order
    rng = np.random.RandomState(n % 1000 + bound % 1000)
    rid = rng.permutation(n).astype(np.int32)
    values = rng.randint(0, bound, n).astype(np.int32)
    values[:2] = (bound - 1, 0)[:min(n, 2)]
    fn = jax.jit(lambda r, v: to_row_order(r, v, bound))
    text = fn.lower(rid, values).as_text()
    assert ("ui32" in text) == packed
    got = fn(rid, values)
    want = jnp.zeros(n, jnp.int32).at[rid].set(values)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _rows_with_a_category(n=30000, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 9)
    X[:, 4] = rng.randint(0, 12, n)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.8 * (X[:, 4] % 3 == 0)
         + 0.3 * rng.randn(n) > 0).astype(float)
    return X, y


_SORT_PATH = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "min_data_in_leaf": 20, "verbosity": -1, "metric": "none",
              "bagging_fraction": 0.7, "bagging_freq": 1, "bagging_seed": 5,
              # a shard of the four-device run holds 7,680 rows: under the
              # default cutoff of 8,192 no window of it would ever sort
              "tpu_wave_sort_cutoff": 256}
_SORT_PATH_RUNS = {
    "serial": {},
    "data4": {"tree_learner": "data", "parallel_mesh": "4"},
    # the opening levels end in ``_materialize_sort``
    "serial-opening": {"tpu_wave_open_levels": 2},
    "serial-opening4": {"tpu_wave_open_levels": 4}}


def _train_on_sort_path(run, rounds=3):
    if run == "data4" and len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    X, y = _rows_with_a_category()
    params = dict(_SORT_PATH, **_SORT_PATH_RUNS[run])
    bst = _train(params, X, y, rounds, categorical_feature=[4])
    assert isinstance(bst.gbdt.learner, WaveTPUTreeLearner)
    return bst


def test_pallas_partition_option_is_accepted_and_ignored():
    """The benchmark's traffic files still pass the key: ``off`` and ``auto``
    are silent (no "Unknown parameter"), ``on`` names what took its place."""
    import warnings

    from lightgbm_tpu.config import Config
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for value in ("off", "auto"):
            Config.from_params({"tpu_wave_pallas_partition": value})
    with pytest.raises(ValueError, match="growth_sort"):
        Config.from_params({"tpu_wave_pallas_partition": "on"})


@pytest.mark.parametrize("run", list(_SORT_PATH_RUNS))
def test_row_ids_ascend_inside_every_materialised_window(run, monkeypatch):
    """What lets ``growth_sort`` break ties by row id without stability:
    after every wave (and after the replay's stall splits) ``rid_p`` ascends
    strictly inside every leaf's materialised span, shared frozen spans
    included, on every device."""
    import lightgbm_tpu.learner_wave as lw
    seen = []

    def note(rid_p, phys_i, split_m, num_nodes):
        leaves = ~np.asarray(split_m)[:int(num_nodes)]
        seen.append((np.asarray(rid_p),
                     np.asarray(phys_i)[:int(num_nodes)][leaves]))

    def spy_on(name, state_of):
        orig = getattr(lw.WaveTPUTreeLearner, name)

        def spied(self, *args, **kw):
            out = orig(self, *args, **kw)
            st = state_of(out)
            jax.debug.callback(note, st.rid_p, st.phys_i, st.split_m,
                               st.num_nodes)
            return out

        monkeypatch.setattr(lw.WaveTPUTreeLearner, name, spied)

    spy_on("_wave_body", lambda st: st)
    spy_on("_materialize_sort", lambda st: st)
    spy_on("_replay", lambda out: out[0])
    bst = _train_on_sort_path(run)
    bst.model_to_string()
    jax.effects_barrier()
    assert len(seen) >= 3 * 5 * (4 if run == "data4" else 1)
    moved = 0
    for rid, spans in seen:
        assert np.array_equal(np.sort(rid), np.arange(rid.shape[0]))
        moved += not np.array_equal(rid, np.arange(rid.shape[0]))
        for s, c in spans:
            assert np.all(np.diff(rid[s:s + c]) > 0), (s, c)
    # rows have moved in most snapshots; a 31-leaf tree opened four levels
    # deep sorts once, late (its one materialisation sort a tree)
    assert moved > (len(seen) // 2 if run != "serial-opening4" else 3)


@pytest.mark.parametrize("run", list(_SORT_PATH_RUNS))
def test_growth_sort_builds_the_stable_sorts_models(run, monkeypatch):
    import lightgbm_tpu.learner_wave as lw
    new = _train_on_sort_path(run).model_to_string()
    calls = []

    def old(*args):
        calls.append(1)
        return _stable_one_key_sort(*args)

    monkeypatch.setattr(lw, "growth_sort", old)
    assert new == _train_on_sort_path(run).model_to_string()
    assert calls
