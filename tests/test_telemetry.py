"""Unified training telemetry (`lightgbm_tpu/observability/`).

Covers the observability contract from three sides:

  * ``telemetry=False`` is a NO-OP on the hot path — the wave tree
    program traces the exact same jaxpr as before the subsystem existed
    (the device counter lane is None), and neither mode emits host
    callbacks.
  * ``telemetry=True`` produces a JSON report that validates against the
    checked-in schema (observability/schema.json) with per-phase wall
    timings, wave/stall counters decoded from the async record flush,
    memory gauges that AGREE with the wave budget gate, and collective
    accounting for the sharded learners.
  * the round-5 advisor's high-severity finding: the batched stall gate
    must read REPLICATED spans (pmax seam) so row-sharded learners cannot
    diverge when a leaf's local span straddles the vectorized-partition
    cap on only some shards.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.learner_wave import (WaveTPUTreeLearner,
                                       wave_transient_bytes)
from lightgbm_tpu.observability import load_schema, validate_report


def _problem(rng, n=2048, f=4):
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.2 * rng.randn(n) > 0).astype(float)
    return X, y


_BASE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbosity": -1}


# -- report content + schema (tier-1 smoke, satellite: CI/tooling) ----------

def test_report_schema_smoke(rng):
    """2-iteration train with telemetry=True: the report validates against
    the checked-in schema and carries per-phase timings and stall/extras
    counters."""
    X, y = _problem(rng)
    params = dict(_BASE, telemetry=True)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(2):
        bst.update()
    rep = bst.get_telemetry()
    assert validate_report(rep, load_schema()) == []
    assert rep["enabled"] is True
    # per-phase wall timings
    for phase in ("binning", "iteration", "dispatch"):
        assert phase in rep["phases"], rep["phases"].keys()
        assert rep["phases"][phase]["count"] >= 1
        assert rep["phases"][phase]["total_ms"] >= 0.0
    assert rep["iterations"]["count"] == 2
    # decoded per-tree wave counters
    c = rep["counters"]
    assert c["trees_measured"] == 2
    assert c["waves"] >= 2
    assert c["pops"] >= 2
    assert c["total_splits"] == c["grow_splits"] + c["stall_splits"]
    for key in ("stall_splits", "stall_extras", "stall_events"):
        assert c[key] >= 0
    # memory gauge present and equal to the budget gate's own estimate
    gw = rep["gauges"]["wave_working_set"]
    learner = bst.gbdt.learner
    expect = wave_transient_bytes(learner.cfg, learner._rows_len(),
                                  learner.fw * 4, learner._hist_nbins)
    assert gw == expect
    # serial learner: no collectives, but the section exists
    assert rep["collectives"]["sites"] == []


def test_materialisation_sort_is_counted(rng):
    """``wave_sorts`` counts the opening's one materialisation sort with the
    waves' own: at 2,048 rows every window is under the sort cut-off, so a
    tree without the opening sorts nothing and a tree with it sorts once."""
    X, y = _problem(rng)
    sorts = {}
    for levels in (0, 2):
        params = dict(_BASE, telemetry=True, tpu_learner="wave",
                      tpu_wave_open_levels=levels)
        bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
        for _ in range(2):
            bst.update()
        assert bst.gbdt.learner.open_levels == levels
        c = bst.get_telemetry()["counters"]
        assert c["trees_measured"] == 2
        sorts[levels] = c["wave_sorts"]
    assert sorts == {0: 0, 2: 2}


def test_disabled_report_is_inert(rng):
    X, y = _problem(rng)
    ds = lgb.Dataset(X, label=y, params=dict(_BASE))
    bst = lgb.Booster(dict(_BASE), ds)
    bst.update()
    rep = bst.get_telemetry()
    assert validate_report(rep) == []
    assert rep["enabled"] is False
    assert rep["iterations"]["count"] == 0
    assert rep["counters"]["trees_measured"] == 0


def test_telemetry_out_writes_valid_report(rng, tmp_path):
    """engine.train with telemetry_out writes the schema-valid JSON file
    (the CLI --telemetry-out flag resolves to these params)."""
    X, y = _problem(rng)
    out = tmp_path / "telemetry.json"
    params = dict(_BASE, telemetry=True, telemetry_out=str(out))
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=2, verbose_eval=False)
    rep = json.loads(out.read_text())
    assert validate_report(rep) == []
    assert rep["iterations"]["count"] == 2


def test_cli_flag_tokens_resolve():
    from lightgbm_tpu.cli import _load_params
    p = _load_params(["task=train", "--telemetry-out=rep.json"])
    assert p["telemetry_out"] == "rep.json"
    p = _load_params(["--telemetry-out", "rep.json", "data=train.txt"])
    assert p["telemetry_out"] == "rep.json"
    assert p["data"] == "train.txt"
    p = _load_params(["--telemetry"])
    assert p["telemetry"] == "true"


def test_record_telemetry_callback(rng):
    X, y = _problem(rng)
    params = dict(_BASE, telemetry=True)
    seen = {}
    lgb.train(params, lgb.Dataset(X, label=y, params=params),
              num_boost_round=3, verbose_eval=False,
              callbacks=[lgb.record_telemetry(seen)])
    assert seen["enabled"] is True
    assert seen["iterations"]["count"] >= 2   # light report lags <= 1 iter
    assert validate_report(seen) == []


# -- telemetry=False is a hot-path no-op ------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for s in vs:
                inner = getattr(s, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)
                elif hasattr(s, "eqns"):
                    yield from _iter_eqns(s)


def _tree_jaxpr(params, X, y, rng):
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    learner = WaveTPUTreeLearner(Config.from_params(params), ds.constructed)
    n_pad = ds.constructed.num_data_padded
    z = jnp.zeros(n_pad, jnp.float32)
    fmask = jnp.ones(learner.num_features, bool)
    return jax.make_jaxpr(learner._train_tree_wave)(
        learner.bins_packed(), z, z, z, fmask)


def test_disabled_telemetry_adds_no_ops(rng):
    """telemetry=False traces the same op count as another disabled build;
    telemetry=True adds only pure device counter ops (more eqns, one more
    output, still ZERO host-callback/infeed/outfeed primitives)."""
    X, y = _problem(rng)
    off1 = _tree_jaxpr(dict(_BASE), X, y, rng)
    off2 = _tree_jaxpr(dict(_BASE), X, y, rng)
    on = _tree_jaxpr(dict(_BASE, telemetry=True), X, y, rng)
    n_off1 = sum(1 for _ in _iter_eqns(off1.jaxpr))
    n_off2 = sum(1 for _ in _iter_eqns(off2.jaxpr))
    n_on = sum(1 for _ in _iter_eqns(on.jaxpr))
    assert n_off1 == n_off2
    assert len(off1.jaxpr.outvars) == 5
    assert len(on.jaxpr.outvars) == 6
    assert n_on > n_off1          # counters exist only in the enabled trace
    banned = ("callback", "infeed", "outfeed", "host")
    for jx in (off1, on):
        for eqn in _iter_eqns(jx.jaxpr):
            name = eqn.primitive.name
            assert not any(b in name for b in banned), name


# -- sharded learners: collective accounting + the replicated stall gate ----

pytestmark_multi = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs a multi-device (virtual) mesh")


@pytestmark_multi
def test_sharded_collectives_accounted(rng):
    from lightgbm_tpu.parallel.learners import apply_parallel_sharding
    from lightgbm_tpu.parallel.mesh import make_mesh
    X, y = _problem(rng, n=2048, f=8)
    params = dict(_BASE, telemetry=True, tree_learner="data")
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    apply_parallel_sharding(bst.gbdt, make_mesh(), "data")
    for _ in range(2):
        bst.update()
    rep = bst.get_telemetry()
    assert validate_report(rep) == []
    sites = rep["collectives"]["sites"]
    ops = {s["op"] for s in sites}
    assert "psum_scatter" in ops and "all_gather" in ops, sites
    phases = {s["phase"] for s in sites}
    assert "grow_wave" in phases, phases
    assert all(s["bytes_per_call"] > 0 for s in sites)
    # the dynamic estimate combines sites with the decoded counters
    est = rep["collectives"]["per_tree_estimate"]
    assert est["count"] is None or est["count"] > 0


@pytestmark_multi
def test_stall_batch_gate_replicated_across_devices(rng):
    """Round-5 advisor (high): local spans straddling the vectorized
    partition cap on only SOME shards must not diverge the trees.

    Construction: gradients are zero on the lower half of the rows and
    feature 0 is the row index, so every split (and every replay stall)
    lands in rows owned by the LAST shard — the other shard sees local
    spans of 0 (under the cap) while the owner and the serial learner see
    the real over-cap spans.  With a device-local gate the zero-span
    shards wrongly include the extras, diverging num_nodes/split_m and
    the whole replicated replay (observed as record mismatch or a
    collective deadlock); the pmax seam makes the gate replicated.  The
    cap is shrunk via tpu_wave_vec_cap so the gate is exercised at CI
    size — the serial run's stall counters assert that."""
    from lightgbm_tpu.parallel.mesh import make_mesh
    from lightgbm_tpu.parallel.wave_sharded import ShardedWaveLearner

    n, f = 4096, 6
    X = np.empty((n, f))
    X[:, 0] = np.arange(n)           # leaves = contiguous row ranges
    X[:, 1:] = rng.randn(n, f - 1)
    y = (rng.rand(n) > 0.5).astype(float)
    params = dict(_BASE, num_leaves=31, enable_bundle=False,
                  telemetry=True, tpu_wave_stall_batch=4,
                  tpu_wave_vec_cap=128, tpu_wave_overshoot=0.0,
                  tpu_wave_sort_cutoff=256, tpu_sort_cutoff=256)
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    data = ds.constructed
    cfg = Config.from_params(params)
    n_pad = data.num_data_padded
    g = rng.randn(n_pad).astype(np.float32)
    g[:n // 2] = 0.0                 # all structure on the last shard
    grad = jnp.asarray(g)
    hess = jnp.ones(n_pad, jnp.float32) * 0.25
    bag = jnp.zeros(n_pad, jnp.float32).at[:n].set(1.0)

    serial = WaveTPUTreeLearner(cfg, data)
    rf_s, ri_s = [np.asarray(a)
                  for a in serial.train_async(grad, hess, bag)[:2]]
    tel = np.asarray(serial.take_telemetry())
    from lightgbm_tpu.observability.telemetry import (
        TEL_GROW_SPLITS, TEL_POPS, TEL_STALL_EXTRAS, TEL_STALL_SPLITS,
        TEL_TOTAL_SPLITS, TEL_WAVES)
    assert tel[TEL_STALL_SPLITS] > 0, \
        "problem no longer stalls — the gate is not exercised"

    sharded = ShardedWaveLearner(cfg, data, make_mesh(2))
    out = sharded.train_async(grad, hess, bag)
    np.testing.assert_allclose(np.asarray(out[0]), rf_s, rtol=2e-4,
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(out[1]), ri_s)
    # the REPLICATED counter slots match serial exactly (a diverged gate
    # shows up first as mismatched stall/extras counts); frozen/sort
    # counters are intentionally per-device window geometry
    tel_d = np.asarray(sharded.take_telemetry())
    rep_slots = [TEL_WAVES, TEL_GROW_SPLITS, TEL_STALL_SPLITS,
                 TEL_STALL_EXTRAS, TEL_POPS, TEL_TOTAL_SPLITS]
    np.testing.assert_array_equal(tel_d[rep_slots], tel[rep_slots])


@pytestmark_multi
def test_stall_batch_hist_single_collective(rng):
    """The batched stall correction exchanges ONE stacked (K, F, B, 3)
    reduce-scatter per event (satellite: was K per-member collectives in
    the non-Pallas sharded path) — visible in the lowered HLO as a rank-4
    site with leading dim K, distinct from the wave exchange's W/8."""
    import re
    from lightgbm_tpu.parallel.mesh import make_mesh
    from lightgbm_tpu.parallel.wave_sharded import ShardedWaveLearner

    X, y = _problem(rng, n=4096, f=8)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5, "enable_bundle": False}
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    cfg = Config.from_params(params)
    learner = ShardedWaveLearner(cfg, ds.constructed, make_mesh())
    hlo = learner.lowered_hlo_text()
    shapes = [tuple(int(x) for x in m.group(1).split(","))
              for m in re.finditer(
                  r"= f32\[([\d,]+)\][^\n]*? reduce-scatter\(", hlo)]
    k = learner._stall_batch
    assert k > 1
    stall_sites = [s for s in shapes if len(s) == 4 and s[0] == k]
    assert stall_sites, (shapes, k)


# -- schema v7: provenance + sampled-sync runtime attribution ---------------

def test_provenance_block(rng):
    """Every enabled report carries the required who-produced-this block:
    platform, jax version, host layout, the emulated flag (True off-TPU)
    and the GBDT-known extras (tree_learner, learner class)."""
    X, y = _problem(rng)
    params = dict(_BASE, telemetry=True)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    bst.update()
    rep = bst.get_telemetry()
    assert validate_report(rep) == []
    prov = rep["provenance"]
    assert prov["jax_version"] == jax.__version__
    assert prov["num_devices"] == jax.device_count()
    assert prov["emulated"] == (jax.devices()[0].platform != "tpu")
    assert prov["tree_learner"] == "serial"
    assert prov["learner"] == type(bst.gbdt.learner).__name__
    # schema v11: the provenance block pins the exact static cost ledger
    # (analysis/costs.json) the run was gated against
    import hashlib
    from lightgbm_tpu.analysis.common import COSTS_PATH
    with open(COSTS_PATH, "rb") as fh:
        want = hashlib.sha256(fh.read()).hexdigest()
    assert prov["cost_ledger_sha256"] == want
    # the disabled report has one too (schema: required section)
    ds2 = lgb.Dataset(X, label=y, params=dict(_BASE))
    bst2 = lgb.Booster(dict(_BASE), ds2)
    bst2.update()
    assert "provenance" in bst2.get_telemetry()


def test_sampled_sync_attribution_coverage(rng):
    """telemetry_sync_every=1: every iteration is bracketed with forced
    syncs and the per-leg table must account for the measured iteration
    wall within the acceptance bar (|1 - coverage| <= 0.1)."""
    X, y = _problem(rng, n=4096)
    params = dict(_BASE, telemetry=True, telemetry_sync_every=1)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(6):
        bst.update()
    rep = bst.get_telemetry()
    assert validate_report(rep) == []
    dist = rep["distributed"]
    assert dist["sync_every"] == 1
    table = dist["attribution"]
    assert table["sampled_iterations"] == 6
    assert table["legs_ms"], table
    assert abs(1.0 - table["coverage"]) <= 0.1, table
    assert table["legs_sum_ms"] == pytest.approx(
        sum(table["legs_ms"].values()))
    # memory watermarks ride the same section (devices may be empty on
    # backends without memory_stats — the KEY must exist)
    assert "devices" in dist["memory"]


def test_no_sync_phases_without_sampling(rng):
    """With telemetry on but telemetry_sync_every unset, no iteration pays
    the forced-sync bracket: no sync.* phases, no attribution table."""
    X, y = _problem(rng)
    params = dict(_BASE, telemetry=True)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(3):
        bst.update()
    rep = bst.get_telemetry()
    assert not [p for p in rep["phases"] if p.startswith("sync.")]
    assert "attribution" not in rep["distributed"]


def test_training_prometheus_renders(rng):
    from lightgbm_tpu.observability.metrics_export import training_prometheus
    X, y = _problem(rng, n=4096)
    params = dict(_BASE, telemetry=True, telemetry_sync_every=2)
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)
    for _ in range(4):
        bst.update()
    text = training_prometheus(bst.get_telemetry())
    assert "lgbt_training_iterations_total 4" in text
    assert "lgbt_training_phase_iteration_total_seconds" in text
    assert "lgbt_training_iteration_mean_ms" in text
    # sampled-sync legs + coverage ride the same page
    assert "lgbt_training_leg_ms:" in text
    assert "lgbt_training_attribution_coverage" in text
    # well-formed exposition: every non-comment line is "name value"
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            name, val = ln.rsplit(" ", 1)
            float(val)


def test_telemetry_off_model_bit_identical(rng):
    """The whole observability layer is a no-op when disabled: the same
    problem trains to a BYTE-identical model text with telemetry (and
    sampling) on vs off."""
    X, y = _problem(rng)
    texts = {}
    for tel in (False, True):
        params = dict(_BASE, telemetry=tel, bagging_fraction=0.8,
                      bagging_freq=1, feature_fraction=0.9, seed=3)
        if tel:
            params["telemetry_sync_every"] = 2
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.Booster(params, ds)
        for _ in range(5):
            bst.update()
        texts[tel] = bst.model_to_string()
    assert texts[False] == texts[True]


# -- wave budget: batched-correction transient (satellite) ------------------

def test_wave_budget_counts_stall_vec_transient():
    cfg = Config.from_params({"num_leaves": 255, "tpu_wave_stall_batch": 4})
    n_pad, f_pad, b = 1 << 20, 32, 256
    bb = wave_transient_bytes(cfg, n_pad, f_pad, b)
    # k (not k-1) slices since round 6: the fused-top correction path
    # (tpu_wave_stall_fuse_top) stacks every member's slice
    k, cap = 4, WaveTPUTreeLearner._VEC_CAP
    assert bb["stall_vec_bytes"] == \
        k * min(cap, n_pad) * (f_pad // 4 + 4) * 4
    assert bb["total_bytes"] == sum(v for kk, v in bb.items()
                                    if kk != "total_bytes")
    # K=1 has no vectorized extras stage
    cfg1 = Config.from_params({"num_leaves": 255, "tpu_wave_stall_batch": 1})
    assert wave_transient_bytes(cfg1, n_pad, f_pad, b)["stall_vec_bytes"] == 0
    # a shrunken vec cap shrinks the transient accordingly
    cfg_s = Config.from_params({"num_leaves": 255, "tpu_wave_stall_batch": 4,
                                "tpu_wave_vec_cap": 1024})
    assert wave_transient_bytes(cfg_s, n_pad, f_pad, b)["stall_vec_bytes"] \
        == k * 1024 * (f_pad // 4 + 4) * 4
    # wide datasets: the transient scales with the word count, the round-5
    # advisor's concern — hundreds of columns make it budget-material
    bb_wide = wave_transient_bytes(cfg, n_pad, 1024, b)
    assert bb_wide["stall_vec_bytes"] > 100 * 2**20
