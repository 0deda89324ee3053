"""Static-analysis subsystem (`lightgbm_tpu/analysis/`).

Covers the gate's passes from both sides (the cost-model ledger and the
resource-lifecycle pass have their own files, test_costmodel.py /
test_resources.py):

  * each pass demonstrably FAILS on its bad input — the lint fixture trips
    every repo rule, the lock fixture has an ABBA cycle and a mixed
    locked/unlocked mutation, toy jaxprs violate the collective budget /
    f64 / callback / baked-constant rules, and a forced retrace trips the
    recompile sentinel;
  * the current tree is GREEN — the repo lint and race passes find
    nothing unsuppressed, every traced program fits its checked-in budget
    (``analysis/budgets.json``), and the CLI gate
    (``python -m lightgbm_tpu.analysis``) exits 0 with a report that
    validates against ``analysis/schema.json``.
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.analysis import (Finding, build_report, load_budgets,
                                   validate_findings_report)
from lightgbm_tpu.analysis import jaxpr_lint, lint, races, recompile
from lightgbm_tpu.analysis.races import LockOrderMonitor

pytestmark = pytest.mark.analysis

_HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(_HERE, "analysis_fixtures")
BAD_LINT = os.path.join(FIXTURES, "bad_lint.py")
BAD_LOCKS = os.path.join(FIXTURES, "bad_locks.py")

ALL_LINT_RULES = {"LGB001-socket-timeout", "LGB002-atomic-write",
                  "LGB003-global-np-random", "LGB004-bare-except",
                  "LGB005-wallclock-in-traced"}


# -- repo lint (lint.py) -----------------------------------------------------

def test_lint_fixture_trips_every_rule():
    kept, suppressed = lint.run(paths=[BAD_LINT], allowlist=[], traced=True)
    assert {f.rule for f in kept} == ALL_LINT_RULES
    assert suppressed == []
    # all three socket-creation shapes are covered
    socket_hits = [f for f in kept if f.rule == "LGB001-socket-timeout"]
    assert len(socket_hits) == 3
    assert all(f.file.endswith("bad_lint.py") and f.line > 0 for f in kept)


def test_lint_repo_clean_with_allowlist():
    """The checked-in tree lints clean; the allowlist suppressions are the
    vetted exceptions, each carrying a reason."""
    kept, suppressed = lint.run()
    assert kept == [], [str(f) for f in kept]
    from lightgbm_tpu.analysis import load_allowlist
    entries = load_allowlist()
    assert all(e.get("reason") for e in entries)
    assert len(suppressed) >= 1        # the allowlist is exercised, not dead


def test_allowlist_suppresses_only_matching_rule():
    allow = [{"rule": "LGB003-global-np-random", "file": "bad_lint.py",
              "reason": "fixture"}]
    kept, suppressed = lint.run(paths=[BAD_LINT], allowlist=allow,
                                traced=True)
    assert "LGB003-global-np-random" not in {f.rule for f in kept}
    assert {f.rule for f in suppressed} == {"LGB003-global-np-random"}
    # the other rules still fire
    assert "LGB004-bare-except" in {f.rule for f in kept}


# -- traced-program lints (jaxpr_lint.py) ------------------------------------

def _shard_psum_program():
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(2)
    kw = dict(mesh=mesh, in_specs=(P("data"),), out_specs=P())
    body = lambda x: lax.psum(x, "data")  # noqa: E731
    fn = jax.shard_map(body, check_vma=False, **kw)
    return jax.make_jaxpr(fn)(jnp.ones(8, jnp.float32))


def test_jaxpr_collective_budget_violation_on_toy_fn():
    closed = _shard_psum_program()
    findings, stats = jaxpr_lint.lint_program(
        "toy", closed, {}, 1 << 20, x64_off=False, file="toy.py")
    assert stats["collectives"].get("psum", 0) >= 1
    assert any(f.rule == "collective-budget" for f in findings)
    # with the site budgeted, the program is clean
    ok, _ = jaxpr_lint.lint_program(
        "toy", closed, {"collectives": stats["collectives"]}, 1 << 20,
        x64_off=False, file="toy.py")
    assert ok == []


def test_jaxpr_f64_leak_flagged_when_x64_off():
    # the test suite runs with x64 ON (conftest), so this trace really
    # contains f64 ops; the lint is told the production config is x64-off
    closed = jax.make_jaxpr(
        lambda x: x.astype(jnp.float64) * 2.0)(jnp.ones(4, jnp.float32))
    findings, stats = jaxpr_lint.lint_program(
        "toy", closed, {}, 1 << 20, x64_off=True, file="toy.py")
    assert stats["f64_ops"] >= 1
    assert any(f.rule == "f64-leak" for f in findings)
    # same trace passes when x64 is legitimately on
    ok, _ = jaxpr_lint.lint_program("toy", closed, {}, 1 << 20,
                                    x64_off=False, file="toy.py")
    assert not any(f.rule == "f64-leak" for f in ok)


def test_jaxpr_host_callback_flagged():
    def fn(x):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct((4,), x.dtype), x)

    closed = jax.make_jaxpr(fn)(jnp.ones(4, jnp.float32))
    findings, _ = jaxpr_lint.lint_program("toy", closed, {}, 1 << 20,
                                          x64_off=False, file="toy.py")
    assert any(f.rule == "host-callback" for f in findings)


def test_jaxpr_baked_constant_ceiling():
    big = jnp.asarray(np.ones(65536, np.float32))    # 256 KB baked in
    closed = jax.make_jaxpr(lambda x: x + big)(jnp.ones(65536, jnp.float32))
    findings, stats = jaxpr_lint.lint_program(
        "toy", closed, {"max_const_bytes": 1024}, 1 << 20, x64_off=False,
        file="toy.py")
    assert stats["const_bytes"] >= big.nbytes
    assert any(f.rule == "baked-constants" for f in findings)


def test_jaxpr_repo_programs_within_checked_in_budgets():
    """The real program set (serial wave tree step, sharded learners,
    serving binner + traversal) traces within analysis/budgets.json."""
    findings, stats, skipped = jaxpr_lint.run()
    assert findings == [], [str(f) for f in findings]
    assert {"wave_serial", "serving_bin", "serving_traverse"} <= set(stats)
    if len(jax.devices()) >= 2:
        assert {"wave_sharded_data", "wave_sharded_voting",
                "wave_feature"} <= set(stats)
        assert skipped == {}
        # the sharded wave program really exchanges something; the budget
        # file pins those counts explicitly
        budgets = load_budgets()["programs"]
        assert stats["wave_sharded_data"]["collectives"] == \
            budgets["wave_sharded_data"]["collectives"]
        assert sum(stats["wave_sharded_data"]["collectives"].values()) > 0
    # the serial/serving programs are collective- and callback-free
    for name in ("wave_serial", "serving_bin", "serving_traverse"):
        assert stats[name]["collectives"] == {}
        assert stats[name]["banned"] == []


# -- recompile sentinel (recompile.py) ---------------------------------------

def test_recompile_sentinel_detects_forced_retrace():
    fn = jax.jit(lambda x: x * 2.0)
    if recompile.jit_cache_size(fn) is None:
        pytest.skip("jax version exposes no jit cache introspection")
    fn(jnp.ones(4))
    s = recompile.RecompileSentinel()
    s.register("toy", fn, "toy.py")
    s.arm()
    fn(jnp.ones(4))                      # warmed shape: no retrace
    assert s.check() == []
    fn(jnp.ones(8))                      # new shape: forced retrace
    findings = s.check()
    assert len(findings) == 1 and findings[0].rule == "retrace"
    assert "toy" in findings[0].message


def test_recompile_sentinel_serving_warm_path():
    """The serving-bucket invariant from
    test_serving.py::test_zero_recompiles_within_bucket, enforced by the
    sentinel without a server: warmed buckets never compile, an unwarmed
    bucket is caught as a retrace."""
    from lightgbm_tpu.predictor import _predict_all
    from lightgbm_tpu.serving.binner import _bin_device
    from lightgbm_tpu.serving.registry import ServingModel

    if recompile.jit_cache_size(_bin_device) is None:
        pytest.skip("jax version exposes no jit cache introspection")
    bst = recompile._tiny_booster(iters=2)
    model = ServingModel(bst)
    model.warm([32])
    s = recompile.RecompileSentinel()
    s.register("serving_bin", _bin_device, "lightgbm_tpu/serving/binner.py")
    s.register("serving_traverse", _predict_all, "lightgbm_tpu/predictor.py")
    s.arm()
    for m in (1, 16, 32):                # distinct in-bucket row counts
        model.predict_padded(np.zeros((32, model.num_features)), m)
    assert s.check() == []
    model.predict_padded(np.zeros((64, model.num_features)), 1)  # unwarmed
    assert {f.symbol for f in s.check()} == {"serving_bin",
                                             "serving_traverse"}


def test_recompile_gate_pass_green():
    findings, detail, skip = recompile.run()
    if skip:
        pytest.skip(skip)
    assert findings == [], [str(f) for f in findings]
    assert any(k.startswith("train_step") for k in detail)
    assert "serving_bin" in detail and "serving_traverse" in detail


# -- race detector (races.py) ------------------------------------------------

def test_races_fixture_cycle_and_mixed_mutation():
    kept, _ = races.run(paths=[BAD_LOCKS], allowlist=[])
    rules = {f.rule for f in kept}
    assert rules == {"lock-order-cycle", "unlocked-mutation"}
    cyc = next(f for f in kept if f.rule == "lock-order-cycle")
    assert "Left._lock" in cyc.message and "Right._lock" in cyc.message
    mix = next(f for f in kept if f.rule == "unlocked-mutation")
    assert "Mixed.total" in mix.message


def test_races_repo_clean():
    kept, _ = races.run()
    assert kept == [], [str(f) for f in kept]


def test_races_sees_real_cross_class_edge():
    """Sanity that the pass actually resolves the serving lock web: the
    server's batcher registry holds _batcher_lock while calling
    ModelRegistry.get (which takes the registry lock) — an edge, not a
    cycle."""
    rep = races.analyze()
    graph = rep.graph()
    src = "server.PredictionServer._batcher_lock"
    assert any("ModelRegistry._lock" in dst
               for dst in graph.get(src, ())), graph


def test_runtime_lock_monitor_detects_inversion():
    mon = LockOrderMonitor()
    a, b = mon.make_lock("a"), mon.make_lock("b")

    def order_ab():
        with a:
            with b:
                pass

    def order_ba():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=order_ab)
    t1.start()
    t1.join()
    assert mon.violations == []          # one ordering alone is fine
    t2 = threading.Thread(target=order_ba)
    t2.start()
    t2.join()
    assert len(mon.violations) == 1      # inversion caught WITHOUT deadlock
    v = mon.violations[0]
    assert {v["held"], v["acquiring"]} == {"a", "b"}
    assert mon.findings()[0].rule == "runtime-lock-order"


# -- report schema + CLI gate ------------------------------------------------

def test_findings_report_validates_and_rejects():
    f = Finding("lint", "LGB001-socket-timeout", "x.py", "msg", line=3)
    rep = build_report({"lint": {"status": "findings", "findings": 1}}, [f])
    assert validate_findings_report(rep) == []
    del rep["summary"]
    assert validate_findings_report(rep) != []


def test_gate_exit_codes(monkeypatch):
    from lightgbm_tpu.analysis import __main__ as gate

    assert gate.main(["--passes", "lint,races", "--quiet"]) == 0
    monkeypatch.setattr(
        gate.lint, "run",
        lambda paths=None: (
            [Finding("lint", "LGB004-bare-except", "x.py", "boom")], []))
    assert gate.main(["--passes", "lint", "--quiet"]) == 1


@pytest.mark.analysis(timeout=600)
def test_gate_cli_end_to_end(tmp_path):
    """`python -m lightgbm_tpu.analysis --json` in a fresh process (x64
    OFF — the production config, where the f64 rule is live): exits 0 on
    the current tree, writes a schema-valid report covering all eight
    passes + the allowlist-staleness check, and its passes stay inside
    the ~90s pre-merge time budget."""
    repo_root = os.path.dirname(_HERE)
    out = tmp_path / "analysis.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_ENABLE_X64", None)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    # JAX_COMPILATION_CACHE_DIR is inherited (conftest.py places it)
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis", "--json", str(out)],
        cwd=repo_root, env=env, capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(out.read_text())
    assert validate_findings_report(rep) == []
    assert rep["summary"]["total"] == 0
    assert set(rep["passes"]) == {"allowlist", "lint", "races", "resources",
                                  "spmd", "donation", "jaxpr", "costmodel",
                                  "recompile"}
    for name, res in rep["passes"].items():
        assert res["status"] in ("ok", "skipped"), (name, res)
        # per-pass wall time lands in the report AND on stdout
        assert res["seconds"] >= 0, (name, res)
    assert "per-pass wall time:" in proc.stdout
    # the full eight-pass gate stays a pre-merge check, not a CI tier
    # (warm persistent compile cache: ~50s measured; budget ~90s).  Judged
    # by the seconds the passes report themselves, summed: the wall clock
    # of this subprocess counts start-up and whatever the five other
    # workers of a tier-1 run are doing to the machine (79 s alone, over 90
    # in the driver's run of PR 28's tree)
    spent = sum(res["seconds"] for res in rep["passes"].values())
    assert spent < 90.0, f"the gate's passes took {spent:.1f}s"
    assert rep["environment"]["x64_enabled"] is False
    # the jaxpr pass really traced the serving + training programs, and
    # the shared trace cache reported per-program timings (schema v2)
    progs = rep["passes"]["jaxpr"]["programs"]
    assert "wave_serial" in progs
    assert all(p["trace_seconds"] >= 0 for p in progs.values())
    # the cost ledger measured every traced program against costs.json
    rows = rep["passes"]["costmodel"]["programs"]
    assert set(rows) == set(progs)
    assert all(r["flops"] > 0 and r["bytes_accessed"] > 0
               and r["peak_live_bytes"] > 0 for r in rows.values())
    # the round-8 wire-tier claim is visible in the ledger itself: the
    # quantized data-sharded exchange is about HALF the f32 program's
    f32 = sum(rows["wave_sharded_data"]["exchange_bytes"].values())
    quant = sum(rows["wave_sharded_data_quant"]["exchange_bytes"].values())
    assert 0 < quant < f32
    # the donation pass proved HLO aliasing for every donating program
    assert "aliased" in rep["passes"]["donation"]["detail"]
    assert "missing" not in rep["passes"]["donation"]["detail"]


def test_gate_changed_only_scopes_and_falls_back(tmp_path):
    """--changed-only REF narrows the AST file sets and the traced-program
    set to the git diff; an unresolvable ref falls back to the full gate
    rather than silently skipping passes."""
    from lightgbm_tpu.analysis import __main__ as gate

    # AST passes against HEAD: whatever the working tree holds, the
    # scoped sets are a subset of the full scan and the gate stays green
    assert gate.main(["--passes", "lint,races,resources",
                      "--changed-only", "HEAD", "--quiet"]) == 0
    # a bogus ref must not crash or skip — it degrades to the full gate
    assert gate.main(["--passes", "lint,races,resources",
                      "--changed-only", "no-such-ref-xyzzy",
                      "--quiet"]) == 0
    changed = gate._changed_files("no-such-ref-xyzzy")
    assert changed is None


def test_trace_programs_changed_only_narrowing():
    """The traced set honors the --changed-only narrowing: programs whose
    source file is outside the diff are skipped with an auditable
    reason, not silently dropped."""
    tp = jaxpr_lint.trace_programs(only={"serving_bin"})
    assert set(tp.closed) == {"serving_bin"}
    assert all("--changed-only" in reason
               for name, reason in tp.skipped.items())
    assert set(tp.closed) | set(tp.skipped) == \
        set(jaxpr_lint.PROGRAM_FILES)


# -- allowlist staleness (always-on gate check) ------------------------------

def test_stale_allowlist_detects_rot(tmp_path):
    from lightgbm_tpu.analysis import stale_allowlist_findings

    good = {"rule": "LGB004-bare-except",
            "file": "lightgbm_tpu/analysis/lint.py", "symbol": "run",
            "reason": "x"}
    gone_file = {"rule": "r", "file": "lightgbm_tpu/no_such_module.py",
                 "reason": "x"}
    gone_sym = {"rule": "r", "file": "lightgbm_tpu/analysis/lint.py",
                "symbol": "renamed_away_fn", "reason": "x"}
    no_file = {"rule": "r", "reason": "x"}
    fs = stale_allowlist_findings([good, gone_file, gone_sym, no_file])
    assert len(fs) == 3
    assert all(f.rule == "stale-allowlist" for f in fs)
    assert all(f.file == "analysis/allowlist.json" for f in fs)
    msgs = " | ".join(f.message for f in fs)
    assert "no_such_module.py" in msgs
    assert "renamed_away_fn" in msgs
    assert "names no file" in msgs


def test_checked_in_allowlist_resolves_clean():
    """Every vetted exception still points at a real file and symbol."""
    from lightgbm_tpu.analysis import stale_allowlist_findings
    fs = stale_allowlist_findings()
    assert fs == [], [str(f) for f in fs]
