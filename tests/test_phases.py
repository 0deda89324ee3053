"""The program names its own work (`lightgbm_tpu/observability/phases.py`):
phase scopes on the device operations of the fused step, a pinned name on
every Pallas kernel, and host spans in the profiler's own trace that need no
``telemetry``.

What the chip's compiler makes of the scopes and names is in
``tests/test_chip_compile.py``; what a trace of the chip reads off them is in
``benchmark/tests/test_program_trace.py``.
"""

import contextlib
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability import TraceRecorder, phases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BASE = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbosity": -1}


def _problem(rng, n=2048, f=4):
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.2 * rng.randn(n) > 0).astype(float)
    return X, y


def _booster(rng, **extra):
    X, y = _problem(rng)
    params = dict(_BASE, **extra)
    return lgb.Booster(params, lgb.Dataset(X, label=y, params=params))


def _step_args(g):
    return (g.train_score.score, g.learner.bins_packed(), g._bag_mask,
            g._feature_sample(), jnp.float32(0.1))


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for s in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(s, "jaxpr", s)
                if hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)


# -- the names are data the benchmark reads -----------------------------------

def test_benchmark_names_equal_the_programs():
    with open(os.path.join(ROOT, "benchmark", "phases.json")) as fh:
        data = json.load(fh)
    assert data["span_prefix"] == phases.SPAN_PREFIX
    assert tuple(data["device_phases"]) == phases.DEVICE_PHASES
    assert tuple(data["device_stages"]) == phases.DEVICE_STAGES
    assert tuple(data["host_spans"]) == phases.HOST_SPANS
    assert tuple(data["kernel_names"]) == phases.KERNEL_NAMES
    with pytest.raises(ValueError):
        phases.scope("not_a_phase")


# -- device: phase scopes -----------------------------------------------------

def test_fused_step_sorts_sit_under_phases_and_every_phase_occurs(rng):
    """The compiled fused step (CPU, two opening levels so that the opening
    exists): every ``sort`` has one of the program's phases in its
    ``op_name``, and every scope name occurs in some operation's."""
    g = _booster(rng, tpu_wave_open_levels=2).gbdt
    assert g._can_fuse()
    text = g._fused_iter_fn().lower(*_step_args(g)).compile().as_text()
    sorts = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.split("\n") if re.search(r"[ )]sort\(", line)]
    assert len(sorts) >= 5, sorts
    for op_name in sorts:
        assert set(op_name.split("/")) & set(phases.DEVICE_PHASES), op_name
    seen = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        seen.update(op_name.split("/"))
    assert set(phases.DEVICE_SCOPES) <= seen, \
        set(phases.DEVICE_SCOPES) - seen
    # the full-array sorts: the partition's, and emit's back to row order
    # (the opening's keys wait for the first growth wave's sort)
    assert any("/grow/" in s and "/partition/" in s for s in sorts)
    assert not any("/opening/" in s for s in sorts)
    assert any(s.endswith("/emit/sort") for s in sorts)


@pytest.mark.parametrize("learner", ["wave", "compact"])
def test_scopes_add_no_equation_and_no_output(rng, monkeypatch, learner):
    """A scope is metadata: the fused step traces to the same number of
    equations and outputs with ``jax.named_scope`` taken away."""
    def trace():
        g = _booster(np.random.RandomState(42), tpu_learner=learner).gbdt
        jx = jax.make_jaxpr(g._fused_iter_fn())(*_step_args(g))
        return sum(1 for _ in _iter_eqns(jx.jaxpr)), len(jx.jaxpr.outvars)

    with_scopes = trace()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert trace() == with_scopes
    assert with_scopes[1] == 4      # score and the three record arrays


# -- device: kernel names -----------------------------------------------------

# kept in KERNEL_NAMES for ``benchmark/phases.json`` alone (see phases.py)
_NO_CALL_SITE = ("apply_partition_permute",)


def _kernel_calls():
    from lightgbm_tpu.ops import hist_pallas, scan_pallas
    n, fw, f, b, k = 2048, 2, 8, 256, 2
    bins = jnp.zeros((fw, n), jnp.int32)
    w = jnp.zeros((3, n), jnp.float32)
    rows = jnp.zeros(n, jnp.int32)
    chunk = jnp.zeros(4, jnp.int32)
    hist = jnp.zeros((k, f, b, 3), jnp.float32)
    leaf = jnp.zeros(k, jnp.float32)
    meta = jnp.zeros(f, jnp.int32)
    fmask = jnp.ones(f, bool)
    return {
        "build_histogram_pallas": lambda: hist_pallas.build_histogram_pallas(
            jnp.zeros((8, n), jnp.uint8), w, num_bins=b),
        "build_histogram_packed": lambda: hist_pallas.build_histogram_packed(
            bins, w, num_bins=b),
        "build_histogram_segments":
            lambda: hist_pallas.build_histogram_segments(
                bins, w, rows, chunk, chunk, chunk, num_bins=b, n_slots=k),
        "build_histogram_multislot":
            lambda: hist_pallas.build_histogram_multislot(
                bins, w, rows, num_bins=b, n_slots=k),
        "find_best_splits_batched":
            lambda: scan_pallas.find_best_splits_batched(
                hist, leaf, leaf, leaf, meta, meta, meta, fmask),
        "fused_child_scans": lambda: scan_pallas.fused_child_scans(
            jnp.zeros((1, f, b, 3), jnp.float32),
            jnp.zeros((1, f, b, 3), jnp.float32), jnp.ones(1, bool),
            leaf, leaf, leaf, meta, meta, meta, fmask),
    }


@pytest.mark.parametrize("kernel", [k for k in phases.KERNEL_NAMES
                                    if k not in _NO_CALL_SITE])
def test_pallas_call_carries_its_pinned_name(kernel):
    """Every ``pl.pallas_call`` names its kernel: the trace and the Mosaic
    dump then show the kernel under a name a refactor of the enclosing jit
    does not change (``benchmark/kernels/*.json`` looks for these)."""
    jx = jax.make_jaxpr(_kernel_calls()[kernel])()
    names = {eqn.params["name"] for eqn in _iter_eqns(jx.jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert names == {kernel}


def test_every_pallas_call_site_is_named():
    """No ``pallas_call`` in ``ops/`` without a ``name=`` from the list."""
    found = []
    for path in sorted(glob.glob(os.path.join(ROOT, "lightgbm_tpu", "ops",
                                              "*.py"))):
        src = open(path).read()
        for m in re.finditer(r"pl\.pallas_call\(", src):
            call = src[m.end():src.index(")(", m.end())]
            named = re.search(r'\bname="([^"]+)"', call)
            assert named, f"{path}: a pallas_call without name="
            found.append(named.group(1))
    assert sorted(found + list(_NO_CALL_SITE)) == sorted(phases.KERNEL_NAMES)


# -- host: spans on the profiler's clock --------------------------------------

def _lgbt_events(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(phases.SPAN_PREFIX):
                    out.append((ev.name[len(phases.SPAN_PREFIX):],
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_profiler_trace_holds_the_spans_without_telemetry(rng, tmp_path):
    """Three tiny iterations, ``telemetry`` off.  Inside a profiler session
    the spans are events of the profiler's own trace, nested and with their
    arguments; with no session (and no recorder) nothing is recorded
    anywhere."""
    g = _booster(rng, tpu_pipeline_flush_depth=1)
    g.update()                                  # compile outside the trace
    tel = g.gbdt.telemetry
    assert not tel.enabled and tel.tracer is None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            g.update()
    finally:
        jax.profiler.stop_trace()
    ev = _lgbt_events(str(tmp_path))
    by = {}
    for name, a, b, args in ev:
        by.setdefault(name, []).append((a, b, args))
    assert [e[2]["it"] for e in by["iteration"]] == [1, 2, 3]
    assert [e[2]["queued"] for e in by["dispatch"]] == [1, 1, 1]
    assert [e[2]["tree"] for e in by["assemble_tree"]] == [0, 1, 2]
    assert [e[2]["tree"] for e in by["d2h_wait"]] == [0, 1, 2]
    assert all(e[2]["trees"] == 1 for e in by["flush"])

    def inside(child, parents):
        return any(p[0] <= child[0] and child[1] <= p[1] for p in parents)

    for name, parent in (("dispatch", "iteration"), ("flush", "iteration"),
                         ("feature_sample", "iteration"),
                         ("assemble_tree", "flush"), ("d2h_wait", "flush")):
        assert all(inside(c, by[parent]) for c in by[name]), (name, parent)
    # the spans left nothing behind in the program
    assert tel._phases == {} and tel._counters == {} and tel.tracer is None


def test_trace_out_needs_no_telemetry_and_leaves_the_step_alone(
        rng, tmp_path, monkeypatch):
    """``trace_out`` with ``telemetry`` off: the Chrome trace holds the spans
    with their arguments, telemetry stays off, and so the device program has
    no counter lane: ``WaveState.telem`` is None and the step has a plain
    booster's four outputs.  Equation counts are not compared: a step traced
    while training read 12 more than a fresh one's once in two whole runs
    (cause not found: CHANGES.md, PR 30)."""
    X, y = _problem(rng)
    out = tmp_path / "train_trace.json"
    bst = lgb.train(dict(_BASE, trace_out=str(out),
                         tpu_pipeline_flush_depth=1),
                    lgb.Dataset(X, label=y), 4)
    tel = bst.gbdt.telemetry
    assert not tel.enabled and isinstance(tel.tracer, TraceRecorder)
    events = json.loads(out.read_text())["traceEvents"]
    begun = [e for e in events if e.get("ph") == "B"]
    names = {e["name"] for e in begun}
    assert {"iteration", "dispatch", "flush", "assemble_tree",
            "d2h_wait"} <= names
    assert sorted(e["args"]["it"] for e in begun
                  if e["name"] == "iteration") == [0, 1, 2, 3]
    assert all("queued" in e["args"] for e in begun
               if e["name"] == "dispatch")

    from lightgbm_tpu.learner_wave import WaveTPUTreeLearner
    lanes = []
    init_root = WaveTPUTreeLearner._init_root_wave

    def spy(self, *args, **kw):
        st = init_root(self, *args, **kw)
        lanes.append(st.telem)
        return st

    monkeypatch.setattr(WaveTPUTreeLearner, "_init_root_wave", spy)

    def outputs(g):
        g._jit_fused = None         # trace now, not what training traced
        jx = jax.make_jaxpr(g._fused_iter_fn())(*_step_args(g))
        return len(jx.jaxpr.outvars)

    plain = lgb.Booster(dict(_BASE), lgb.Dataset(X, label=y))
    assert outputs(bst.gbdt) == outputs(plain.gbdt) == 4
    assert lanes == [None, None]


# -- the sharded learners' own scope -------------------------------------------

def test_mesh_scope_file_equals_the_programs():
    """``benchmark/phases_mesh.json`` (new with the four-chip cell; the
    accepted ``phases.json`` is the benchmark's and does not know the name)
    holds ``phases.MESH_STAGES``, and a mesh stage is a scope ``scope()``
    lets through without being one of the accepted phases or stages."""
    with open(os.path.join(ROOT, "benchmark", "phases_mesh.json")) as fh:
        data = json.load(fh)
    assert tuple(data["mesh_stages"]) == phases.MESH_STAGES
    assert not set(phases.MESH_STAGES) & set(phases.DEVICE_SCOPES)
    with phases.scope("exchange"):
        pass
