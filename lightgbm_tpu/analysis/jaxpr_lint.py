"""Traced-program lints: walk closed jaxprs and enforce budgets.

The training and serving hot paths are a handful of jitted programs; the
regressions that hurt are *structural* and visible at trace time, long
before a device profile:

  * a new collective slipping into the wave body multiplies per-tree
    exchanges (the PR-1 class: K psums per stall event instead of one);
  * an f64 op leaking into a traced path while x64 is off means an
    unintended cast chain (and on TPU, an emulated-precision cliff);
  * a ``pure_callback`` / infeed / outfeed in the hot loop is a host sync
    per iteration;
  * a large array baked into the program as a constant (instead of passed
    as an argument) bloats every executable and defeats donation.

``run()`` traces the standard program set — the serial wave tree step
(`learner_wave.py`), the sharded learners (`parallel/`), and the serving
binner + traversal programs — and checks each against the checked-in
per-program budgets (``budgets.json``).  Budgets count **static collective
call sites** in the traced program (the same notion
`observability.CollectiveLedger` records): a site inside ``lax.while_loop``
executes once per iteration, so site count is the per-tree multiplier that
matters.  Any learner change that adds a collective site must raise the
budget explicitly in the same commit.

The f64 rule only runs when x64 is off (the gate's configuration); the
test suite runs with x64 on for parity tests, where f64 is legitimate.
"""

from __future__ import annotations

import fnmatch
import functools
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from .common import Finding, load_budgets

#: jaxpr primitive names that are cross-device collectives
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pargmax", "pargmin",
})

#: primitive-name substrings that mean a host round-trip inside the program
BANNED_SUBSTRINGS = ("callback", "infeed", "outfeed")

#: program name -> the source file a finding anchors to
PROGRAM_FILES = {
    "wave_serial": "lightgbm_tpu/learner_wave.py",
    # the serial wave program with the Pallas split scan forced on —
    # traced in interpret mode off-TPU, which exercises the same jaxpr
    # structure the TPU path compiles
    "wave_serial_pallas": "lightgbm_tpu/ops/scan_pallas.py",
    # round-8 quantized-gradient programs: the serial step with int8/int16
    # discretization, and the data-sharded step whose histogram exchange
    # rides the int16 wire tier (ops/quant.py) — its psum_scatter payload
    # is pinned at HALF the f32 program's (checked pairwise in run())
    "wave_serial_quant": "lightgbm_tpu/ops/quant.py",
    "wave_sharded_data_quant": "lightgbm_tpu/parallel/compact_sharded.py",
    "wave_sharded_data": "lightgbm_tpu/parallel/wave_sharded.py",
    "wave_sharded_voting": "lightgbm_tpu/parallel/wave_sharded.py",
    "wave_feature": "lightgbm_tpu/parallel/feature_sharded.py",
    "wave_sharded_2d": "lightgbm_tpu/parallel/wave2d_sharded.py",
    # pod-shaped variants: the SAME programs traced at the 2-host virtual
    # layout (`parallel/multihost.py` — 8 global devices = 2 hosts x 4
    # local).  Collective structure must not change with host count (only
    # shard widths do); a cross-host-only collective slipping in shows up
    # as a site-count delta against these budgets.
    "wave_sharded_data_pod": "lightgbm_tpu/parallel/wave_sharded.py",
    "wave_sharded_2d_pod": "lightgbm_tpu/parallel/wave2d_sharded.py",
    "serving_bin": "lightgbm_tpu/serving/binner.py",
    "serving_traverse": "lightgbm_tpu/predictor.py",
}


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Every eqn, recursing into sub-jaxprs (pjit / while / cond / scan /
    shard_map bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for s in vs:
                inner = getattr(s, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from iter_eqns(inner)
                elif hasattr(s, "eqns"):
                    yield from iter_eqns(s)


def collect_stats(closed_jaxpr) -> Dict[str, Any]:
    """Structural stats of one closed jaxpr: eqn count, per-primitive
    collective site counts, banned-primitive sites, f64 op count, and the
    total bytes of baked-in constants."""
    import numpy as np

    collectives: Dict[str, int] = {}
    collective_bytes: Dict[str, int] = {}
    banned: List[str] = []
    f64_ops = 0
    eqns = 0
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        eqns += 1
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            collectives[name] = collectives.get(name, 0) + 1
            # wire payload per execution of this site: the input operands'
            # aval bytes (per-device shapes under shard_map).  This is what
            # the int16 histogram-exchange tier shrinks — the site COUNT
            # stays identical, the bytes halve.
            nb = 0
            for iv in eqn.invars:
                aval = getattr(iv, "aval", None)
                shape = getattr(aval, "shape", None)
                dt = getattr(aval, "dtype", None)
                if shape is not None and dt is not None:
                    size = 1
                    for d in shape:
                        size *= int(d)
                    nb += size * np.dtype(dt).itemsize
            collective_bytes[name] = collective_bytes.get(name, 0) + nb
        if any(b in name for b in BANNED_SUBSTRINGS):
            banned.append(name)
        for ov in eqn.outvars:
            dt = getattr(getattr(ov, "aval", None), "dtype", None)
            if dt is not None and dt == np.dtype("float64"):
                f64_ops += 1
                break
    const_bytes = sum(int(getattr(c, "nbytes", 0))
                      for c in closed_jaxpr.consts)
    return {"eqns": eqns, "collectives": collectives,
            "collective_bytes": collective_bytes, "banned": banned,
            "f64_ops": f64_ops, "const_bytes": const_bytes}


def lint_program(name: str, closed_jaxpr, budget: Dict[str, Any],
                 max_const_bytes: int, x64_off: bool,
                 file: Optional[str] = None
                 ) -> Tuple[List[Finding], Dict[str, Any]]:
    """Findings for one traced program against its budget entry."""
    stats = collect_stats(closed_jaxpr)
    file = file or PROGRAM_FILES.get(name, "lightgbm_tpu")
    allowed: Dict[str, int] = dict(budget.get("collectives", {}))
    findings: List[Finding] = []
    for prim, count in sorted(stats["collectives"].items()):
        cap = int(allowed.get(prim, 0))
        if count > cap:
            findings.append(Finding(
                "jaxpr", "collective-budget", file,
                f"program {name!r} traces {count} {prim} site(s), budget "
                f"allows {cap} — a new collective must raise "
                f"analysis/budgets.json explicitly", symbol=name))
    byte_caps: Dict[str, int] = dict(budget.get("collective_bytes", {}))
    for prim, cap in sorted(byte_caps.items()):
        traced = int(stats["collective_bytes"].get(prim, 0))
        if traced > int(cap):
            findings.append(Finding(
                "jaxpr", "collective-payload", file,
                f"program {name!r} traces {traced} {prim} payload bytes, "
                f"budget pins {cap} — a payload regression (e.g. the int16 "
                f"exchange tier silently falling back to f32) must raise "
                f"analysis/budgets.json explicitly", symbol=name))
    for prim in stats["banned"]:
        findings.append(Finding(
            "jaxpr", "host-callback", file,
            f"program {name!r} contains host-sync primitive {prim!r} "
            f"inside the traced hot path", symbol=name))
    if x64_off and stats["f64_ops"]:
        findings.append(Finding(
            "jaxpr", "f64-leak", file,
            f"program {name!r} traces {stats['f64_ops']} float64 op(s) "
            f"with x64 disabled — an unintended f64 cast chain",
            symbol=name))
    cap = int(budget.get("max_const_bytes", max_const_bytes))
    if cap and stats["const_bytes"] > cap:
        findings.append(Finding(
            "jaxpr", "baked-constants", file,
            f"program {name!r} bakes {stats['const_bytes']} bytes of "
            f"constants into the trace (ceiling {cap}) — pass large "
            f"arrays as arguments", symbol=name))
    return findings, stats


# -- the standard program set ------------------------------------------------

def _toy_dataset(n: int, f: int, params: Dict[str, Any]):
    """Deterministic synthetic problem (seeded Generator — rule LGB003)."""
    import numpy as np

    import lightgbm_tpu as lgb

    rng = np.random.default_rng(0)
    X = rng.standard_normal((n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    ds = lgb.Dataset(X, label=y, params=params)
    ds.construct()
    return ds


_BASE_PARAMS = {"objective": "binary", "num_leaves": 15,
                "min_data_in_leaf": 5, "verbosity": -1}


def _trace_wave_serial():
    import jax
    import jax.numpy as jnp

    from ..config import Config
    from ..learner_wave import WaveTPUTreeLearner

    ds = _toy_dataset(512, 4, dict(_BASE_PARAMS))
    learner = WaveTPUTreeLearner(Config.from_params(_BASE_PARAMS),
                                 ds.constructed)
    z = jnp.zeros(ds.constructed.num_data_padded, jnp.float32)
    fmask = jnp.ones(learner.num_features, bool)
    return jax.make_jaxpr(learner._train_tree_wave)(
        learner.bins_packed(), z, z, z, fmask)


def _trace_wave_serial_pallas():
    import jax
    import jax.numpy as jnp

    from ..config import Config
    from ..learner_wave import WaveTPUTreeLearner

    ds = _toy_dataset(512, 4, dict(_BASE_PARAMS))
    cfg = Config.from_params(dict(_BASE_PARAMS, tpu_wave_pallas_scan="on"))
    learner = WaveTPUTreeLearner(cfg, ds.constructed)
    assert learner._use_scan, "the forced Pallas scan did not resolve on"
    z = jnp.zeros(ds.constructed.num_data_padded, jnp.float32)
    fmask = jnp.ones(learner.num_features, bool)
    return jax.make_jaxpr(learner._train_tree_wave)(
        learner.bins_packed(), z, z, z, fmask)


def _trace_wave_serial_quant():
    import jax
    import jax.numpy as jnp

    from ..config import Config
    from ..learner_wave import WaveTPUTreeLearner

    ds = _toy_dataset(512, 4, dict(_BASE_PARAMS))
    learner = WaveTPUTreeLearner(
        Config.from_params(dict(_BASE_PARAMS, tpu_quantized_grad="on")),
        ds.constructed)
    assert learner._quant, learner._quant_reason
    z = jnp.zeros(ds.constructed.num_data_padded, jnp.float32)
    fmask = jnp.ones(learner.num_features, bool)
    return jax.make_jaxpr(learner._train_tree_wave)(
        learner.bins_packed(), z, z, z, fmask)


def _trace_wave_sharded(kind: str, quant: bool = False, ndev: int = 2):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..config import Config
    from ..parallel.mesh import make_mesh
    from ..parallel.feature_sharded import FeatureShardedWaveLearner
    from ..parallel.wave_sharded import ShardedVotingWaveLearner, \
        ShardedWaveLearner

    params = dict(_BASE_PARAMS, enable_bundle=False)
    ds = _toy_dataset(2048, 8, params)
    mesh = make_mesh(ndev)
    cfg_params = dict(params, tree_learner={
        "data": "data", "voting": "voting", "feature": "feature"}[kind])
    if kind == "data":
        # two opened levels: an opening level's one exchange and count
        # psum are budgeted too (the chip opens five from 2^22 rows a shard)
        cfg_params["tpu_wave_open_levels"] = 2
    if quant:
        # 2048 global rows keep the int16 exchange tier active
        # (HMAX·N <= 32767, ops/quant.py)
        cfg_params["tpu_quantized_grad"] = "on"
    cfg = Config.from_params(cfg_params)
    if kind == "feature":
        learner = FeatureShardedWaveLearner(cfg, ds.constructed, mesh)
        body = learner._train_tree_feature_wave
        in_specs = (P(None, None), P(), P(), P(), P())
        out_specs = (P(), P(), P(), P(), P())
    else:
        cls = ShardedWaveLearner if kind == "data" else \
            ShardedVotingWaveLearner
        learner = cls(cfg, ds.constructed, mesh)
        body = learner._train_tree_wave_sharded
        ax = learner.axis
        in_specs = (P(None, ax), P(ax), P(ax), P(ax), P())
        out_specs = (P(), P(), P(), P(ax), P())
    if quant:
        assert learner._quant, learner._quant_reason
        assert learner._wire_int16(), "int16 exchange tier did not engage"
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    fn = jax.shard_map(body, check_vma=False, **kw)
    z = jnp.zeros(learner.n_pad, jnp.float32)
    fmask_pad = jnp.ones(learner.f_pad, bool)
    return jax.make_jaxpr(fn)(learner.sharded_bins(), z, z, z, fmask_pad)


def _trace_wave_sharded_2d(shape: Tuple[int, int] = (2, 2),
                           features: int = 8):
    """The 2-D hybrid wave tree step on a (data, feature) mesh.  The
    toy dataset's 8 padded features pack to 2 words, so feature-axis=2 is
    the word-aligned tile limit at this width (tests use wider problems
    for 2x4 shapes); the pod variant scales the DATA axis instead
    ((4, 2) — the 2-host x 4-local virtual layout, row axis host-major).
    ``features`` widens the toy problem for the mesh-factorization sweep
    (spmd.py needs feature-axis=4 eligible, i.e. 16 features -> 4 words)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..config import Config
    from ..parallel.sharding import AXIS_DATA, AXIS_FEATURE, make_mesh
    from ..parallel.wave2d_sharded import ShardedWave2DLearner, \
        wave2d_ineligible_reason

    params = dict(_BASE_PARAMS, enable_bundle=False)
    ds = _toy_dataset(2048, features, params)
    mesh = make_mesh(shape=shape, axis_names=(AXIS_DATA, AXIS_FEATURE))
    cfg = Config.from_params(dict(params, tree_learner="data_feature"))
    reason = wave2d_ineligible_reason(cfg, ds.constructed, mesh)
    assert reason is None, f"gate dataset ineligible for 2D: {reason}"
    learner = ShardedWave2DLearner(cfg, ds.constructed, mesh)
    ax, fx = learner.axis, learner.faxis
    kw = dict(mesh=mesh,
              in_specs=(P(fx, ax), P(ax), P(ax), P(ax), P()),
              out_specs=(P(), P(), P(), P(ax), P()))
    fn = jax.shard_map(learner._train_tree_wave_sharded, check_vma=False,
                       **kw)
    z = jnp.zeros(learner.n_pad, jnp.float32)
    fmask_pad = jnp.ones(learner.f_pad, bool)
    return jax.make_jaxpr(fn)(learner.sharded_bins(), z, z, z, fmask_pad)


def _trace_serving_bin():
    import jax
    import numpy as np

    from ..serving.binner import BinnerArrays

    ds = _toy_dataset(512, 4, dict(_BASE_PARAMS))
    arrays = BinnerArrays.for_data(ds.constructed)
    xu = np.zeros((64, max(arrays.num_used, 1)), np.float64)
    return jax.make_jaxpr(arrays.bin_device)(xu)


def _trace_serving_traverse():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..predictor import _predict_all

    # shape-realistic fake packs: the traversal's structure (and therefore
    # its collective/callback/f64 profile) depends only on shapes
    T, ni, nl, F = 6, 14, 15, 8
    rng = np.random.default_rng(0)
    packs = dict(
        feat=jnp.asarray(rng.integers(0, F, (T, ni)), jnp.int32),
        thr=jnp.asarray(rng.integers(0, 16, (T, ni)), jnp.int32),
        dtyp=jnp.zeros((T, ni), jnp.int32),
        lch=jnp.full((T, ni), -1, jnp.int32),
        rch=jnp.full((T, ni), -1, jnp.int32),
        lval=jnp.zeros((T, nl), jnp.float32),
        cat_bits=jnp.zeros((T, 1), jnp.uint32),
        cat_lo=jnp.zeros((T, ni), jnp.int32),
        cat_hi=jnp.zeros((T, ni), jnp.int32),
        cls=jnp.zeros(T, jnp.int32))
    meta = jnp.zeros(F, jnp.int32)
    bins = jnp.zeros((F, 64), jnp.int32)
    fn = functools.partial(_predict_all, depth=4, K=1, es=False,
                           es_freq=10, es_margin=10.0)
    return jax.make_jaxpr(fn)(bins, packs, meta, meta, meta)


def program_builders(need_mesh_of: int = 2
                     ) -> Dict[str, Callable[[], Any]]:
    """Name -> zero-arg tracer for the standard program set.  Sharded
    programs are included only when the platform exposes enough devices
    (the gate forces an 8-virtual-device CPU platform)."""
    import jax

    builders: Dict[str, Callable[[], Any]] = {
        "wave_serial": _trace_wave_serial,
        "wave_serial_pallas": _trace_wave_serial_pallas,
        "wave_serial_quant": _trace_wave_serial_quant,
        "serving_bin": _trace_serving_bin,
        "serving_traverse": _trace_serving_traverse,
    }
    if len(jax.devices()) >= need_mesh_of:
        builders["wave_sharded_data"] = lambda: _trace_wave_sharded("data")
        builders["wave_sharded_data_quant"] = \
            lambda: _trace_wave_sharded("data", quant=True)
        builders["wave_sharded_voting"] = \
            lambda: _trace_wave_sharded("voting")
        builders["wave_feature"] = lambda: _trace_wave_sharded("feature")
    if len(jax.devices()) >= 2 * need_mesh_of:
        builders["wave_sharded_2d"] = _trace_wave_sharded_2d
    if len(jax.devices()) >= 8:
        # pod shapes: the 2-host x 4-local virtual layout flattened onto
        # the gate's 8 devices (1D data row axis, and a (4, 2) 2D mesh)
        builders["wave_sharded_data_pod"] = \
            lambda: _trace_wave_sharded("data", ndev=8)
        builders["wave_sharded_2d_pod"] = \
            lambda: _trace_wave_sharded_2d(shape=(4, 2))
    return builders


class TracedPrograms:
    """One trace of the standard program set, shared across passes.

    The budget, sequence-order, f64 and const-ceiling checks all walk the
    SAME closed jaxprs — tracing each program once (seconds apiece for the
    sharded learners) instead of once per pass is the gate's dominant
    cost.  ``closed`` maps program name -> closed jaxpr, ``seconds`` the
    per-program tracing wall time (surfaced in the JSON report), and
    ``skipped`` maps untraceable programs to reasons."""

    def __init__(self) -> None:
        self.closed: Dict[str, Any] = {}
        self.seconds: Dict[str, float] = {}
        self.skipped: Dict[str, str] = {}


def trace_programs(programs: Optional[Dict[str, Callable[[], Any]]] = None,
                   glob: Optional[str] = None,
                   only: Optional[set] = None) -> TracedPrograms:
    """Trace the standard program set once (``--programs <glob>`` narrows
    the selection) and return the shared :class:`TracedPrograms` cache.
    ``only`` (a set of program names, or None for all) is the
    ``--changed-only`` narrowing: programs outside it are skipped with a
    reason that names the flag, so the report stays auditable."""
    if programs is None:
        programs = program_builders()
    tp = TracedPrograms()
    for name in sorted(PROGRAM_FILES):
        if glob and not fnmatch.fnmatch(name, glob):
            tp.skipped[name] = f"not selected by --programs {glob!r}"
            continue
        if only is not None and name not in only:
            tp.skipped[name] = "source file unchanged under --changed-only"
            continue
        builder = programs.get(name)
        if builder is None:
            tp.skipped[name] = "not traceable on this platform " \
                "(needs a multi-device mesh)"
            continue
        t0 = time.perf_counter()
        tp.closed[name] = builder()
        tp.seconds[name] = time.perf_counter() - t0
    return tp


def run(budgets: Optional[Dict[str, Any]] = None,
        programs: Optional[Dict[str, Callable[[], Any]]] = None,
        x64_off: Optional[bool] = None,
        traced: Optional[TracedPrograms] = None):
    """Lint the standard program set against its budgets.

    Returns ``(findings, program_stats, skipped)`` where ``program_stats``
    maps program name to its :func:`collect_stats` output (the input for
    ``--dump-budgets``) and ``skipped`` maps missing programs to reasons.
    ``traced`` reuses an existing :func:`trace_programs` cache instead of
    re-tracing (the gate shares one cache with the sequence pass).
    """
    import jax

    if budgets is None:
        budgets = load_budgets()
    if traced is None:
        traced = trace_programs(programs)
    if x64_off is None:
        x64_off = not jax.config.jax_enable_x64
    max_const = int(budgets.get("max_const_bytes", 0))
    prog_budgets = budgets.get("programs", {})

    findings: List[Finding] = []
    stats: Dict[str, Dict[str, Any]] = {}
    skipped: Dict[str, str] = dict(traced.skipped)
    for name, closed in sorted(traced.closed.items()):
        fs, st = lint_program(name, closed, prog_budgets.get(name, {}),
                              max_const, x64_off)
        findings.extend(fs)
        stats[name] = st
    # paired payload check: the quantized data-sharded program's histogram
    # exchange must move at most HALF the f32 program's bytes (the int16
    # wire tier's whole point); checked structurally so a silent fallback
    # to the f32 path fails the gate even before budgets are re-pinned
    qs = stats.get("wave_sharded_data_quant")
    fs32 = stats.get("wave_sharded_data")
    if qs is not None and fs32 is not None:
        qb = int(qs["collective_bytes"].get("psum_scatter", 0))
        fb = int(fs32["collective_bytes"].get("psum_scatter", 0))
        if fb and 2 * qb > fb:
            findings.append(Finding(
                "jaxpr", "quant-exchange-payload",
                PROGRAM_FILES["wave_sharded_data_quant"],
                f"quantized data-sharded histogram exchange traces {qb} "
                f"psum_scatter payload bytes, more than half the f32 "
                f"program's {fb} — the int16 wire tier is not engaging",
                symbol="wave_sharded_data_quant"))
    return findings, stats, skipped


def budgets_from_stats(stats: Dict[str, Dict[str, Any]],
                       max_const_bytes: int = 1 << 20) -> Dict[str, Any]:
    """A budgets.json payload pinning the CURRENT collective site counts
    (``--dump-budgets``).  Raising a number is a deliberate, reviewed act."""
    return {
        "_comment": "Per-program collective-site budgets derived from the "
                    "traced programs. A learner change that adds a "
                    "collective site MUST raise its budget here, in the "
                    "same commit, with the why in the commit message.",
        "max_const_bytes": int(max_const_bytes),
        "programs": {
            name: {"collectives": dict(sorted(
                st["collectives"].items())),
                "collective_bytes": dict(sorted(
                    st["collective_bytes"].items()))}
            for name, st in sorted(stats.items())
        },
    }
