"""Static per-program cost ledger: pin FLOPs, bytes and exchange payloads.

Four rounds of perf work (Pallas kernels, overlap, quantized gradients,
pod) are queued behind one TPU session, so a CPU-only PR can silently
regress the compute/byte profile of the very programs the hardware round
will validate.  The existing gate pins collective *sites* and *order*
(budgets.json / sequences.json); this pass pins how much WORK and MEMORY
each traced program does:

  * **flops / bytes_accessed** — XLA's own ``cost_analysis()`` over the
    lowered (not compiled) program: the closed jaxpr is rebuilt into a
    callable (``jaxpr_as_fun``), lowered for the gate's CPU platform and
    its analytical cost model read back.  Deterministic for a fixed jax
    version and platform.
  * **exchange_bytes** — per-collective-primitive payload bytes from the
    jaxpr walk (`jaxpr_lint.collect_stats`), generalizing the one-off
    int16-exchange pin: EVERY program's collective payload profile is
    pinned, exact by default.
  * **peak_live_bytes** — a liveness-walk estimate over the jaxpr: each
    value allocates at its defining eqn and frees after its last use
    (program outputs live to the end); sub-jaxpr (while/scan/cond body)
    peaks ride on top of the live set at their call site.  An estimate —
    XLA fuses and rematerializes — but a deterministic one, and a 2x
    jump here is a real regression no matter what the scheduler does.

All of it is pinned in the checked-in ``analysis/costs.json`` with
per-metric relative tolerance bands (``tolerance``); ``--dump-costs``
re-derives the file byte-identically (same review-artifact workflow as
budgets/sequences).  A gate failure names the program, the metric, the
pinned vs measured values, and the heaviest jaxpr primitives so review
starts at the offending region instead of a diff hunt.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

from . import jaxpr_lint
from .common import COSTS_PATH, Finding, load_costs

#: pinned metrics, in report order
METRICS = ("flops", "bytes_accessed", "peak_live_bytes", "exchange_bytes")

#: default relative tolerance bands (two-sided).  flops/bytes ride XLA's
#: cost model, which shifts slightly across jax versions — a band absorbs
#: that; the exchange payload is OUR wire contract and stays exact.
DEFAULT_TOLERANCE = {
    "flops": 0.10,
    "bytes_accessed": 0.15,
    "peak_live_bytes": 0.15,
    "exchange_bytes": 0.0,
}


def _aval_bytes(v: Any) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    import numpy as np
    size = 1
    for d in shape:
        size *= int(d)
    return size * np.dtype(dtype).itemsize


def xla_costs(closed_jaxpr) -> Tuple[int, int]:
    """(flops, bytes_accessed) from XLA's analytical cost model over the
    LOWERED program — no compilation, no execution."""
    import jax
    from jax.extend.core import jaxpr_as_fun

    fn = jaxpr_as_fun(closed_jaxpr)
    args = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
            for v in closed_jaxpr.jaxpr.invars]
    lowered = jax.jit(fn).lower(*args)
    ca = lowered.cost_analysis()
    if isinstance(ca, (list, tuple)):          # per-device list on some
        ca = ca[0] if ca else {}               # jax versions
    ca = ca or {}
    return int(round(float(ca.get("flops", 0.0)))), \
        int(round(float(ca.get("bytes accessed", 0.0))))


def _sub_jaxprs(eqn) -> List[Any]:
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for s in vs:
            inner = getattr(s, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                out.append(inner)
            elif hasattr(s, "eqns"):
                out.append(s)
    return out


def peak_live_bytes(jaxpr) -> int:
    """Liveness-walk peak over one (open) jaxpr: values allocate at
    their defining eqn, free after their last use; inputs/constants are
    live from the start, outputs to the end.  A sub-jaxpr's peak rides
    on top of the live set at its call-site eqn."""
    eqns = list(jaxpr.eqns)
    n = len(eqns)
    if n == 0:
        return sum(_aval_bytes(v)
                   for v in list(jaxpr.invars) + list(jaxpr.constvars))

    def_idx: Dict[Any, int] = {}
    last_use: Dict[Any, int] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        def_idx[v] = 0
        last_use[v] = 0
    for i, eqn in enumerate(eqns):
        for iv in eqn.invars:
            if hasattr(iv, "val"):             # Literal: no lifetime
                continue
            last_use[iv] = i
            def_idx.setdefault(iv, 0)
        for ov in eqn.outvars:
            def_idx[ov] = i
            last_use[ov] = max(last_use.get(ov, i), i)
    for v in jaxpr.outvars:
        if hasattr(v, "val"):
            continue
        last_use[v] = n - 1
        def_idx.setdefault(v, 0)

    delta = [0] * (n + 1)
    for v, d in def_idx.items():
        delta[d] += _aval_bytes(v)
        delta[last_use[v] + 1] -= _aval_bytes(v)
    live = 0
    live_at = [0] * n
    for i in range(n):
        live += delta[i]
        live_at[i] = live
    peak = max(live_at)
    for i, eqn in enumerate(eqns):
        subs = _sub_jaxprs(eqn)
        if subs:
            peak = max(peak, live_at[i] + max(peak_live_bytes(s)
                                              for s in subs))
    return peak


def measure(closed_jaxpr) -> Dict[str, Any]:
    """The full cost row for one traced program."""
    flops, bytes_accessed = xla_costs(closed_jaxpr)
    stats = jaxpr_lint.collect_stats(closed_jaxpr)
    return {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "peak_live_bytes": int(peak_live_bytes(closed_jaxpr.jaxpr)),
        "exchange_bytes": dict(sorted(stats["collective_bytes"].items())),
        "eqns": int(stats["eqns"]),
    }


def _heaviest_region(closed_jaxpr, top: int = 3) -> str:
    """The review starting point a cost failure names: the heaviest
    primitives in the program by total output bytes."""
    weights: Dict[str, Tuple[int, int]] = {}
    for eqn in jaxpr_lint.iter_eqns(closed_jaxpr.jaxpr):
        nb = sum(_aval_bytes(ov) for ov in eqn.outvars)
        cnt, tot = weights.get(eqn.primitive.name, (0, 0))
        weights[eqn.primitive.name] = (cnt + 1, tot + nb)
    ranked = sorted(weights.items(), key=lambda kv: -kv[1][1])[:top]
    return ", ".join(f"{name} x{cnt} ({tot} out bytes)"
                     for name, (cnt, tot) in ranked)


def costs_from(traced: jaxpr_lint.TracedPrograms,
               tolerance: Optional[Dict[str, float]] = None
               ) -> Dict[str, Any]:
    """A costs.json payload pinning the CURRENT measured costs
    (``--dump-costs``).  Moving a pin is a deliberate, reviewed act."""
    return {
        "_comment": "Per-program static cost ledger (XLA cost_analysis "
                    "flops/bytes, jaxpr collective payload bytes, "
                    "liveness-walk peak-live bytes). Re-derive with "
                    "--dump-costs and commit the diff when a reviewed "
                    "change legitimately moves a cost; tolerance bands "
                    "are relative, two-sided, per metric.",
        "tolerance": dict(tolerance if tolerance is not None
                          else DEFAULT_TOLERANCE),
        "programs": {name: measure(closed)
                     for name, closed in sorted(traced.closed.items())},
    }


def dump_costs(traced: jaxpr_lint.TracedPrograms, path: str = COSTS_PATH,
               tolerance: Optional[Dict[str, float]] = None
               ) -> Dict[str, Any]:
    """Atomically (re)write ``costs.json`` — byte-stable: sorted keys,
    2-space indent, trailing newline (the budgets/sequences workflow)."""
    payload = costs_from(traced, tolerance=tolerance)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    return payload


def _check_scalar(name: str, metric: str, pinned: int, measured: int,
                  tol: float, closed, file: str) -> Optional[Finding]:
    band = abs(pinned) * max(float(tol), 0.0)
    if abs(measured - pinned) <= band:
        return None
    direction = "above" if measured > pinned else "below"
    return Finding(
        "costmodel", "cost-regression", file,
        f"program {name!r} {metric}: measured {measured} vs pinned "
        f"{pinned} (±{tol:.0%} band) — {direction} the band; heaviest "
        f"region: {_heaviest_region(closed)}. A reviewed change that "
        f"legitimately moves this cost re-pins it via --dump-costs",
        symbol=name)


def check_costs(name: str, closed_jaxpr, entry: Dict[str, Any],
                tolerance: Dict[str, float],
                measured: Optional[Dict[str, Any]] = None
                ) -> List[Finding]:
    """Findings for one traced program against its costs.json entry."""
    file = jaxpr_lint.PROGRAM_FILES.get(name, "lightgbm_tpu")
    if measured is None:
        measured = measure(closed_jaxpr)
    if not entry:
        return [Finding(
            "costmodel", "cost-unpinned", file,
            f"program {name!r} has no analysis/costs.json entry — pin "
            f"its cost ledger with --dump-costs", symbol=name)]
    findings: List[Finding] = []
    for metric in ("flops", "bytes_accessed", "peak_live_bytes"):
        if metric not in entry:
            findings.append(Finding(
                "costmodel", "cost-unpinned", file,
                f"program {name!r} pins no {metric!r} — re-derive "
                f"costs.json with --dump-costs", symbol=name))
            continue
        f = _check_scalar(name, metric, int(entry[metric]),
                          int(measured[metric]),
                          float(tolerance.get(metric, 0.0)),
                          closed_jaxpr, file)
        if f is not None:
            findings.append(f)
    pinned_ex: Dict[str, int] = {
        k: int(v) for k, v in (entry.get("exchange_bytes") or {}).items()}
    measured_ex: Dict[str, int] = dict(measured["exchange_bytes"])
    tol = float(tolerance.get("exchange_bytes", 0.0))
    for prim in sorted(set(pinned_ex) | set(measured_ex)):
        p, m = pinned_ex.get(prim, 0), measured_ex.get(prim, 0)
        if abs(m - p) <= abs(p) * tol:
            continue
        findings.append(Finding(
            "costmodel", "cost-regression", file,
            f"program {name!r} exchange_bytes[{prim}]: measured {m} vs "
            f"pinned {p} — the collective payload contract moved (e.g. a "
            f"quantized wire tier silently widening); re-pin via "
            f"--dump-costs only with review", symbol=name))
    return findings


def run(costs: Optional[Dict[str, Any]] = None,
        traced: Optional[jaxpr_lint.TracedPrograms] = None):
    """Check every traced program against the checked-in ledger.

    Returns ``(findings, measured, skipped)``: ``measured`` maps program
    name to its cost row (surfaced in the JSON report), ``skipped`` maps
    untraced programs to reasons.  ``traced`` reuses the gate's shared
    trace cache (this pass lowers but never compiles)."""
    if costs is None:
        costs = load_costs()
    if traced is None:
        traced = jaxpr_lint.trace_programs()
    tolerance = {**DEFAULT_TOLERANCE, **costs.get("tolerance", {})}
    pinned = costs.get("programs", {})
    findings: List[Finding] = []
    measured: Dict[str, Dict[str, Any]] = {}
    for name, closed in sorted(traced.closed.items()):
        row = measure(closed)
        measured[name] = row
        findings.extend(check_costs(name, closed,
                                    pinned.get(name, {}), tolerance,
                                    measured=row))
    # a pin whose program no longer exists is ledger rot, same class as
    # a stale allowlist entry
    for name in sorted(pinned):
        if name not in jaxpr_lint.PROGRAM_FILES:
            findings.append(Finding(
                "costmodel", "cost-stale-pin", "analysis/costs.json",
                f"costs.json pins unknown program {name!r} (removed or "
                f"renamed) — re-derive with --dump-costs", symbol=name))
    return findings, measured, dict(traced.skipped)
