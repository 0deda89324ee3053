"""Runtime collective & phase attribution (schema v7, ROADMAP item 1).

The phase table (`telemetry.py`) times host-visible dispatch windows, and
the CollectiveLedger records trace-time collective SITES — neither says
where device time actually goes.  This module adds two runtime attribution
mechanisms (device time by phase is read off a ``jax.profiler`` trace
through the program's own scopes and spans, ``phases.py``):

  * **Sampled-sync timer** (``telemetry_sync_every=N``): every Nth
    iteration the boosting loop drains the dispatch queue, then brackets
    each leg of the iteration (gradients, tree build, score update) with
    a forced device sync, landing ``sync.*`` phases whose per-leg means
    sum to the synced iteration wall.  Amortized: N-1 of every N
    iterations stay fully async, so the pipeline measurements and the
    training throughput coexist in one run.  ``force_sync`` is
    ``jax.block_until_ready``: on a directly attached chip it returns
    only once the value exists, so it is the sync every timing in this
    repo uses.
  * **Exchange-window probe**: the sharded learners expose their REAL
    exchange seam (`exchange_probe` — the per-wave psum_scatter, the
    2D word-select psum, the voting all_gather) as a standalone jitted
    program over a representative zero buffer; timing it isolates the
    collective leg the fused program hides.  The probe jits are outside
    the analysis gate's traced-program set and the ledger is muted while
    they trace, so budgets.json and ``collectives.sites`` are unchanged.

Everything here is host-only and lives in ``observability/`` — never
imported into a traced function — so the LGB005 wall-clock discipline
holds: these perf_counter reads can never bake a constant into a
compiled program (allowlisted with that verdict in
``analysis/allowlist.json``).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence

# sync.* phases that are NOT iteration legs: the iteration wall itself,
# the pre-iteration queue drain, and the standalone exchange probe
_NON_LEG_SYNC = ("sync.iteration", "sync.drain", "sync.exchange_probe")

# per-iteration host phases folded into the table so the leg sum tracks
# the full iteration wall (they run on every iteration; their global
# means estimate their share of a sampled one).  ``tree_train`` is the
# non-pipelined sync path's fully-host-synchronous tree build.
_HOST_LEGS = ("bagging", "feature_sample", "dispatch", "score_update",
              "flush", "tree_train")

# host phases whose window is a strict prefix of a sync leg's
# [dispatch, completion] window — when that sync leg was recorded,
# counting the host phase too would double-count the dispatch time
_HOST_SHADOWED = {"dispatch": "sync.tree_build",
                  "score_update": "sync.score_update",
                  "tree_train": "sync.tree_train"}


def force_sync(*arrays: Any) -> None:
    """Block until every array's value is actually available
    (``jax.block_until_ready``; non-array arguments are ignored)."""
    import jax
    jax.block_until_ready([a for a in arrays if hasattr(a, "shape")])


def timeit(fn: Callable, *args: Any, iters: int = 5, warmup: int = 2,
           sync: Optional[Callable[[Any], None]] = None) -> float:
    """Best-of-``iters`` seconds for one synced call of ``fn(*args)`` —
    THE timing implementation (the exchange probe routes here).

    ``sync`` overrides the default ``force_sync`` on the result (callers
    whose output pytree needs a specific leaf fetched pass their own).
    """
    do_sync = sync if sync is not None else \
        (lambda out: force_sync(*_leaves(out)))
    for _ in range(max(warmup, 0)):
        do_sync(fn(*args))
    best = float("inf")
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        do_sync(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _leaves(out: Any) -> List[Any]:
    if out is None:
        return []
    if isinstance(out, (tuple, list)):
        return [a for a in out if hasattr(a, "shape")]
    return [out] if hasattr(out, "shape") else []


class SampledSync:
    """The boosting loop's sampled-sync bracket (``telemetry_sync_every``).

    ``sampled(iter_)`` is True on every Nth iteration; while active the
    GBDT paths call :meth:`leg` after each dispatch to force-sync that
    leg's outputs and record a ``sync.<name>`` phase.  All ranks of a pod
    evaluate ``sampled`` on the lockstep iteration counter, so the
    exchange probe's collective program is entered pod-wide together.
    """

    def __init__(self, tel, every: int):
        self.tel = tel
        self.every = max(int(every), 0)
        self.active = False

    def sampled(self, iter_: int) -> bool:
        return self.every > 0 and self.tel.enabled \
            and (iter_ % self.every == 0)

    def leg(self, name: str, t0: float, arrays: Sequence[Any]) -> None:
        """Force-sync ``arrays`` and record ``sync.<name>`` covering
        dispatch start ``t0`` → completion."""
        if not self.active:
            return
        force_sync(*arrays)
        self.tel.add_phase_time(f"sync.{name}",
                                time.perf_counter() - t0, t0=t0)

    def drain(self, *arrays: Any) -> None:
        """Pre-iteration queue drain so the bracketed iteration measures
        only its own work (recorded as ``sync.drain``, excluded from the
        leg table)."""
        t0 = time.perf_counter()
        force_sync(*arrays)
        self.tel.add_phase_time("sync.drain", time.perf_counter() - t0,
                                t0=t0)

    def probe_exchange(self, learner) -> None:
        """Time the learner's exchange-window probe (one representative
        collective, best-of-3) and record it as ``sync.exchange_probe``
        plus an ``exchange_probe_ms`` gauge.  No-op for learners without
        an exchange seam (the serial paths)."""
        probe = getattr(learner, "exchange_probe", None)
        if probe is None:
            return
        try:
            fn_args = probe()
            if fn_args is None:
                return
            fn, args = fn_args
            t0 = time.perf_counter()
            best = timeit(fn, *args, iters=3, warmup=1)
        except Exception:
            # best-effort: a probe that fails to trace (e.g. quantized
            # scales not established yet) must never kill training
            return
        self.tel.add_phase_time("sync.exchange_probe",
                                time.perf_counter() - t0, t0=t0)
        self.tel.gauge("exchange_probe_ms", best * 1e3)


def attribution_table(phases_ms: Dict[str, Dict[str, float]]
                      ) -> Optional[Dict[str, Any]]:
    """The per-leg attribution table from the ``sync.*`` phases of a
    report's ``phases`` section (``{name: {total_ms, count, max_ms}}``).

    Legs are per-iteration means: every ``sync.<leg>`` phase divided by
    the sampled-iteration count, plus the per-iteration host phases
    (bagging, flush, assembly) at their own means.  ``coverage`` is
    leg-sum / synced iteration wall — the acceptance bar is |1 - coverage|
    <= 0.1.  Returns None when no sampled iteration ran.
    """
    it = phases_ms.get("sync.iteration")
    if not it or not it.get("count"):
        return None
    n = int(it["count"])
    wall_ms = it["total_ms"] / n
    legs: Dict[str, float] = {}
    for name, st in phases_ms.items():
        if not name.startswith("sync.") or name in _NON_LEG_SYNC:
            continue
        legs[name[len("sync."):]] = st["total_ms"] / n
    for name in _HOST_LEGS:
        if _HOST_SHADOWED.get(name) in phases_ms:
            continue
        st = phases_ms.get(name)
        if st and st.get("count"):
            legs[f"host.{name}"] = st["total_ms"] / st["count"]
    legs_sum = sum(legs.values())
    probe = phases_ms.get("sync.exchange_probe")
    return {
        "sampled_iterations": n,
        "iteration_ms": wall_ms,
        "legs_ms": legs,
        "legs_sum_ms": legs_sum,
        "coverage": (legs_sum / wall_ms) if wall_ms > 0 else 0.0,
        "unattributed_ms": wall_ms - legs_sum,
        "exchange_probe_ms": (probe["total_ms"] / probe["count"]
                              if probe and probe.get("count") else None),
    }
