"""Host-side telemetry accumulator (see package docstring).

Phase timers are HOST wall clocks around host-visible phases (binning,
gradient/tree dispatch, score update, pipeline flush, host tree assembly);
device-side work inside one fused program is attributed through the
per-tree counter vector (``TEL_*``) and, for real device timings, the
opt-in ``profile_trace_dir`` trace.  Everything here is designed so the
enabled path never forces a device sync: per-tree counter vectors arrive
through ``device_telem`` ALREADY ``copy_to_host_async``'d by the caller
and are only materialized in ``flush_device`` — the same cadence at which
the boosting loop materializes tree records.
"""

from __future__ import annotations

import tracemalloc
from typing import Any, Dict, List, Optional

import numpy as np

from .trace import span

# -- device counter vector layout (accumulated by the wave learner) ---------
# int32 slots; the vector is carried through the tree program only when
# telemetry is enabled (WaveState.telem is None otherwise).
(TEL_WAVES, TEL_WAVE_SORTS, TEL_WAVE_MEMBERS, TEL_FROZEN_MEMBERS,
 TEL_GROW_SPLITS, TEL_STALL_SPLITS, TEL_STALL_EXTRAS, TEL_STALL_SORT_MODE,
 TEL_POPS, TEL_TOTAL_SPLITS) = range(10)
TEL_NSLOTS = 12  # spare slots so adding a counter never reshapes the lane

TEL_NAMES = {
    TEL_WAVES: "waves",
    TEL_WAVE_SORTS: "wave_sorts",
    TEL_WAVE_MEMBERS: "wave_members",
    TEL_FROZEN_MEMBERS: "frozen_members",
    TEL_GROW_SPLITS: "grow_splits",
    TEL_STALL_SPLITS: "stall_splits",
    TEL_STALL_EXTRAS: "stall_extras",
    TEL_STALL_SORT_MODE: "stall_sort_mode",
    TEL_POPS: "pops",
    TEL_TOTAL_SPLITS: "total_splits",
}

# v2: optional "serving" section (QPS / stage latency / batch occupancy /
# compile-cache — `lightgbm_tpu/serving/batcher.py` ServingStats.report)
# v3: "reliability" section (process-wide failure accounting: retries,
# sheds, fallbacks, aborts, snapshots, injected faults —
# `lightgbm_tpu/reliability/metrics.py`); serving section gains
# shed/fallback counters
# v4: serving section gains "latency_ms" (exact p50/p95/p99 from the
# request latency histogram — `observability/metrics_export.py`)
# v5: optional "lifecycle" section (promotions / rollbacks / shadow
# reports / watchdog state — `lightgbm_tpu/lifecycle/controller.py`);
# serving section gains "errors" (admitted requests answered with an
# error frame)
# v6: serving section gains optional "replicas" array (per-replica fleet
# state: health, in-flight, dispatched, ejections, latency histogram —
# `lightgbm_tpu/serving/fleet/replicas.py`)
# v7: required "provenance" block (platform / jax version / device & host
# counts / emulated-vs-real flag — no ambiguity about what hardware a
# number came from) and optional "distributed" section
# (per-rank step timings + skew, sampled-sync attribution table, memory
# watermarks, clock-offset handshake — `observability/attribution.py` /
# `observability/podtrace.py`)
# v8: serving section gains "tenants" (per-model-name latency histogram,
# request/error/shed counters and SLO attainment / error-budget burn —
# `serving/batcher.py` TenantStats) and reports gain an optional "drift"
# section (PSI/KS baseline-vs-window verdict over the traffic recorder —
# `observability/drift.py`)
# v9: optional "elastic" section (membership epoch / survivor count set by
# the engine on elastic pods; the per-host controller merges the recovery
# totals — epochs, recoveries, ranks_lost, re-dealt row count, recovery
# wall-time — into the final report, `lightgbm_tpu/elastic/controller.py`)
# v10: optional "autopilot" section (drift-triggered refit daemon: check /
# trigger / suppress / promote / rollback counts, the RefitBudget state and
# the bounded decision history — `lightgbm_tpu/lifecycle/autopilot.py`);
# serving.tenants[] items gain "tenant_shed" (sheds by the tenant's OWN
# admission cap, `reliability/degrade.py` TenantAdmission)
# v11: provenance gains "cost_ledger_sha256" — the sha256 of the checked-in
# static cost-model ledger (`analysis/costs.json`) at report time, so any
# perf artifact can be matched to the exact pinned FLOPs/bytes/exchange
# expectations it was produced under (null when the ledger is absent)
SCHEMA_VERSION = 11


def _cost_ledger_sha256() -> Optional[str]:
    """sha256 of ``analysis/costs.json`` (the static cost-model ledger),
    or None when the ledger is not checked in."""
    import hashlib
    try:
        from ..analysis.common import COSTS_PATH
        with open(COSTS_PATH, "rb") as fh:
            return hashlib.sha256(fh.read()).hexdigest()
    except (OSError, ImportError):
        return None


def provenance_section(extra: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    """The required schema-v7 ``provenance`` block: what hardware and
    software stack produced this report.  ``emulated`` is True whenever
    the accelerator platform is NOT a real TPU (CPU runs, forced-host
    virtual device pods) — the flag the BENCH/MULTICHIP writers assert on
    so a CPU-parity number can never masquerade as a device result."""
    import jax
    dev = jax.devices()[0]
    out: Dict[str, Any] = {
        "platform": str(dev.platform),
        "device_kind": str(dev.device_kind),
        "jax_version": str(jax.__version__),
        "num_devices": int(jax.device_count()),
        "num_hosts": int(jax.process_count()),
        "process_index": int(jax.process_index()),
        "emulated": dev.platform != "tpu", "mesh_shape": None,
        "cost_ledger_sha256": _cost_ledger_sha256(),
    }
    if extra:
        out.update({k: v for k, v in extra.items() if v is not None})
    return out


def memory_watermarks() -> Dict[str, Any]:
    """Device HBM peaks (``memory_stats()``; absent on backends that
    don't expose them — CPU) and the process tracemalloc snapshot when
    the caller has tracing on.  Host-only, never forces a device sync."""
    import jax
    devices = []
    for d in jax.local_devices():
        st = d.memory_stats()       # None where the backend has none (CPU)
        if not st:
            continue
        devices.append({
            "device": str(d),
            "peak_bytes_in_use": int(st.get("peak_bytes_in_use", 0)),
            "bytes_in_use": int(st.get("bytes_in_use", 0)),
            "bytes_limit": int(st.get("bytes_limit", 0)),
        })
    host = None
    if tracemalloc.is_tracing():
        cur, peak = tracemalloc.get_traced_memory()
        host = {"current_bytes": int(cur), "peak_bytes": int(peak)}
    return {"devices": devices, "host_heap": host}


class Telemetry:
    """Accumulates phases / counters / gauges and builds the JSON report."""

    def __init__(self, enabled: bool):
        self.enabled = bool(enabled)
        # optional span recorder (observability/trace.py): when attached,
        # every phase occurrence that carries a start stamp also lands as
        # a trace span, so the Perfetto timeline and the phase table are
        # two views of the same measurements
        self.tracer = None
        self._phases: Dict[str, List[float]] = {}  # name -> [sum_s, n, max_s]
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, Any] = {}
        self._iter_wall: List[float] = []          # bounded ring, seconds
        self._iter_total = 0.0
        self._iter_count = 0
        self._pending: List[Any] = []              # async-copied device telem
        self._device_totals = np.zeros(TEL_NSLOTS, np.int64)
        self._device_trees = 0
        self._last_tree: Optional[np.ndarray] = None
        # schema-v7 additions: provenance extras (mesh shape, learner name
        # — facts only the engine/GBDT knows), the distributed section
        # (rank skew, clock handshake) and per-phase tracemalloc peaks
        self._provenance_extra: Dict[str, Any] = {}
        self._distributed: Dict[str, Any] = {}
        self._elastic: Dict[str, Any] = {}
        self._phase_heap: Dict[str, int] = {}      # name -> peak bytes
        self._heap_stack: List[int] = []

    # -- phases --------------------------------------------------------------

    def phase(self, name: str, **args: Any):
        """Context manager around one phase occurrence: ``trace.span``
        with this accumulator attached (a profiler event always, a
        recorder span when ``self.tracer`` is set, a row of the phase
        table when ``self.enabled``)."""
        return span(name, self, **args)

    def add_phase_time(self, name: str, seconds: float,
                       t0: Optional[float] = None) -> None:
        """Accumulate one phase occurrence.  ``t0`` (a ``perf_counter``
        stamp) additionally records the occurrence as a trace span when a
        recorder is attached; without it the time lands in the phase
        table only (some callers measure durations whose start they no
        longer hold)."""
        if not self.enabled:
            return
        st = self._phases.setdefault(name, [0.0, 0, 0.0])
        st[0] += seconds
        st[1] += 1
        st[2] = max(st[2], seconds)
        tr = self.tracer
        if tr is not None and t0 is not None:
            tr.add_complete(name, t0, seconds, cat="phase")
        if name == "iteration":
            self._iter_total += seconds
            self._iter_count += 1
            self._iter_wall.append(seconds)
            if len(self._iter_wall) > 512:
                del self._iter_wall[:256]

    # -- host-heap watermarks (per phase) ------------------------------------
    # tracemalloc's peak is global-since-start; per-phase window peaks use
    # reset_peak() with explicit propagation to the enclosing phase, so a
    # nested phase's reset never loses the parent's window high-water mark.
    # Only active when the USER already turned tracemalloc on — telemetry
    # never starts tracing itself (it costs ~2x on every allocation).

    def _heap_enter(self) -> None:
        if not tracemalloc.is_tracing():
            return
        try:
            tracemalloc.reset_peak()
        except Exception:   # pragma: no cover — <3.9 has no reset_peak
            return
        self._heap_stack.append(0)

    def _heap_exit(self, name: str) -> None:
        if not self._heap_stack or not tracemalloc.is_tracing():
            return
        try:
            wpeak = max(tracemalloc.get_traced_memory()[1],
                        self._heap_stack.pop())
            self._phase_heap[name] = max(self._phase_heap.get(name, 0),
                                         int(wpeak))
            if self._heap_stack:
                self._heap_stack[-1] = max(self._heap_stack[-1], wpeak)
            tracemalloc.reset_peak()
        except Exception:   # pragma: no cover
            pass

    # -- counters / gauges ---------------------------------------------------

    def inc(self, name: str, v: int = 1) -> None:
        if self.enabled:
            self._counters[name] = self._counters.get(name, 0) + int(v)

    def gauge(self, name: str, v: Any) -> None:
        if self.enabled:
            self._gauges[name] = v

    # -- distributed / provenance extras -------------------------------------

    def set_provenance(self, **kw: Any) -> None:
        """Merge engine/GBDT-known facts (mesh_shape, tree_learner, ...)
        into the report's ``provenance`` block."""
        if self.enabled:
            self._provenance_extra.update(kw)

    def set_distributed(self, **kw: Any) -> None:
        """Merge pod facts (rank step timings, skew, clock handshake) into
        the report's ``distributed`` section."""
        if self.enabled:
            self._distributed.update(kw)

    def set_elastic(self, **kw: Any) -> None:
        """Merge elastic-pod facts (membership epoch, survivor count,
        recovery totals) into the report's optional ``elastic`` section."""
        if self.enabled:
            self._elastic.update(kw)

    def last_iteration_s(self) -> Optional[float]:
        """Duration of the most recent "iteration" phase occurrence — the
        per-rank step timing that rides the liveness heartbeat."""
        return self._iter_wall[-1] if self._iter_wall else None

    # -- device counter lane -------------------------------------------------

    def device_telem(self, arr) -> None:
        """Queue one per-tree counter vector.  The caller must have issued
        ``copy_to_host_async`` on it alongside the tree's record arrays."""
        if self.enabled and arr is not None:
            self._pending.append(arr)

    def flush_device(self) -> None:
        """Materialize queued counter vectors (host-resident after the
        async copies — the same ~0.2 ms fetch the record flush pays)."""
        if not self._pending:
            return
        pend, self._pending = self._pending, []
        for a in pend:
            v = np.asarray(a).astype(np.int64)
            n = min(len(v), TEL_NSLOTS)
            self._device_totals[:n] += v[:n]
            self._device_trees += 1
            self._last_tree = v[:n]

    # -- report --------------------------------------------------------------

    def device_counters(self) -> Dict[str, int]:
        out = {name: int(self._device_totals[idx])
               for idx, name in TEL_NAMES.items()}
        out["trees_measured"] = self._device_trees
        # derived: every correction event splits exactly one stalled TOP,
        # the rest are speculative extras (see learner_wave._replay)
        events = out["stall_splits"] - out["stall_extras"]
        out["stall_events"] = events
        out["sim_passes"] = events + self._device_trees
        return out

    def report(self, ledger=None, extra_gauges: Optional[Dict] = None,
               light: bool = False) -> Dict[str, Any]:
        if not light:
            self.flush_device()
        dev = self.device_counters()
        counters = dict(self._counters)
        counters.update(dev)
        gauges = dict(self._gauges)
        if extra_gauges:
            gauges.update(extra_gauges)
        phases = {
            name: {"total_ms": st[0] * 1e3, "count": st[1],
                   "max_ms": st[2] * 1e3}
            for name, st in self._phases.items()}
        it = {
            "count": self._iter_count,
            "total_ms": self._iter_total * 1e3,
            "mean_ms": (self._iter_total / self._iter_count * 1e3
                        if self._iter_count else 0.0),
            "last_ms": (self._iter_wall[-1] * 1e3
                        if self._iter_wall else 0.0),
        }
        coll = self._collectives(ledger, dev)
        # failure accounting travels with every report (training AND
        # serving) — the section is process-wide by design
        from ..reliability.metrics import reliability_section
        rep = {"schema_version": SCHEMA_VERSION, "enabled": self.enabled,
               "phases": phases, "iterations": it, "counters": counters,
               "gauges": gauges, "collectives": coll,
               "provenance": provenance_section(self._provenance_extra),
               "distributed": self._distributed_section(phases),
               "reliability": reliability_section()}
        if self._elastic:
            rep["elastic"] = dict(self._elastic)
        return rep

    def _distributed_section(self, phases_ms: Dict[str, Any]
                             ) -> Dict[str, Any]:
        """Schema-v7 ``distributed`` section: rank skew + clock handshake
        (set by the engine via :meth:`set_distributed`), the sampled-sync
        attribution table derived from the ``sync.*`` phases, and memory
        watermarks."""
        out: Dict[str, Any] = dict(self._distributed)
        from .attribution import attribution_table
        table = attribution_table(phases_ms)
        if table is not None:
            out["attribution"] = table
        mem = memory_watermarks()
        if self._phase_heap:
            mem["phase_heap_peak_bytes"] = dict(self._phase_heap)
        out["memory"] = mem
        return out

    def _collectives(self, ledger, dev: Dict[str, int]) -> Dict[str, Any]:
        sites = list(ledger.sites()) if ledger is not None else []
        trees = max(dev.get("trees_measured", 0), 0)
        # per-tree execution estimates from the decoded counters; cadences
        # the counters don't cover report count/bytes as null
        per_tree = {
            "tree": 1.0,
            "wave": dev["waves"] / trees if trees else None,
            "stall_event": dev["stall_events"] / trees if trees else None,
            "split": dev["total_splits"] / trees if trees else None,
        }
        total_count = 0.0
        total_bytes = 0.0
        known = True
        for s in sites:
            mult = per_tree.get(s["cadence"])
            if mult is None:
                known = False
                continue
            total_count += mult
            total_bytes += mult * s["bytes_per_call"]
        totals = {"count": total_count if (sites and known) else
                  (total_count or None),
                  "bytes": total_bytes if (sites and known) else
                  (total_bytes or None)}
        return {"sites": sites, "per_tree_estimate": totals,
                # the batched stall correction reduces K stacked member
                # histograms in ONE collective; each extra member is one
                # collective the round-5 per-member loop would have issued
                "saved_by_stall_batching": dev["stall_extras"]}
