"""Structured training telemetry.

The early perf rounds were driven by one-off profiling scripts (deleted)
and hand-done ablation arithmetic; the library itself measured nothing.
This package is the first-class observability layer the boosting loop and
tree learners report through:

  * ``phases`` (`phases.py`) — the names the program gives its own work:
    the ``jax.named_scope`` phases of the fused step (gradients, root,
    opening, grow, replay, emit, score_update, with select / partition /
    hist / scan / stall inside), the pinned Pallas kernel names, and the
    ``lgbt.*`` host spans.  ``trace.span`` is the one span call: an event
    of a ``jax.profiler`` session (the device's clock) always, a
    ``TraceRecorder`` span when one is attached, a phase-table row when
    ``telemetry`` is on.

  * ``Telemetry`` — host wall timers per phase, per-iteration timing, and
    the host-side decode of the per-tree device counter vector the wave
    learner accumulates on device (``learner_wave.TEL_*``).  The counter
    vector rides the SAME ``copy_to_host_async`` flush as the per-tree
    record arrays, so enabling telemetry adds zero host syncs to the hot
    path; with ``telemetry=False`` the learners trace the exact same jaxpr
    as before (the counter lane is ``None`` and never enters the program).
  * ``CollectiveLedger`` — trace-time accounting of every collective the
    sharded learners issue (op, payload bytes, phase, cadence).  Dynamic
    per-tree totals are estimated by combining the static sites with the
    decoded wave/stall counters.
  * ``report`` — the JSON report schema (``schema.json``, checked in and
    validated by the tier-1 smoke test) plus a dependency-free validator.
    Schema v2 adds the optional ``serving`` section that the prediction
    service (`lightgbm_tpu/serving/`) reports QPS, queue/bin/traverse/unpad
    stage latency, batch occupancy and compile-cache hits through.
    Schema v3 adds the ``reliability`` section — the process-wide failure
    accounting (connect retries, collective aborts, shed requests, host
    fallbacks, snapshots written/pruned, injected faults) maintained by
    `lightgbm_tpu/reliability/metrics.py`.

  * ``TraceRecorder`` (`trace.py`) — request-scoped structured spans: a
    thread-safe monotonic-clock ring buffer exporting Chrome trace-event
    JSON (open in Perfetto).  Training phase timers and the serving
    queue→pad→bin→traverse→unpad stages land as spans automatically when
    a recorder is attached (``Telemetry.tracer``); serving requests carry
    a ``trace_id`` end-to-end so one id links the request span, its
    micro-batch span and the batch's stage spans.
  * ``LatencyHistogram`` / Prometheus export (`metrics_export.py`) —
    log-bucketed latency histograms with exact p50/p95/p99 over a bounded
    raw-sample window, and the text-format snapshot behind the server's
    ``metrics`` op.  Schema v4 adds the serving ``latency_ms`` section.

Schema v7 adds the distributed-training layer (ROADMAP items 1 & 2):

  * ``attribution`` (`attribution.py`) — the sampled-sync timer
    (``telemetry_sync_every``: every Nth iteration brackets each leg of
    the jitted step with a forced sync), the exchange-window probe the
    sharded learners expose, and the per-leg attribution table.
  * ``podtrace`` (`podtrace.py`) — the pod flight recorder: per-rank
    trace export with a KV-store clock-offset handshake, and the merge
    of N per-rank traces into ONE pod-wide Chrome trace.
  * every report carries a required ``provenance`` block (platform /
    jax version / host count / emulated-vs-real) and a ``distributed``
    section (rank skew, attribution table, memory watermarks);
    ``training_prometheus`` renders it as ``lgbt_training_*`` gauges.

Schema v8 adds the fleet-serving monitoring layer (ROADMAP items 3c & 4
prerequisite):

  * ``drift`` (`drift.py`) — PSI and two-sample-KS detectors over
    per-feature bin-index distributions (through the serving binner's
    existing bins) and score distributions; baselines are captured from
    the traffic recorder at promote time and later windows are compared
    against them, emitting the optional ``drift`` report section,
    ``lgbt_serving_drift_*`` gauges and ``drift.alert`` trace instants.
  * per-tenant SLO metrics (`serving/batcher.py` ``TenantStats``) — a
    per-model-name latency histogram + request/error/shed counters with
    SLO attainment and error-budget burn, reported as
    ``serving.tenants[]`` and scraped as ``lgbt_serving_tenant_*``
    series; the fleet gateway additionally answers plain-HTTP
    ``GET /metrics`` on its serving port.

Device-side *time* attribution inside the fused tree program is out of
scope for counters — that is what the opt-in ``profile_trace_dir``
(`jax.profiler`) trace is for, in which every device operation carries the
program's phase scopes in its ``op_name``; see README "Telemetry &
profiling" and "Tracing & service metrics".
"""

from .attribution import (SampledSync, attribution_table, force_sync,
                          timeit)
from .collectives import CollectiveLedger
from .drift import DriftMonitor, ks_2samp, ks_from_counts, psi_from_counts
from .metrics_export import (BENCH_SERVING_SCHEMA, LatencyHistogram,
                             prometheus_text, training_prometheus)
from .podtrace import estimate_clock_offset, export_rank_trace, \
    merge_pod_trace
from .report import load_schema, validate_report, write_report
from .telemetry import TEL_NAMES, Telemetry, provenance_section
from .trace import (TraceRecorder, get_global_tracer, new_trace_id,
                    set_global_tracer)

__all__ = ["Telemetry", "CollectiveLedger", "TEL_NAMES",
           "load_schema", "validate_report", "write_report",
           "TraceRecorder", "new_trace_id", "LatencyHistogram",
           "prometheus_text", "BENCH_SERVING_SCHEMA",
           "SampledSync", "attribution_table", "force_sync",
           "timeit", "training_prometheus",
           "estimate_clock_offset", "export_rank_trace",
           "merge_pod_trace", "provenance_section",
           "get_global_tracer", "set_global_tracer",
           "DriftMonitor", "psi_from_counts", "ks_from_counts",
           "ks_2samp"]
