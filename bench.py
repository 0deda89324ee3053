"""Round benchmark — prints ONE JSON line for the driver.

Workload: synthetic Higgs-shaped binary classification (28 dense features,
255 bins, 255 leaves — the `docs/Experiments.rst:104-116` configuration) at
TWO scales in one run:

  * 1M rows  — the steady-state headline (``value``/``vs_baseline``);
  * 10.5M rows — the reference's REAL Higgs row count, reported under
    ``value_10p5m``/``vs_baseline_10p5m`` so the scale ratio is
    driver-captured every round (no perf number may live only in prose).

Metric: boosting iterations/second, steady-state (compile excluded).

Baseline: the reference's 28-core CPU Higgs number — 500 iterations over
10.5M rows in 238.5 s (`docs/Experiments.rst:106`) = 2.10 iters/s.  Histogram
work scales linearly in rows, so at R rows the equivalent reference
throughput is 2.10 × 10.5e6/R; ``vs_baseline`` is ours divided by that.
(BASELINE.json's target is ≥5× a single socket; the table's machine is a
dual socket, so parity with 22.0 at 1M ≈ 2× the single-socket bar.)

Usage: ``python bench.py``          — both scales, one JSON line.
       ``python bench.py ROWS [IT]`` — one scale (profiling convenience).
       ``--telemetry-out PATH``      — train with ``telemetry=True`` and
       write the per-scale JSON telemetry reports (phase timings, wave /
       stall counters, collective accounting — observability/schema.json)
       next to the headline metric, so BENCH_r*.json rounds carry phase
       breakdowns.
       ``--tree-learner MODE``       — parallel-mode passthrough
       (serial/data/feature/voting/data_feature) so the driver captures
       per-mode JSON lines without editing this script; recorded in the
       ``metric`` string.
       ``--parallel-mesh SHAPE``     — mesh-shape passthrough ("8", "2x4";
       data×feature for data_feature).
       ``--quantized-grad MODE``     — ``tpu_quantized_grad`` passthrough
       (on/off/auto) so quantized-vs-f32 A/B legs land as driver-captured
       JSON lines; recorded in the ``metric`` string.
       ``--num-hosts N --coordinator HOST:PORT --process-id R`` —
       multi-host passthrough (`parallel/multihost.py`): the same bench
       command runs on every pod host (only ``--process-id`` differs), the
       mesh spans processes, and the host layout lands in the ``metric``
       string.  ``--parallel-mesh`` should put the host count on the data
       axis ("2x4" on 2 hosts x 4 local devices).
       ``--out-of-core``             — write the synthetic problem to disk
       once and ingest it through the streaming two-pass loader
       (``two_round=true``, `dataset.py:from_stream`) instead of from
       memory, so loader-path regressions show up in bench rounds.
       ``--sync-every N``            — sampled-sync cadence
       (``telemetry_sync_every``; defaults to 8 whenever telemetry is on):
       every Nth iteration is bracketed with forced device syncs and the
       per-leg runtime attribution table + rank-skew gauges are embedded
       in the JSON line itself (``attribution`` / ``rank_skew`` keys), so
       BENCH rounds carry the collective/phase attribution evidence
       inline (observability/attribution.py).
"""

import gc
import json
import sys
import time


import numpy as np


def run_scale(rows: int, iters: int, warmup: int = 2,
              telemetry: bool = False, extra_params: dict = None,
              out_of_core: bool = False):
    """Train steady-state iterations at one scale; returns
    (iters/sec, telemetry report or None)."""
    import lightgbm_tpu as lgb

    lgb.use_compile_cache()
    rng = np.random.RandomState(7)
    f = 28
    X = rng.randn(rows, f).astype(np.float64)
    logit = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2] * 0.5 + np.sin(X[:, 3])
             + 0.5 * rng.randn(rows))
    y = (logit > 0).astype(np.float64)

    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "none", "telemetry": telemetry}
    if extra_params:
        params.update(extra_params)
    if out_of_core:
        # spill the problem to disk, ingest through the streaming loader
        import os
        import tempfile

        path = os.path.join(tempfile.mkdtemp(prefix="bench_ooc_"),
                            "train.csv")
        np.savetxt(path, np.column_stack([y, X]), delimiter=",",
                   fmt="%.17g")
        del X, y
        gc.collect()
        params["two_round"] = True
        ds = lgb.Dataset(path, params=params)
    else:
        ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.Booster(params, ds)

    # the boosting loop is async (device-resident score updates, lazy host
    # tree assembly): wait for the last score update before reading a clock
    import jax
    sync = lambda: jax.block_until_ready(bst.gbdt.train_score.score)

    for _ in range(warmup):  # compile + cache
        bst.update()
    sync()
    t0 = time.time()
    for _ in range(iters):
        bst.update()
    sync()
    dt = time.time() - t0
    report = bst.gbdt.get_telemetry() if telemetry else None
    del bst, ds  # release device buffers before the next scale
    if not out_of_core:
        del X, y
    gc.collect()
    return iters / dt, report


def ref_ips(rows: int) -> float:
    return (500.0 / 238.5) * (10.5e6 / rows)  # reference CPU, row-scaled


def _pop_opt_arg(argv, flag):
    """Extract ``--flag VALUE`` / ``--flag=VALUE`` from an argv list."""
    out = None
    rest = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a.startswith(flag):
            if "=" in a:
                out = a.split("=", 1)[1]
            elif i + 1 < len(argv):
                i += 1
                out = argv[i]
        else:
            rest.append(a)
        i += 1
    return out, rest


def _pop_flag(argv, flag):
    """Extract a valueless ``--flag`` from an argv list."""
    return flag in argv, [a for a in argv if a != flag]


def main():
    telemetry_out, argv = _pop_opt_arg(sys.argv[1:], "--telemetry-out")
    tree_learner, argv = _pop_opt_arg(argv, "--tree-learner")
    parallel_mesh, argv = _pop_opt_arg(argv, "--parallel-mesh")
    quantized, argv = _pop_opt_arg(argv, "--quantized-grad")
    num_hosts, argv = _pop_opt_arg(argv, "--num-hosts")
    coordinator, argv = _pop_opt_arg(argv, "--coordinator")
    process_id, argv = _pop_opt_arg(argv, "--process-id")
    out_of_core, argv = _pop_flag(argv, "--out-of-core")
    sync_every, argv = _pop_opt_arg(argv, "--sync-every")
    telem = telemetry_out is not None
    extra = {}
    mode_tag = ""
    if telem:
        # sampled-sync attribution on by default for telemetry benches:
        # 1-in-8 iterations pays the sync, the rest stay pipelined
        extra["telemetry_sync_every"] = int(sync_every) if sync_every else 8
        if sync_every:
            mode_tag += f", sync_every={sync_every}"
    if tree_learner:
        extra["tree_learner"] = tree_learner
        mode_tag = f", tree_learner={tree_learner}"
    if parallel_mesh:
        extra["parallel_mesh"] = parallel_mesh
        mode_tag += f", mesh={parallel_mesh}"
    if quantized:
        extra["tpu_quantized_grad"] = quantized
        mode_tag += f", quantized_grad={quantized}"
    if num_hosts or coordinator or process_id:
        # multi-host passthrough: the same command runs on every pod host;
        # resolve_multihost rejects a partial spec loudly rather than
        # silently benching single-host
        if coordinator:
            extra["coordinator_address"] = coordinator
        if num_hosts:
            extra["num_hosts"] = int(num_hosts)
        if process_id is not None:
            extra["process_id"] = int(process_id)
        mode_tag += (f", hosts={num_hosts or '?'}"
                     f", host_rank={process_id or '?'}")
    if out_of_core:
        mode_tag += ", out_of_core"
    reports = {}
    if argv:  # single-scale profiling mode
        rows = int(argv[0])
        iters = int(argv[1]) if len(argv) > 1 else 10
        ips, rep = run_scale(rows, iters, telemetry=telem,
                             extra_params=extra, out_of_core=out_of_core)
        if rep is not None:
            reports[str(rows)] = rep
        line = {
            "metric": f"boosting iters/sec (synthetic Higgs-like {rows}x28, "
                      f"255 leaves, 255 bins{mode_tag})",
            "value": round(ips, 4),
            "unit": "iters/sec",
            "vs_baseline": round(ips / ref_ips(rows), 4),
        }
    else:
        # the reference's Higgs number times 500 iterations end-to-end:
        # more steady-state iterations = closer to its methodology
        ips_1m, rep_1m = run_scale(1_000_000, 30, telemetry=telem,
                                   extra_params=extra,
                                   out_of_core=out_of_core)
        ips_full, rep_full = run_scale(10_500_000, 6, telemetry=telem,
                                       extra_params=extra,
                                       out_of_core=out_of_core)
        if rep_1m is not None:
            reports["1000000"] = rep_1m
            reports["10500000"] = rep_full
        line = {
            "metric": f"boosting iters/sec (synthetic Higgs-like 1Mx28, "
                      f"255 leaves, 255 bins; _10p5m = reference row count"
                      f"{mode_tag})",
            "value": round(ips_1m, 4),
            "unit": "iters/sec",
            "vs_baseline": round(ips_1m / ref_ips(1_000_000), 4),
            "value_10p5m": round(ips_full, 4),
            "vs_baseline_10p5m": round(ips_full / ref_ips(10_500_000), 4),
        }
    if telem:
        from lightgbm_tpu.observability import validate_report
        for rep in reports.values():
            assert "provenance" in rep, \
                "telemetry report lost its provenance block (schema v7)"
            # schema v11: the perf artifact must name the exact cost
            # ledger (analysis/costs.json sha256) it was gated against
            assert "cost_ledger_sha256" in rep["provenance"], \
                "telemetry provenance lost cost_ledger_sha256 (schema v11)"
            errs = validate_report(rep)
            assert not errs, errs
        with open(telemetry_out, "w") as fh:
            json.dump(reports, fh, indent=2, sort_keys=True)
            fh.write("\n")
        line["telemetry_out"] = telemetry_out
        # the runtime attribution table + rank-skew gauges ride the
        # driver-captured line itself (round-4 verdict: no perf evidence
        # may live only in a side file)
        attribution = {}
        rank_skew = {}
        for scale, rep in reports.items():
            dist = rep.get("distributed", {})
            if dist.get("attribution"):
                attribution[scale] = dist["attribution"]
            if dist.get("skew_ratio") is not None:
                rank_skew[scale] = {
                    "skew_ratio": dist["skew_ratio"],
                    "slowest_rank": dist.get("slowest_rank")}
        if attribution:
            line["attribution"] = attribution
        if rank_skew:
            line["rank_skew"] = rank_skew
    print(json.dumps(line))


if __name__ == "__main__":
    main()
