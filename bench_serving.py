"""Serving load generator — closed + open loop, one BENCH_SERVING JSON.

The training side has had trajectory discipline since round 1: every perf
claim moves `bench.py`'s JSON line and lands in a ``BENCH_r*.json``.  This
is the same arbiter for the serving path (ROADMAP item 3 "measured like a
service"): an in-process ``PredictionServer`` is driven by

  * a **closed loop** — N client threads, each issuing sequential
    predicts; measures the latency the service delivers when clients wait
    for responses (throughput ∝ clients / latency), and
  * an **open loop** — requests fired on a fixed schedule at a target
    QPS regardless of completions (the honest arrival model for external
    traffic).  Latency is measured from the request's SCHEDULED send time,
    so coordinated omission is counted, not hidden; sheds
    (``ServerOverloaded``) and errors are tallied separately.

Both loops record exact p50/p95/p99 (``observability.LatencyHistogram``),
and the server's own stats supply batch occupancy and compile-cache
counts.  The output validates against
``observability.BENCH_SERVING_SCHEMA`` and is written atomically.

Usage:
  python bench_serving.py                         # defaults, writes
                                                  # BENCH_SERVING_r01.json
  python bench_serving.py --out F.json --round 2 --clients 8 \
      --requests 800 --qps 200 --open-seconds 5 --rows-per-request 8
  python bench_serving.py --model model.txt       # serve an existing model
  python bench_serving.py --trace-out trace.json  # capture spans too
  python bench_serving.py --replicas 4 --protocol binary   # fleet gateway
  python bench_serving.py --compare --out BENCH_SERVING_r02.json --round 2
      # pickle-vs-binary x 1-vs-N replica legs (headline = binary + N)

Tiny smoke (CI): --train-rows 2000 --trees 5 --requests 40 --qps 40
--open-seconds 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np


def build_booster(args):
    import lightgbm_tpu as lgb

    lgb.use_compile_cache()
    if args.model:
        return lgb.Booster(model_file=args.model)
    rng = np.random.RandomState(11)
    n, f = args.train_rows, args.num_features
    X = rng.randn(n, f)
    logit = X[:, 0] * 1.5 + X[:, 1] * X[:, 2 % f] * 0.5 + 0.3 * rng.randn(n)
    y = (logit > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 20,
              "verbosity": -1, "metric": "none"}
    return lgb.train(params, lgb.Dataset(X, label=y), args.trees)


def _request_matrix(rng: np.random.RandomState, rows: int,
                    f: int) -> np.ndarray:
    return rng.randn(rows, f)


class _LoopStats:
    """Latency + outcome accounting for one load phase (thread-safe)."""

    def __init__(self):
        from lightgbm_tpu.observability import LatencyHistogram
        self.hist = LatencyHistogram()
        self._lock = threading.Lock()
        self.ok = 0
        self.shed = 0
        self.errors = 0

    def done(self, latency_ms: float, outcome: str) -> None:
        self.hist.record(latency_ms)
        with self._lock:
            setattr(self, outcome, getattr(self, outcome) + 1)

    def section(self, duration_s: float, **extra) -> Dict[str, Any]:
        with self._lock:
            ok, shed, errors = self.ok, self.shed, self.errors
        total = ok + shed + errors
        return {"requests": total, "ok": ok, "shed": shed, "errors": errors,
                "duration_s": round(duration_s, 4),
                "qps": round(total / duration_s, 3) if duration_s else 0.0,
                "shed_rate": round(shed / total, 5) if total else 0.0,
                "latency_ms": _round_latency(self.hist.snapshot()), **extra}


def _round_latency(snap: Dict[str, Any]) -> Dict[str, Any]:
    return {k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in snap.items()}


def _issue(client, X, stats: _LoopStats, t_ref: float) -> None:
    """One request; latency measured from ``t_ref`` (enqueue time for the
    closed loop, SCHEDULED send time for the open loop)."""
    from lightgbm_tpu.serving import ServerOverloaded
    try:
        client.predict(X)
        stats.done((time.perf_counter() - t_ref) * 1e3, "ok")
    except ServerOverloaded:
        stats.done((time.perf_counter() - t_ref) * 1e3, "shed")
    except Exception:
        stats.done((time.perf_counter() - t_ref) * 1e3, "errors")


def run_closed_loop(host, port, args) -> Dict[str, Any]:
    from lightgbm_tpu.serving import ServingClient

    stats = _LoopStats()
    per_client = max(args.requests // args.clients, 1)

    def worker(seed: int) -> None:
        rng = np.random.RandomState(1000 + seed)
        with ServingClient(host, port, timeout=60,
                           protocol=args.protocol) as c:
            for _ in range(per_client):
                X = _request_matrix(rng, args.rows_per_request,
                                    args.num_features)
                _issue(c, X, stats, time.perf_counter())

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(args.clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return stats.section(time.perf_counter() - t0, clients=args.clients)


def run_open_loop(host, port, args) -> Dict[str, Any]:
    from lightgbm_tpu.serving import ServingClient

    stats = _LoopStats()
    n = max(int(args.qps * args.open_seconds), 1)
    interval = 1.0 / args.qps
    next_idx = [0]
    idx_lock = threading.Lock()
    pool = max(min(args.open_pool, n), 1)
    clients: List[Any] = []

    t0 = time.perf_counter()

    def worker(wid: int) -> None:
        rng = np.random.RandomState(2000 + wid)
        c = clients[wid]
        while True:
            with idx_lock:
                i = next_idx[0]
                if i >= n:
                    return
                next_idx[0] = i + 1
            sched = t0 + i * interval
            delay = sched - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            X = _request_matrix(rng, args.rows_per_request,
                                args.num_features)
            # latency from the SCHEDULED time: a saturated pool shows up
            # as latency (coordinated omission counted), not hidden
            _issue(c, X, stats, sched)

    for w in range(pool):
        clients.append(ServingClient(host, port, timeout=60,
                                     protocol=args.protocol))
    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(pool)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dur = time.perf_counter() - t0
    for c in clients:
        c.close()
    return stats.section(dur, target_qps=float(args.qps))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_serving.py",
        description="closed+open-loop serving load generator "
                    "(BENCH_SERVING_r*.json)")
    ap.add_argument("--out", default="BENCH_SERVING_r01.json")
    ap.add_argument("--round", type=int, default=1)
    ap.add_argument("--model", default="",
                    help="serve this model text instead of training one")
    ap.add_argument("--train-rows", type=int, default=20000)
    ap.add_argument("--trees", type=int, default=20)
    ap.add_argument("--num-features", type=int, default=28)
    ap.add_argument("--rows-per-request", type=int, default=8)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=400,
                    help="closed-loop total across all clients")
    ap.add_argument("--qps", type=float, default=100.0,
                    help="open-loop target request rate")
    ap.add_argument("--open-seconds", type=float, default=3.0)
    ap.add_argument("--open-pool", type=int, default=32,
                    help="open-loop connection pool size")
    ap.add_argument("--deadline-ms", type=float, default=2.0)
    ap.add_argument("--max-batch-rows", type=int, default=256)
    ap.add_argument("--max-inflight", type=int, default=64)
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve through the fleet gateway with N replicas "
                         "(0 = legacy threaded server, -1 = one per local "
                         "device)")
    ap.add_argument("--protocol", choices=("auto", "binary", "pickle"),
                    default="auto",
                    help="client wire protocol (auto negotiates binary, "
                         "falls back to pickle)")
    ap.add_argument("--compare", action="store_true",
                    help="run pickle-vs-binary x 1-vs-N replica legs in one "
                         "process; the binary+N leg is the headline")
    ap.add_argument("--trace-out", default="",
                    help="also capture request spans (Chrome trace JSON)")
    ap.add_argument("--note", default="")
    args = ap.parse_args(argv)

    import jax
    from lightgbm_tpu.observability import (BENCH_SERVING_SCHEMA,
                                            validate_report)

    booster = build_booster(args)
    if args.num_features != booster.num_feature():
        args.num_features = booster.num_feature()

    def run_leg(protocol: str, replicas: int):
        """One (protocol, replicas) measurement with a fresh server."""
        leg_args = argparse.Namespace(**vars(args))
        leg_args.protocol = protocol
        server = booster.serve(
            replicas=replicas, port=0, max_batch_rows=args.max_batch_rows,
            deadline_ms=args.deadline_ms, max_inflight=args.max_inflight,
            trace_out=args.trace_out)
        try:
            closed = run_closed_loop(server.host, server.port, leg_args)
            open_ = run_open_loop(server.host, server.port, leg_args)
            # fleet servers expose the registry per replica; the legacy
            # threaded server has a single one
            reg = getattr(server, "registry", None) or server.replicas
            section = server.stats.serving_section(
                models=reg.versions(), jit_entries=reg.jit_entries())
        finally:
            server.stop()
        return closed, open_, section

    if args.compare:
        n = args.replicas if args.replicas > 0 else \
            max(len(jax.local_devices()), 2)
        specs = [("pickle", 1), ("binary", 1), ("pickle", n), ("binary", n)]
        legs = []
        for proto, nrep in specs:
            closed, open_, section = run_leg(proto, nrep)
            legs.append({"protocol": proto, "replicas": nrep,
                         "closed_loop": closed, "open_loop": open_})
            print(json.dumps({"leg": f"{proto} x{nrep}",
                              "closed_p99_ms":
                              closed["latency_ms"]["p99"],
                              "closed_qps": closed["qps"],
                              "open_p99_ms": open_["latency_ms"]["p99"],
                              "open_qps": open_["qps"]}), file=sys.stderr)
        # the final (binary, N) leg is the headline; `section` already
        # holds that leg's server stats
        headline = legs[-1]
        closed, open_ = headline["closed_loop"], headline["open_loop"]
        args.protocol, args.replicas = headline["protocol"], n
    else:
        legs = None
        closed, open_, section = run_leg(args.protocol, args.replicas)

    from lightgbm_tpu.observability import provenance_section

    report = {
        # v2: provenance carries cost_ledger_sha256 (analysis/costs.json)
        "schema_version": 2,
        "round": args.round,
        # the driver's TPU runs are the arbiter; CPU seeds are marked
        "platform": jax.devices()[0].platform,
        # who-produced-this, same block as bench.py/MULTICHIP artifacts:
        # platform, jax version, host/device counts, emulated flag
        "provenance": provenance_section(),
        **({"note": args.note} if args.note else {}),
        "workload": {
            "model": args.model or "synthetic-binary",
            "train_rows": args.train_rows, "trees": args.trees,
            "num_features": args.num_features,
            "rows_per_request": args.rows_per_request,
            "deadline_ms": args.deadline_ms,
            "max_batch_rows": args.max_batch_rows,
            "max_inflight": args.max_inflight,
            "protocol": args.protocol,
            "replicas": args.replicas,
        },
        "closed_loop": closed,
        "open_loop": open_,
        **({"legs": legs} if legs else {}),
        "server": {
            "batches": section["batches"],
            "batch_occupancy": round(section["batch_occupancy"], 4),
            "shed": section["shed"],
            "compile_cache": section["compile_cache"],
            "buckets": section["buckets"],
        },
    }
    assert "provenance" in report and \
        isinstance(report["provenance"].get("emulated"), bool), \
        "BENCH_SERVING report lost its provenance block"
    assert "cost_ledger_sha256" in report["provenance"], \
        "BENCH_SERVING provenance lost cost_ledger_sha256 (schema v2)"
    errs = validate_report(report, BENCH_SERVING_SCHEMA)
    if errs:
        print(f"BENCH_SERVING report violates schema: {errs}",
              file=sys.stderr)
        return 2
    tmp = args.out + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, args.out)
    line = {"metric": "serving p50/p99 ms + sustained QPS "
                      f"({args.rows_per_request} rows/req)",
            "closed_p50_ms": report["closed_loop"]["latency_ms"]["p50"],
            "closed_p99_ms": report["closed_loop"]["latency_ms"]["p99"],
            "closed_qps": report["closed_loop"]["qps"],
            "open_p99_ms": report["open_loop"]["latency_ms"]["p99"],
            "open_qps": report["open_loop"]["qps"],
            "shed_rate": report["open_loop"]["shed_rate"],
            "protocol": args.protocol,
            "replicas": args.replicas,
            "out": args.out}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
