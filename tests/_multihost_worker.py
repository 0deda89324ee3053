"""Subprocess worker for tests/test_multihost.py — one emulated pod host.

Launched N times against a local coordinator; each process forces
``JAX_PLATFORMS=cpu`` with ``--xla_force_host_platform_device_count=K``
local virtual devices, so ``jax.distributed.initialize`` (gloo CPU
collectives, wired by `parallel/multihost.py:initialize_from_config` from
the config keys) yields a genuine N-process x K-device global platform.

The spec (one JSON argv) selects a job:

  * ``train`` — train the deterministic gate problem through the plain
    Booster API for each requested tree_learner mode; report the model text,
    the routed learner class, the host layout, a recompile-sentinel verdict
    over the warmed multi-host step, and a DistributedNet
    allgather/sync/barrier exercise;
  * ``chaos`` — no training: heartbeat over the coordinator KV store until
    the armed `reliability/faults.py` ``net.crash`` clause kills this rank
    (os._exit(17)) or a peer's death surfaces as the named-root-cause
    ConnectionError; survivors report the error text, elapsed time, and
    reliability counters.
  * ``observe`` — pod observability drill: train through the engine
    (``lgb.train``) with telemetry + the per-rank flight recorder
    (``trace_out``), so every rank runs the clock-offset handshake and
    exports ``<trace_out>.rank<r>``; when ``straggle_s`` is set, rank 1
    sleeps inside every boosting step, so the heartbeat-borne skew gauges
    must name it.  Reports the telemetry report's ``distributed`` +
    ``provenance`` sections and counters.

Results are written as JSON to ``spec["out"]``.
"""

import json
import os
import sys
import time


def _setup(spec):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               f"{spec['local_devices']}")
    if spec.get("faults"):
        os.environ["LGBT_FAULTS"] = spec["faults"]
    # share the suite's persistent compile cache (tests/conftest.py): the
    # pod processes compile the same programs as the in-process tests.
    # Inherited through the environment under pytest; the fixed default
    # applies only when the variable is unset (a worker run by hand)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), ".jax_cache")
    import jax
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _problem(seed=0, n=600, f=30):
    import numpy as np
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + np.sin(X[:, 1]) + 0.3 * rng.randn(n) > 0).astype(float)
    return X, y


def _pod_params(spec, mode):
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "min_data_in_leaf": 5, "verbosity": -1, "metric": "none",
              "tree_learner": mode, "parallel_mesh": spec["mesh"],
              # f64 histogram accounting makes the cross-process reduction
              # order immaterial: model text is BYTE-identical to the
              # single-host run (f32 differs in summation-order ulps)
              "tpu_hist_dtype": "float64", "tpu_double_precision": True}
    if spec["num_hosts"] > 1:
        params.update({
            "coordinator_address": f"127.0.0.1:{spec['port']}",
            "num_hosts": spec["num_hosts"],
            "process_id": spec["rank"]})
    return params


def _job_train(spec):
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.analysis.recompile import (RecompileSentinel,
                                                 _learner_jits)
    from lightgbm_tpu.parallel import multihost

    X, y = _problem()
    out = {"rank": spec["rank"], "modes": {}}
    iters = int(spec.get("iters", 6))
    for mode in spec["modes"]:
        params = _pod_params(spec, mode)
        ds = lgb.Dataset(X, label=y, params=params)
        bst = lgb.Booster(params, ds)
        for _ in range(2):                       # warm the wave program
            bst.update()
        sentinel = RecompileSentinel()
        for name, fn in _learner_jits(bst.gbdt.learner).items():
            sentinel.register(name, fn)
        sentinel.arm()
        for _ in range(iters - 2):
            bst.update()
        retraces = [f.message for f in sentinel.check()] \
            if sentinel.supported() else None
        out["modes"][mode] = {
            "model": bst.model_to_string(),
            "learner": type(bst.gbdt.learner).__name__,
            "retraces": retraces,
            "heartbeats": (bst._mh_net._seq if bst._mh_net is not None
                           else None),
        }
    out["process_count"] = jax.process_count()
    out["process_index"] = jax.process_index()
    out["device_count"] = jax.device_count()
    out["local_device_count"] = jax.local_device_count()
    # -- DistributedNet seam exercise (loader-side collectives)
    if spec["num_hosts"] > 1:
        net = multihost.DistributedNet(namespace="probe")
        gathered = net.allgather(("hello", spec["rank"]))
        out["net"] = {
            "allgather": gathered,
            "sync_min": net.sync_min(100 + spec["rank"]),
            "sync_max": net.sync_max(100 + spec["rank"]),
        }
        net.barrier("probe-done")
    return out


def _job_observe(spec):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT

    if spec["rank"] == 1 and spec.get("straggle_s"):
        # inject the straggler INSIDE the engine's step timing window
        # (Booster.update brackets gbdt.train_one_iter), so the sleep
        # rides the next heartbeat as this rank's step duration
        delay = float(spec["straggle_s"])
        orig = GBDT.train_one_iter

        def slow(self, *a, **kw):
            time.sleep(delay)
            return orig(self, *a, **kw)

        GBDT.train_one_iter = slow
    X, y = _problem()
    params = _pod_params(spec, spec.get("mode", "serial"))
    params.update({
        "telemetry": True,
        "trace_out": spec["trace_out"],
        "telemetry_out": spec["telemetry_out"],
        "telemetry_sync_every": int(spec.get("sync_every", 0)),
        "telemetry_skew_warn_ratio": float(spec.get("skew_warn_ratio", 0.0)),
    })
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds,
                    num_boost_round=int(spec.get("iters", 5)),
                    verbose_eval=False, keep_training_booster=True)
    with open(spec["telemetry_out"]) as fh:
        rep = json.load(fh)
    return {"rank": spec["rank"],
            "learner": type(bst.gbdt.learner).__name__,
            "distributed": rep.get("distributed"),
            "provenance": rep.get("provenance"),
            "counters": rep.get("counters")}


def _job_elastic(spec):
    """One elastic AGENT (per-host controller): supervise this host's
    worker subprocess through every membership epoch via
    ``lightgbm_tpu.elastic.run_host``.  The agent process itself never
    initializes jax.distributed — each epoch's worker subprocess joins its
    own fresh cluster.  Reports the final model text, the epoch history,
    the controller-side reliability counters and the worker's merged
    telemetry ``elastic`` section (or the structured failure)."""
    from lightgbm_tpu.elastic import (ElasticHostDead, ElasticTerminalError,
                                      run_host)
    from lightgbm_tpu.reliability.metrics import rel_counters

    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "min_data_in_leaf": 5, "verbosity": -1, "metric": "none",
              "tree_learner": spec.get("mode", "data"),
              "tpu_hist_dtype": "float64", "tpu_double_precision": True,
              "elastic": True,
              "elastic_min_ranks": int(spec.get("min_ranks", 1)),
              "elastic_max_recoveries": int(spec.get("max_recoveries", 3)),
              "coordinator_address": f"127.0.0.1:{spec['port']}",
              "net_collective_deadline_s": spec.get("deadline_s", 6),
              "telemetry": True}
    if spec.get("telemetry_out"):
        params["telemetry_out"] = spec["telemetry_out"]
    if spec.get("trace_out"):
        params["trace_out"] = spec["trace_out"]
    out = {"rank": spec["rank"], "ok": False}
    try:
        res = run_host(
            params, spec["data"], int(spec.get("iters", 6)),
            host_id=spec["rank"], num_hosts=spec["num_hosts"],
            workdir=spec["workdir"], enable_x64=True,
            negotiate_deadline_s=float(spec.get("negotiate_deadline_s", 20)),
            worker_timeout_s=float(spec.get("worker_timeout_s", 420)))
        with open(res.model_path) as fh:
            model = fh.read()
        out.update({
            "ok": True, "model": model, "history": res.history,
            "recoveries": res.recoveries, "ranks_lost": res.ranks_lost,
            "recovery_wall_s": res.recovery_wall_s,
            "iterations": res.result.get("iterations"),
            "elastic": res.result.get("elastic"),
            "report_elastic": (res.report or {}).get("elastic"),
            "report_schema_version": (res.report or {}).get(
                "schema_version"),
            "worker_counters": (res.report or {}).get(
                "reliability", {}).get("counters", {}),
        })
    except ElasticTerminalError as e:
        out.update({"error_kind": "terminal", "error": str(e),
                    "history": e.history})
    except ElasticHostDead as e:
        out.update({"error_kind": "host_dead", "error": str(e),
                    "rc": e.rc})
    out["rel_counters"] = rel_counters()
    return out


def _job_chaos(spec):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.parallel import multihost
    from lightgbm_tpu.reliability.metrics import rel_counters

    cfg = Config.from_params({
        "coordinator_address": f"127.0.0.1:{spec['port']}",
        "num_hosts": spec["num_hosts"], "process_id": spec["rank"],
        "net_collective_deadline_s": spec.get("deadline_s", 10)})
    assert multihost.initialize_from_config(cfg)
    net = multihost.DistributedNet(cfg, namespace="chaos")
    t0 = time.time()
    out = {"rank": spec["rank"], "survived_error": None}
    try:
        for i in range(int(spec.get("beats", 6))):
            net.heartbeat(i)
        out["beats_completed"] = True
    except ConnectionError as e:
        out["survived_error"] = str(e)
        out["elapsed_s"] = round(time.time() - t0, 3)
        out["dead_ranks"] = list(getattr(e, "dead_ranks", ()))
    out["rel_counters"] = rel_counters()
    return out


def _chaos_quiesce(spec, dead_ranks):
    """Leader-LAST exit ordering for the chaos drill: the coordination
    service lives in rank 0's process and its exit SIGABRTs (via the
    fatal-error poller) any survivor still writing its report — the same
    invariant `lightgbm_tpu/elastic` honors.  Rank 0 waits for the OTHER
    survivors' report files (the typed RankDeathError names who will
    never write one) before exiting.  Filesystem, not KV: reads against
    the in-process coordination service can crash it natively, and the
    wait must stay SHORT — the service's own missed-heartbeat fuse for
    the deliberately-killed rank aborts rank 0 a few seconds after the
    survivors' deadline scan fires."""
    try:
        if int(spec["rank"]) != 0:
            return
        outdir = os.path.dirname(os.path.abspath(spec["out"]))
        peers = [os.path.join(outdir, f"r{r}.json")
                 for r in range(int(spec["num_hosts"]))
                 if r != 0 and r not in set(dead_ranks)]
        deadline = time.time() + 3.0
        while time.time() < deadline:
            if all(os.path.exists(p) for p in peers):
                break
            time.sleep(0.05)
    except Exception:
        pass


def main():
    spec = json.loads(sys.argv[1])
    _setup(spec)
    job = {"train": _job_train, "chaos": _job_chaos,
           "observe": _job_observe,
           "elastic": _job_elastic}[spec.get("job", "train")]
    out = job(spec)
    with open(spec["out"], "w") as fh:
        json.dump(out, fh)
    print(f"rank {spec['rank']} ok", flush=True)
    if spec.get("job") == "chaos":
        # report is durable — quiesce leader-last, then skip
        # jax.distributed's atexit shutdown barrier: with a peer
        # deliberately dead it SIGABRTs the survivors after their report
        _chaos_quiesce(spec, out.get("dead_ranks") or [])
        os._exit(0)


if __name__ == "__main__":
    main()
