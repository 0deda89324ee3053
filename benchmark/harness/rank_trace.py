"""What a traced run of a ranking job says about its objective: device-busy
time under each stage of the lambdarank objective (``benchmark/
phases_rank.json``: the ordering, the pair pass, the write-back), all inside
the program's ``gradients`` phase.

Read from the same ``.xplane.pb`` as ``program_trace`` reads, through its
loader, clipped to the whole ``bench_iteration`` spans as there, control-flow
wrappers left out, overlapping operations counted once, then the mean over
the devices.  A program without these scopes (the parent of the PR that added
them, or an objective that has none) gives an empty table, and the readers
return nothing.
"""

import json
import os

from benchmark.harness import program_trace, trace_reduce
from benchmark.harness.paths import BENCH_DIR, load_json


def names():
    return load_json(BENCH_DIR, "phases_rank.json")


def _add(table, key, value):
    table[key] = table.get(key, 0) + value


def stage_of(tf_op, stages):
    """The first of ``stages`` among the components of an ``op_name`` path
    (a scope entered under a transform reads ``vmap(rank_pairs)``), or
    None."""
    path = [c.split("(")[-1].rstrip(")")
            for c in (tf_op or "").rstrip(":").split("/")]
    for c in path:
        if c in stages:
            return c
    return None


def device_table(ops, lo, hi, stages):
    """One device's busy seconds by rank stage inside the window; ``ops``
    sorted by start."""
    t, covered = {}, lo
    for ev in ops:
        a = max(ev["start_ns"], lo)
        b = min(ev["start_ns"] + ev["duration_ns"], hi)
        if b <= lo or a >= hi:
            continue
        busy = max(0.0, b - max(a, covered)) / 1e9
        covered = max(covered, b)
        stage = stage_of(ev["meta_stats"].get("tf_op"), stages)
        if stage is not None:
            _add(t, stage, busy)
    return t


def reduce(path):
    """``{"iterations", "devices", "stage_seconds"}``, or None where the trace
    holds no whole iteration or no device."""
    known = program_trace.names()
    devices, _, iters = program_trace.load(path, known["span_prefix"])
    win = trace_reduce.window_of(iters)
    if win is None or not devices:
        return None
    lo, hi, n_iter = win
    stages = tuple(names()["rank_stages"])
    out = {"iterations": n_iter, "devices": len(devices), "stage_seconds": {}}
    for ops in devices.values():
        for k, v in device_table(ops, lo, hi, stages).items():
            _add(out["stage_seconds"], k, v / len(devices))
    return out


def of(run):
    """This run's table, made once and kept on ``run`` (and written beside
    the trace for a look by hand); None in an untraced run."""
    if "rank_trace" not in run:
        run["rank_trace"] = None
        if run.get("trace_dir"):
            rt = reduce(trace_reduce.find_xplane(run["trace_dir"]))
            run["rank_trace"] = rt
            if rt is not None:
                with open(os.path.join(os.path.dirname(run["trace_dir"]),
                                       "rank_trace.json"), "w") as f:
                    json.dump(rt, f, indent=1)
    return run["rank_trace"]


def ms_per_iter(run, *stages):
    """Device-busy ms per traced iteration under the given stages; None
    where no operation carries any rank stage."""
    rt = of(run)
    if rt is None or not rt["stage_seconds"]:
        return None
    return 1e3 * sum(rt["stage_seconds"].get(s, 0.0)
                     for s in stages) / rt["iterations"]
