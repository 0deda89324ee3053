"""What the program says about itself in one traced run: device time by the
program's own phase scopes, and its ``lgbt.*`` host spans, both from the same
``.xplane.pb`` and so on one clock.

The names are data (``benchmark/phases.json``; a test of the program holds
them equal to ``lightgbm_tpu.observability.phases``).  A device operation's
phase is read off the ``tf_op`` stat of its event metadata, the operation's
``op_name`` path, of which every ``jax.named_scope`` is a component
(``jit(step)/grow/while/body/partition/cond/branch_1_fun/sort:`` is phase
``grow``, stage ``partition``; the profiler ends the path with ``:``).  A program that has no such scope or span (the
parent of the PR that added them) gives ``None`` here and every reader built
on this returns nothing.

Clipped to the whole ``bench_iteration`` spans exactly as ``trace_reduce``
clips, control-flow wrappers left out as there.  Device time is counted as
BUSY time: where two operations overlap the overlap is counted once, for the
one that started first, so the phases and ``unscoped`` add up to
``trace_reduce``'s ``busy_s``.
"""

import json
import os
import statistics

from benchmark.harness import trace_reduce, work, xplane_wire
from benchmark.harness.paths import BENCH_DIR, load_json

UNSCOPED = "unscoped"
SORT = "sort"
LONG_SORT_NS = 1e6      # a sort over the row array, not over a node table


def names():
    return load_json(BENCH_DIR, "phases.json")


def _wanted_event(prefix):
    def want(plane, name):
        return (plane.startswith(trace_reduce.DEVICE_PLANE)
                or name == trace_reduce.ITER_SPAN or name.startswith(prefix))
    return want


def load(path, prefix):
    """Device operations (one list a device) and host spans (one list a host
    thread) of one ``.xplane.pb``."""
    planes = xplane_wire.read(
        path,
        want_line=lambda plane, line: (
            not plane.startswith(trace_reduce.DEVICE_PLANE)
            or line == trace_reduce.OPS_LINE),
        want_event=_wanted_event(prefix))
    devices, threads, iters = {}, [], []
    for plane in planes:
        if plane["name"].startswith(trace_reduce.DEVICE_PLANE):
            ops = [ev for line in plane["lines"] for ev in line["events"]
                   if not any(w in ev["name"] for w in trace_reduce.WRAPPERS)]
            ops.sort(key=lambda ev: ev["start_ns"])
            devices[plane["name"]] = ops
            continue
        for line in plane["lines"]:
            spans = []
            for ev in line["events"]:
                a = ev["start_ns"]
                b = a + ev["duration_ns"]
                if ev["name"] == trace_reduce.ITER_SPAN:
                    iters.append((ev["name"], a, b))
                else:
                    spans.append({"name": ev["name"], "start": a, "end": b,
                                  "args": ev["stats"]})
            if spans:
                spans.sort(key=lambda s: (s["start"], -s["end"]))
                threads.append(spans)
    iters.sort(key=lambda e: e[1])
    return devices, threads, iters


def phase_of(tf_op, scopes):
    """(top-level scope, innermost scope) among the path's components, or
    (None, None) where the path holds none of the program's scopes."""
    found = [c for c in (tf_op or "").rstrip(":").split("/") if c in scopes]
    return (found[0], found[-1]) if found else (None, None)


def nest(spans):
    """Parent and self time of each span of one thread (spans sorted by
    start, longer first): a span's parent is the innermost span that holds it
    whole."""
    stack = []
    for s in spans:
        while stack and s["start"] >= stack[-1]["end"]:
            stack.pop()
        s["parent"] = stack[-1]["name"] if stack else None
        s["self"] = s["end"] - s["start"]
        if stack:
            stack[-1]["self"] -= min(s["end"], stack[-1]["end"]) - s["start"]
        stack.append(s)
    return spans


def innermost(spans, t):
    """Name of the shortest span that covers time ``t``, or None."""
    best = None
    for s in spans:
        if s["start"] <= t < s["end"] and (
                best is None or s["end"] - s["start"] < best[1]):
            best = (s["name"], s["end"] - s["start"])
    return best[0] if best else None


def _add(table, key, value):
    table[key] = table.get(key, 0) + value


def device_tables(ops, lo, hi, scopes, kernels):
    """One device's busy seconds by phase, stage and operation kind, and its
    merged busy intervals, inside the window."""
    t = {k: {} for k in ("phase_seconds", "stage_seconds",
                         "unscoped_after_seconds", "unscoped_op_seconds",
                         "sort_seconds", "sort_count", "long_sort_count")}
    t["partition_xla_seconds"], t["scoped_ops"] = 0.0, 0
    covered, last_phase, merged = lo, "start", []
    for ev in ops:
        a = max(ev["start_ns"], lo)
        b = min(ev["start_ns"] + ev["duration_ns"], hi)
        if b <= lo or a >= hi:
            continue
        busy = max(0.0, b - max(a, covered)) / 1e9
        covered = max(covered, b)
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
        top, inner = phase_of(ev["meta_stats"].get("tf_op"), scopes)
        short = trace_reduce.short_name(ev["name"])
        if top is None:
            top = inner = UNSCOPED
            _add(t["unscoped_after_seconds"], last_phase, busy)
            _add(t["unscoped_op_seconds"], short, busy)
        else:
            t["scoped_ops"] += 1
            last_phase = top
        _add(t["phase_seconds"], top, busy)
        _add(t["stage_seconds"],
             top if inner == top else top + "/" + inner, busy)
        if short == SORT:
            _add(t["sort_seconds"], top, busy)
            _add(t["sort_count"], top, 1)
            if b - a >= LONG_SORT_NS:
                _add(t["long_sort_count"], top, 1)
        if inner == "partition" and not any(k in short for k in kernels):
            t["partition_xla_seconds"] += busy
    return t, merged


def span_tables(threads, lo, hi, dispatch):
    """Seconds, self seconds and counts of the program's spans that start
    inside the window, and the median ``queued`` of the ``dispatch`` ones."""
    t = {"span_seconds": {}, "span_self_seconds": {}, "span_count": {}}
    queued = []
    for th in threads:
        for s in nest(th):
            if not lo <= s["start"] < hi:
                continue
            _add(t["span_seconds"], s["name"], (s["end"] - s["start"]) / 1e9)
            _add(t["span_self_seconds"], s["name"], s["self"] / 1e9)
            _add(t["span_count"], s["name"], 1)
            if s["name"] == dispatch and "queued" in s["args"]:
                queued.append(s["args"]["queued"])
    t["queued_at_dispatch"] = statistics.median(queued) if queued else None
    return t


def reduce(path):
    """Everything the readers need; ``None`` where the trace holds no whole
    ``bench_iteration`` span or no device.  Device tables are averaged over
    the devices, as ``trace_reduce`` averages."""
    cfg = names()
    prefix = cfg["span_prefix"]
    devices, threads, iters = load(path, prefix)
    win = trace_reduce.window_of(iters)
    if win is None or not devices:
        return None
    lo, hi, n_iter = win
    scopes = set(cfg["device_phases"] + cfg["device_stages"])
    every = [s for th in threads for s in th]
    out = {"iterations": n_iter, "devices": len(devices),
           "window_s": (hi - lo) / 1e9, "has_spans": bool(every),
           "gap_seconds": {}, "longest_gaps": []}
    for ops in devices.values():
        t, merged = device_tables(ops, lo, hi, scopes, work.kernel_needles())
        for key, value in t.items():
            if isinstance(value, dict):
                table = out.setdefault(key, {})
                for k, v in value.items():
                    _add(table, k, v / len(devices))
            else:
                _add(out, key, value / len(devices))
        # the device's idle gaps, by the innermost program span at the midpoint
        idle = trace_reduce.gaps(merged, lo, hi)
        # [seconds into the window, seconds long]: one long gap is a stall
        # (of the profiler's start, of a transfer), many short ones are the
        # spaces between operations
        out["longest_gaps"] += [[(a - lo) / 1e9, (b - a) / 1e9] for a, b
                                in sorted(idle, key=lambda g: g[0] - g[1])[:3]]
        for a, b in idle:
            _add(out["gap_seconds"],
                 innermost(every, (a + b) / 2) or "no_program_span",
                 (b - a) / 1e9 / len(devices))
    out["has_scopes"] = out.pop("scoped_ops") > 0
    out.update(span_tables(threads, lo, hi, prefix + "dispatch"))
    return out


def of(run):
    """The reduction of this run's trace, made once and kept on ``run`` (and
    written beside the trace for a look by hand); None in an untraced run."""
    if "program_trace" not in run:
        run["program_trace"] = None
        if run.get("trace_dir"):
            pt = reduce(trace_reduce.find_xplane(run["trace_dir"]))
            run["program_trace"] = pt
            if pt is not None:
                with open(os.path.join(os.path.dirname(run["trace_dir"]),
                                       "program_trace.json"), "w") as f:
                    json.dump(pt, f, indent=1)
    return run["program_trace"]


def phase_ms_per_iter(run, *phases):
    """Device-busy ms per traced iteration under the given top-level scopes
    (0 for a scope that ran nothing); None where the program has no scopes."""
    pt = of(run)
    if pt is None or not pt["has_scopes"]:
        return None
    return 1e3 * sum(pt["phase_seconds"].get(p, 0.0)
                     for p in phases) / pt["iterations"]


def span_ms_per_iter(run, span, self_time=False):
    """Host ms per traced iteration in one ``lgbt.*`` span; None where the
    program records no such span."""
    pt = of(run)
    if pt is None:
        return None
    name = names()["span_prefix"] + span
    table = pt["span_self_seconds" if self_time else "span_seconds"]
    if name not in table:
        return None
    return 1e3 * table[name] / pt["iterations"]
