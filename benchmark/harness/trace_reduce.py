"""From the profiler's ``.xplane.pb`` to numbers, with nothing but JAX
(``jax.profiler.ProfileData``).

The device plane (``/device:TPU:<n>``) has one line of operations
(``XLA Ops``); the host planes carry the harness's own ``TraceAnnotation``
spans (``bench_*`` around an iteration, ``host_*`` around the calls into the
program's layers).  The traced window is the stretch of whole
``bench_iteration`` spans; device time is clipped to it.
"""

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
# control flow shows as an event around the events of its body: left out,
# so that an operation's time is counted once
WRAPPERS = (" while(", " conditional(", " call(")
SPAN_PREFIXES = ("bench_", "host_")
ITER_SPAN = "bench_iteration"


def short_name(event_name):
    """An operation's event is named by its whole HLO text
    (``%fusion.12 = f32[...] fusion(...)``): keep the instruction's name and
    drop its number, so that all calls of one kind add up."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """``{"devices": {plane: [(name, start_ns, end_ns)]}, "spans": [...]}``."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(short_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events
                            if not any(w in ev.name for w in WRAPPERS)]
            ops.sort(key=lambda e: e[1])
            devices[plane.name] = ops
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    spans.sort(key=lambda e: e[1])
    return {"devices": devices, "spans": spans}


def window_of(spans):
    """(start_ns, end_ns, iterations): the whole iteration spans."""
    its = [s for s in spans if s[0] == ITER_SPAN]
    if not its:
        return None
    return its[0][1], its[-1][2], len(its)


def clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def union(events):
    """Merged busy intervals [(start, end)] of events sorted by start."""
    merged = []
    for _, a, b in events:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def gaps(merged, lo, hi):
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def innermost_span(spans, t):
    """Name of the shortest harness span that covers time ``t``."""
    best = None
    for name, a, b in spans:
        if a <= t < b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "no_host_span"


def reduce(path):
    """Everything the per-layer readers and the result line need from one
    trace.  Times are seconds; per device, then averaged over devices."""
    raw = load(path)
    if not raw["devices"]:
        return None
    win = window_of(raw["spans"])
    all_ops = [e for ops in raw["devices"].values() for e in ops]
    if win is None:           # no harness span: the extent of the device's work
        lo = min(e[1] for e in all_ops)
        hi = max(e[2] for e in all_ops)
        iters = None
    else:
        lo, hi, iters = win
    per_dev, by_name, gap_by_span = [], {}, {}
    for ops in raw["devices"].values():
        ops = clip(ops, lo, hi)
        merged = union(ops)
        per_dev.append(sum(b - a for a, b in merged) / 1e9)
        for name, a, b in ops:
            by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
        for a, b in gaps(merged, lo, hi):
            span = innermost_span(raw["spans"], (a + b) // 2)
            gap_by_span[span] = gap_by_span.get(span, 0.0) + (b - a) / 1e9
    n_dev = len(per_dev)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(per_dev) / n_dev,
        "iterations": iters,
        "devices": n_dev,
        "op_seconds": {k: v / n_dev for k, v in by_name.items()},
        "gap_seconds": {k: v / n_dev for k, v in gap_by_span.items()},
    }


def top(table, n=10):
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def seconds_matching(op_seconds, needles):
    """Total seconds of the operations whose name holds one of ``needles``;
    None where no such operation ran."""
    hit = [v for k, v in op_seconds.items() if any(s in k for s in needles)]
    return sum(hit) if hit else None
