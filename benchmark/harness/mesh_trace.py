"""What a traced run of a job over several chips says about its mesh: the
collective operations of the device trace, the part of their time in which
the device runs nothing else, how often each exchange site of the program
ran, and how evenly the devices were busy.

Read from the same ``.xplane.pb`` as ``program_trace`` reads, through its
loader, clipped to the whole ``bench_iteration`` spans as there, control-flow
wrappers left out.  One table a device, then the mean over the devices (as
``trace_reduce`` averages), with each device's busy seconds kept beside it.

A collective is found by its HLO OPCODE (``benchmark/phases_mesh.json``): an
event is named by its whole HLO text, ``%psum.43 = f32[3]{0} all-reduce(...)``,
whose instruction NAME is sometimes the opcode and sometimes the JAX primitive
that made it.  The program's scope ``exchange`` in the event's ``op_name``
says that it is one of the program's own exchange sites.  A program without
collectives (one chip), or without the scope (the parent of the PR that added
it), gives empty tables or zero counts here and nothing raises.
"""

import json
import os
import re

from benchmark.harness import program_trace, trace_reduce
from benchmark.harness.paths import BENCH_DIR, load_json

ITEMSIZE = {"f32": 4, "s32": 4, "u32": 4, "f16": 2, "bf16": 2, "s16": 2,
            "u16": 2, "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8,
            "u64": 8}
_SHAPE = re.compile(r"\b(" + "|".join(ITEMSIZE) + r")\[([\d,]*)\]")


def names():
    return load_json(BENCH_DIR, "phases_mesh.json")


def collective_of(event_name, opcodes):
    """(opcode, half) of one event, or (None, None).  ``half`` is ``"start"``
    or ``"done"`` for the two events of an asynchronous pair, else ``""``.
    In the HLO text the opcode stands before its operands
    (``... reduce-scatter(f32[...] %bitcast.2169)``; an operand is written
    ``%all-gather.17``, with no space before and no bracket after); a trace
    that names events by the instruction alone is read by that name."""
    head, _, text = event_name.partition(" = ")
    for op in opcodes:
        for half in ("", "start", "done"):
            if f" {op}{'-' + half if half else ''}(" in text:
                return op, half
    if not text:
        name = trace_reduce.short_name(head)
        for op in opcodes:
            if name.startswith(op):
                return op, ("start" if name.endswith("-start") else
                            "done" if name.endswith("-done") else "")
    return None, None


def result_bytes(event_name, opcode):
    """Bytes of the instruction's result, summed over a tuple's parts, read
    off the HLO text before the opcode; None where the name holds no text."""
    _, _, text = event_name.partition(" = ")
    at = text.find(" " + opcode)
    if at < 0:
        return None
    total = 0
    for dtype, dims in _SHAPE.findall(text[:at]):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * ITEMSIZE[dtype]
    return total or None


def payload_bytes(event_name, opcode, devices):
    """What one device hands to the collective (the program's ledger counts
    the same): a reduce-scatter's operand is its result times the devices,
    an all-gather's its result over the devices, an all-reduce's its
    result."""
    size = result_bytes(event_name, opcode)
    if size is None:
        return None
    if opcode == "reduce-scatter":
        return size * devices
    if opcode == "all-gather":
        return size // devices
    return size


def _overlap(a, b, merged):
    """Length of [a, b) covered by the sorted disjoint intervals."""
    covered = 0
    for lo, hi in merged:
        if hi <= a:
            continue
        if lo >= b:
            break
        covered += min(b, hi) - max(a, lo)
    return covered


def device_table(ops, lo, hi, cfg, phases, devices):
    """One device's collective seconds (all, and exposed: with no other
    operation running), events by opcode, by phase and by payload, and its
    busy seconds."""
    opcodes, scoped = cfg["collective_opcodes"], set(cfg["mesh_stages"])
    t = {"collective_seconds": {}, "exposed_seconds": {},
         "collective_count": {}, "phase_count": {}, "payload_count": {},
         "scoped": 0, "unscoped": 0}
    coll, other = [], []
    for ev in ops:
        a = max(ev["start_ns"], lo)
        b = min(ev["start_ns"] + ev["duration_ns"], hi)
        if b <= lo or a >= hi:
            continue
        op, half = collective_of(ev["name"], opcodes)
        (coll if op else other).append((ev, op, half, a, b))
    merged_other = trace_reduce.union([(None, a, b)
                                       for _, _, _, a, b in other])
    for ev, op, half, a, b in coll:
        _add(t["collective_seconds"], op, (b - a) / 1e9)
        _add(t["exposed_seconds"], op,
             (b - a - _overlap(a, b, merged_other)) / 1e9)
        _add(t["collective_count"], op, 1)
        tf_op = ev["meta_stats"].get("tf_op") or ""
        # a scope entered under a transform reads ``vmap(exchange)``
        path = {c.split("(")[-1].rstrip(")")
                for c in tf_op.rstrip(":").split("/")}
        t["scoped" if scoped & path else "unscoped"] += 1
        if half == "done":      # a pair is one call: counted at its start
            continue
        top, _ = program_trace.phase_of(tf_op, phases)
        _add(t["phase_count"], f"{op}@{top or program_trace.UNSCOPED}", 1)
        size = payload_bytes(ev["name"], op, devices)
        if size is not None:
            _add(t["payload_count"], f"{op}:{size}", 1)
    every = sorted(((None, a, b) for _, _, _, a, b in coll + other),
                   key=lambda e: e[1])
    t["busy_s"] = sum(b - a for a, b in trace_reduce.union(every)) / 1e9
    return t


def _add(table, key, value):
    table[key] = table.get(key, 0) + value


def reduce(path):
    """The mean table over the devices and each device's busy seconds, or
    None where the trace holds no whole iteration or no device."""
    known = program_trace.names()
    devices, _, iters = program_trace.load(path, known["span_prefix"])
    win = trace_reduce.window_of(iters)
    if win is None or not devices:
        return None
    lo, hi, n_iter = win
    phases = set(known["device_phases"] + known["device_stages"])
    cfg = names()
    out = {"iterations": n_iter, "devices": len(devices),
           "window_s": (hi - lo) / 1e9, "busy_s_by_device": [],
           "scoped": 0, "unscoped": 0}
    for plane in sorted(devices):
        t = device_table(devices[plane], lo, hi, cfg, phases, len(devices))
        out["busy_s_by_device"].append(t.pop("busy_s"))
        for key, value in t.items():
            if isinstance(value, dict):
                table = out.setdefault(key, {})
                for k, v in value.items():
                    _add(table, k, v / len(devices))
            else:
                out[key] += value
    return out


def of(run):
    """This run's table, made once and kept on ``run`` (and written beside
    the trace for a look by hand); None in an untraced run."""
    if "mesh_trace" not in run:
        run["mesh_trace"] = None
        if run.get("trace_dir"):
            mt = reduce(trace_reduce.find_xplane(run["trace_dir"]))
            run["mesh_trace"] = mt
            if mt is not None:
                with open(os.path.join(os.path.dirname(run["trace_dir"]),
                                       "mesh_trace.json"), "w") as f:
                    json.dump(mt, f, indent=1)
    return run["mesh_trace"]


def ms_per_iter(run, key):
    """One of the collective tables in ms per traced iteration; None where
    no collective operation ran."""
    mt = of(run)
    if mt is None or not mt.get(key):
        return None
    return 1e3 * sum(mt[key].values()) / mt["iterations"]


def exchange_bytes_per_iter(run):
    """Bytes one device hands to the program's exchange sites in a traced
    iteration: the payload of every collective call in the trace (a pair is
    one call), where the program marks its exchanges with the scope.  The
    compiler may merge neighbouring sites into one operation (the root's
    reduce-scatter and its three scalar all-reduces ran as ONE all-reduce of
    220,332 B on the chip) and keeps the bytes, so the sum is what the
    program's ``CollectiveLedger`` reckons from its shapes: bytes a call of
    each site times the calls.  None where no collective carries the scope
    (one chip; the parent of the PR that added it)."""
    mt = of(run)
    if mt is None or not mt["scoped"] or not mt.get("payload_count"):
        return None
    return sum(count * int(key.rsplit(":", 1)[1])
               for key, count in mt["payload_count"].items()) \
        / mt["iterations"]
