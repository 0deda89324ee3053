#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell from ``BENCHMARK.json`` (configuration, traffic mix and metric
readers are files found by name), fails without a TPU, runs the traffic kind's
driver, decides ``correct`` against the plain reference, and prints ONE JSON
object as the last line of standard output.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.
"""

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse                         # noqa: E402
import importlib                        # noqa: E402
import importlib.util                   # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import sys                              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device, paths, trace_reduce   # noqa: E402
from benchmark.harness.events import CompileEvents          # noqa: E402

EXIT_NO_CHIP, EXIT_NO_PROGRAM = 3, 4


def _reader(folder, name):
    """The metric's reader: ``benchmark/<folder>/<name>.py``, found by name."""
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{abs(hash(name))}",
        os.path.join(paths.BENCH_DIR, folder, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _listed(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def run_cell(workload, seed, seconds, trace, need_chip=True, extra_cells=(),
             **overrides):
    """One run of one cell; returns the result line as a dict.  Tests pass
    ``need_chip=False`` and a small ``size_override``; the command never does."""
    cell, config, traffic, bench = paths.load_cell(workload, extra_cells)
    import lightgbm_tpu
    lightgbm_tpu.use_compile_cache()
    stamp = device.require_tpu(cell["chips"]) if need_chip else device.stamp()
    events = CompileEvents().listen()
    ctx = dict(cell=cell, config=config, traffic=traffic, seed=seed,
               seconds=seconds, trace=bool(trace), chips=cell["chips"],
               t_start=T_START, events=events, device=stamp, **overrides)
    kind = importlib.import_module("benchmark.harness.kinds." + traffic["kind"])
    run = kind.run(ctx)
    run.update(ctx=ctx, device=stamp)

    dev = dict(stamp, memory_peak_bytes=run["peak_bytes"])
    line = {"attempted": run["started"]}
    if trace:
        run["trace"] = trace_reduce.reduce(
            trace_reduce.find_xplane(run["trace_dir"]))
        if run["trace"] is None or run["trace"]["busy_s"] <= 0:
            raise RuntimeError("the trace shows no operation on the device")
        dev.update(busy_s=run["trace"]["busy_s"],
                   window_s=run["trace"]["window_s"])
        with open(os.path.join(os.path.dirname(run["trace_dir"]),
                               "trace_ops.json"), "w") as f:
            json.dump(run["trace"], f)         # every operation, for a look by hand
        line["breakdown"] = {
            "device_ops": trace_reduce.top(run["trace"]["op_seconds"]),
            "idle_gaps": trace_reduce.top(run["trace"]["gap_seconds"])}
        wanted, folder = bench["per_layer"], "layer_metrics"
    else:
        wanted, folder = bench["end_to_end"], "end_to_end"

    # correct: once the window has closed and the peak has been read
    t_check = time.perf_counter()
    correct, checks, info = kind.check(run)
    run["check_s"] = time.perf_counter() - t_check

    metrics = {}
    for m in wanted:
        if not _listed(m, cell["name"]):
            continue
        value = _reader(folder, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line.update(correct=correct, failed=run["failed"], metrics=metrics,
                device=dev, info=dict(info, path=run["path"],
                                      clocks=run["clocks"],
                                      window_s=run["window_s"],
                                      check_s=run["check_s"],
                                      compile_cache={"hits": events.hits,
                                                     "misses": events.misses}),
                checks=checks)
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds, args.trace)
    except ImportError as e:
        print(f"benchmark/run.py needs the program beside it: {e}",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    except device.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    sys.stdout.flush()
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
