#!/usr/bin/env python3
"""Records the small trace that ``test_program_trace.py`` reads: the cell's
own driver (``harness/kinds/train_job.py``) at a size a fixture can hold, on
the chip.  By hand, after a change to the program's scopes or spans:

    chiprun -- python3 benchmark/tests/record_fixture.py

writes ``chiprun_out/fixture/fused_small.xplane.pb.gz`` (copy it to
``benchmark/tests/data/``) and prints what the reductions read off it.
The Python tracer is off (a host plane of Python calls would be most of the
file); the program's ``lgbt.*`` spans and the harness's ``bench_iteration``
are ``TraceAnnotation`` events and stay.  At this size the host outruns the
device (a step is 2 ms), and a window of two host iterations would hold half
a device step; ``tpu_pipeline_flush_depth=1`` makes every host iteration wait
for the step before it, as the full-size job does with its queue of 8.
"""

import argparse
import gzip
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT]

from benchmark.harness import (device, paths, program_trace,    # noqa: E402
                               trace_reduce, xplane_wire)
from benchmark.harness.events import CompileEvents               # noqa: E402
from benchmark.harness.kinds import train_job                    # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="higgs.fused-sort")
    ap.add_argument("--rows", type=int, default=100000)
    ap.add_argument("--leaves", type=int, default=15)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--skip", type=int, default=12,
                    help="iterations of the window before the trace")
    ap.add_argument("--seed", type=int, default=2501)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "fixture"))
    ap.add_argument("--no-chip", action="store_true")
    args = ap.parse_args()

    import jax
    import lightgbm_tpu
    lightgbm_tpu.use_compile_cache()
    cell, config, traffic, _ = paths.load_cell(args.workload)
    stamp = device.stamp() if args.no_chip else device.require_tpu(1)
    # the stretch traced: one more span than whole iterations
    traffic = dict(traffic, trace_skip_iters=args.skip,
                   trace_iters=args.iters + 1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    start = jax.profiler.start_trace
    jax.profiler.start_trace = lambda d: start(d, profiler_options=opts)
    ctx = dict(cell=cell, config=config, traffic=traffic, seed=args.seed,
               seconds=3.0, trace=True, chips=1, t_start=time.perf_counter(),
               events=CompileEvents().listen(), device=stamp,
               size_override={"rows": args.rows, "holdout_rows": 5000,
                              "params": {"num_leaves": args.leaves,
                                         "tpu_pipeline_flush_depth": 1}})
    run = train_job.run(ctx)
    path = trace_reduce.find_xplane(run["trace_dir"])
    os.makedirs(args.out, exist_ok=True)
    out = os.path.join(args.out, "fused_small.xplane.pb.gz")
    with open(path, "rb") as src, gzip.open(out, "wb", 9) as dst:
        dst.write(xplane_wire.without_planes(src.read(),
                                             {"/host:metadata"}))
    tr = trace_reduce.reduce(path)
    pt = program_trace.reduce(path)
    print(json.dumps({
        "device": stamp, "raw_bytes": os.path.getsize(path),
        "gz_bytes": os.path.getsize(out), "started": run["started"],
        "trace": tr and {k: tr[k] for k in ("window_s", "busy_s",
                                            "iterations")},
        "ops": tr and trace_reduce.top(tr["op_seconds"], 40),
        "program_trace": pt}, indent=1))


if __name__ == "__main__":
    sys.exit(main())
