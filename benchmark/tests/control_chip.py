#!/usr/bin/env python3
"""Reads, on the chip at the cell's own size, what the limits of ``correct``
are set from: sound runs, the control (the reference in bfloat16 in the
program's place; the program's own lower-precision paths) and the faults.
One process; the rows and the binned dataset of a seed are shared by its runs.

    python3 benchmark/tests/control_chip.py --workload higgs.fused-sort \\
        --seeds 11,12,13 --seconds 5 --modes sound,bf16,half_batch

One JSON line per (seed, mode) on standard output.  The benchmark's own runs
never call this.
"""

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)), HERE]

from benchmark import run as bench_run                      # noqa: E402
from benchmark.harness import device, paths                 # noqa: E402
from benchmark.harness.kinds import train_job               # noqa: E402
import faults                                               # noqa: E402
from conftest import SMALL, VALID_CELL                                 # noqa: E402

# mode -> (run_cell overrides, fault)
MODES = {
    "sound": ({}, None),
    "bf16": ({"control": "bf16"}, None),
    "bf16_all": ({"control": "bf16_all"}, None),
    "bf16x2": ({"param_override": {"tpu_hist_precision": "bf16x2"}}, None),
    "quant": ({"param_override": {"tpu_quantized_grad": "on"}}, None),
    **{name: ({}, name) for name in faults.VALID_FAULTS},
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--modes", default="sound,bf16,half_batch")
    ap.add_argument("--no-chip", action="store_true",
                    help="rehearse on whatever JAX finds, at a small size")
    args = ap.parse_args()
    cell, config, traffic, _ = paths.load_cell(args.workload, [VALID_CELL])
    small = SMALL if args.no_chip else None
    if not args.no_chip:
        device.require_tpu(cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = dict(config=config, traffic=traffic, seed=seed,
                   size_override=small)
        prepared = train_job.prepare(ctx)
        for mode in args.modes.split(","):
            over, fault = MODES[mode]
            with faults.VALID_FAULTS[fault]() if fault else contextlib.nullcontext():
                line = bench_run.run_cell(
                    args.workload, seed, args.seconds, 0,
                    need_chip=not args.no_chip, size_override=small,
                    extra_cells=[VALID_CELL],
                    prepared=prepared, **over)
            print(json.dumps({
                "seed": seed, "mode": mode, "correct": line["correct"],
                "checks": {k: c["value"] for k, c in line["checks"].items()},
                "iters_per_s": line["metrics"]["train_iters_per_s"]["value"],
                "check_s": line["info"]["check_s"],
                "watched": {k: line["info"][k] for k in
                            ("gain_gap", "split_shortfall", "worst_leaf_rows")},
                "trees": line["info"]["trees"]}), flush=True)


if __name__ == "__main__":
    main()
