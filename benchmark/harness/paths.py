"""Where the benchmark's files are, found by name."""

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload, extra_cells=()):
    """(cell, config, traffic, benchmark) for one entry of ``workloads``.
    The configuration's file comes from its ``file`` key; the traffic mix is
    ``benchmark/traffic/<traffic>.json``.  ``extra_cells`` are cells that
    ``BENCHMARK.json`` does not hold yet (the tests' and the control's)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in list(extra_cells) + bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic, bench
