"""What the program says about itself, read off a trace of the chip.

``data/fused_small.xplane.pb.gz`` was recorded on a TPU v5e by
``record_fixture.py`` (PR 25): the cell's own driver at 100,000 rows and 15
leaves, two whole ``bench_iteration`` spans, the program's phase scopes on
the device operations and its ``lgbt.*`` spans on the host's threads.
``data/tiny.xplane.pb`` (PR 24) has neither: it stands for a program that
lacks them, the parent of the PR that brought them."""

import gzip
import importlib.util
import json
import os

import pytest

from benchmark.harness import paths, program_trace, trace_reduce, work

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = ["phase_gradients_ms_per_iter", "phase_root_ms_per_iter",
       "phase_opening_ms_per_iter", "phase_grow_ms_per_iter",
       "phase_replay_ms_per_iter", "phase_emit_ms_per_iter",
       "phase_unscoped_ms_per_iter", "partition_xla_ms_per_iter",
       "sorts_per_iter", "host_dispatch_ms_per_iter",
       "host_d2h_wait_ms_per_iter", "host_assemble_ms_per_iter",
       "queued_at_dispatch", "idle_unattributed_pct"]
STAGES = NEW[:7]


def read(metric, run):
    spec = importlib.util.spec_from_file_location(
        "reader_" + metric,
        os.path.join(paths.BENCH_DIR, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def run_of(path):
    return {"trace_dir": None, "trace": trace_reduce.reduce(path),
            "program_trace": program_trace.reduce(path)}


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "fused_small.xplane.pb")
    with gzip.open(os.path.join(DATA, "fused_small.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        dst.write(src.read())
    return run_of(path)


def test_every_new_metric_is_in_the_benchmark_and_reads_a_number(run):
    listed = {m["name"]: m for m in
              paths.load_json(paths.ROOT, "BENCHMARK.json")["per_layer"]}
    for metric in NEW:
        assert listed[metric]["moves"] == "train_iters_per_s"
        assert "workloads" not in listed[metric]
        value = read(metric, run)
        assert isinstance(value, (int, float)) and value >= 0, metric
    assert read("phase_opening_ms_per_iter", run) == 0    # open_levels = 0
    assert read("queued_at_dispatch", run) == 1            # flush depth 1


def test_the_stages_add_up_to_the_busy_time(run):
    t = run["trace"]
    assert t["iterations"] == run["program_trace"]["iterations"] == 2
    busy_ms = 1e3 * t["busy_s"] / t["iterations"]
    stages = sum(read(m, run) for m in STAGES)
    assert stages == pytest.approx(busy_ms, rel=0.01)
    # and to what the benchmark had: XLA operations plus the kernels
    had = read("xla_ops_ms_per_iter", run) + sum(
        1e3 * (work.kernel_seconds_per_iter(run, g) or 0.0)
        for g in ("hist", "scan", "partition"))
    assert stages == pytest.approx(had, rel=0.01)
    assert read("phase_unscoped_ms_per_iter", run) < 0.06 * busy_ms


def test_phases_of_the_recorded_step(run):
    pt = run["program_trace"]
    assert pt["has_scopes"] and pt["has_spans"]
    assert set(pt["phase_seconds"]) == {
        "gradients", "root", "grow", "replay", "emit", "score_update",
        "unscoped"}
    # the partition's sorts are the largest single thing, in grow
    assert pt["stage_seconds"]["grow/partition"] > 0.4 * sum(
        pt["phase_seconds"].values())
    assert pt["sort_count"]["emit"] == 1 and pt["sort_count"]["grow"] >= 4
    assert pt["partition_xla_seconds"] == pytest.approx(
        pt["stage_seconds"]["grow/partition"])
    assert read("partition_xla_ms_per_iter", run) > \
        1e3 * pt["sort_seconds"]["grow"] / 2
    # the kernels carry their pinned names and sit in their stages
    ops = run["trace"]["op_seconds"]
    for kernel in ("build_histogram_packed", "build_histogram_segments",
                   "find_best_splits_batched"):
        assert ops[kernel] > 0


def test_host_spans_nest_and_explain_the_idle_gaps(run):
    pt = run["program_trace"]
    n = pt["span_count"]
    assert n["lgbt.iteration"] == n["lgbt.dispatch"] == n["lgbt.flush"] == 2
    assert n["lgbt.d2h_wait"] == n["lgbt.assemble_tree"] == 2
    s, self_s = pt["span_seconds"], pt["span_self_seconds"]
    assert s["lgbt.flush"] >= s["lgbt.d2h_wait"] + s["lgbt.assemble_tree"]
    assert self_s["lgbt.flush"] == pytest.approx(
        s["lgbt.flush"] - s["lgbt.d2h_wait"] - s["lgbt.assemble_tree"])
    assert self_s["lgbt.iteration"] < s["lgbt.iteration"]
    idle = run["trace"]["window_s"] - run["trace"]["busy_s"]
    assert sum(pt["gap_seconds"].values()) == pytest.approx(idle, rel=1e-6)
    assert read("idle_unattributed_pct", run) < 10.0
    assert read("host_d2h_wait_ms_per_iter", run) == pytest.approx(
        1e3 * s["lgbt.d2h_wait"] / 2)


def test_a_program_without_scopes_or_spans_reads_nothing():
    """The parent: every new reader returns None and does not raise; the
    metrics the benchmark had read as before."""
    run = run_of(os.path.join(DATA, "tiny.xplane.pb"))
    pt = run["program_trace"]
    assert pt is not None and not pt["has_scopes"] and not pt["has_spans"]
    for metric in NEW:
        assert read(metric, run) is None, metric
    assert read("device_idle_pct", run) is not None
    # an untraced run has no trace at all
    for metric in NEW:
        assert read(metric, {"trace_dir": None}) is None


def test_phase_of_and_nest():
    scopes = {"grow", "partition", "replay", "stall", "hist"}
    assert program_trace.phase_of(
        "jit(step)/grow/while/body/partition/cond/branch_1_fun/sort:",
        scopes) == ("grow", "partition")
    assert program_trace.phase_of("jit(step)/replay/while/body/sort:",
                                  scopes) == ("replay", "replay")
    assert program_trace.phase_of(
        "jit(step)/replay/stall/hist/jit(k)/k/pallas_call:", scopes) == (
            "replay", "hist")
    assert program_trace.phase_of("jit(step)/add:", scopes) == (None, None)
    assert program_trace.phase_of(None, scopes) == (None, None)
    spans = [{"name": n, "start": a, "end": b} for n, a, b in (
        ("it", 0, 100), ("dispatch", 10, 30), ("flush", 40, 90),
        ("wait", 45, 60), ("assemble", 60, 85), ("it", 100, 150))]
    nested = program_trace.nest(spans)
    assert [s["parent"] for s in nested] == [None, "it", "it", "flush",
                                             "flush", None]
    assert [s["self"] for s in nested] == [30, 20, 10, 15, 25, 50]
    assert program_trace.innermost(nested, 50) == "wait"
    assert program_trace.innermost(nested, 95) == "it"
    assert program_trace.innermost(nested, 500) is None


def test_names_file_lists_what_the_readers_look_for():
    names = program_trace.names()
    assert names["span_prefix"] == "lgbt."
    assert {"gradients", "root", "opening", "grow", "replay", "emit",
            "score_update"} == set(names["device_phases"])
    assert {"dispatch", "d2h_wait", "assemble_tree", "iteration",
            "flush"} <= set(names["host_spans"])
    # every pinned kernel name is found by exactly one kernel group
    groups = {g: work.kernel_needles(g)
              for g in ("hist", "scan", "partition")}
    for kernel in names["kernel_names"]:
        hits = [g for g, needles in groups.items()
                if any(n in kernel for n in needles)]
        assert len(hits) == 1, (kernel, hits)
    json.dumps(names)
