"""The four-chip cell ``criteo-v5e128-share.data4-sort``: its driver and its
comparison at a size a CPU holds (four virtual devices), its readers on a
trace recorded on four v5e chips (``data/mesh4_small.xplane.pb.gz``: 200,000
rows, 15 leaves, two whole ``bench_iteration`` spans, four device planes; the
sharded wave learner through this cell's own driver), and its scope file
against the program's tuple."""

import gzip
import importlib.util
import os

# four virtual CPU devices for the cell's mesh: read when JAX first makes its
# backend, which no test module does while it is imported
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import pytest

import faults
from benchmark import run as bench_run
from benchmark.harness import (mesh_trace, paths, program_trace,
                               trace_reduce, work)

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "criteo-v5e128-share.data4-sort"
NEW = ["collective_ms_per_iter", "collective_exposed_ms_per_iter",
       "exchange_mib_per_iter", "device_skew_pct", "mesh_step_mfu",
       "mesh_hist_roofline"]
SMALL = {"rows": 30000, "holdout_rows": 5000,
         "params": {"num_leaves": 15, "parallel_mesh": "4"}}


def exchange_left_out():
    """Every wave's histogram exchange hands a device its slice of its OWN
    rows' histograms: the reduce-scatter without the reduce."""
    from jax import lax
    from lightgbm_tpu.parallel.compact_sharded import ShardedCompactLearner

    def make(orig):
        def patched(self, h, dim):
            size = h.shape[dim] // self.D
            return lax.dynamic_slice_in_dim(
                h, lax.axis_index(self.axis) * size, size, dim)
        return patched
    return faults._patched((ShardedCompactLearner, "_exchange", make))


def score_update_skipped():
    """The pipelined iteration's third dispatch hands back the train score it
    was given (``state_unchanged`` on the path this cell runs: that fault
    patches the fused step and the synchronous update, neither of which a
    sharded job takes)."""
    from lightgbm_tpu.boosting import gbdt
    return faults._patched(
        (gbdt, "_score_add_leaf", lambda orig: lambda score, *a, **kw: score))


# faults that the sharded, pipelined path can have: the benchmark's own that
# reach it, and the two above
MESH_FAULTS = dict({name: faults.FAULTS[name] for name in (
    "half_batch", "answer_altered", "predict_altered")},
    exchange_left_out=exchange_left_out,
    score_update_skipped=score_update_skipped)


def read(metric, run):
    spec = importlib.util.spec_from_file_location(
        "reader_" + metric,
        os.path.join(paths.BENCH_DIR, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def traced_run(tmp_path_factory, name):
    path = str(tmp_path_factory.mktemp("trace") / name)
    with gzip.open(os.path.join(DATA, name + ".gz")) as src, \
            open(path, "wb") as dst:
        dst.write(src.read())
    tree = {"num_leaves": 3, "internal_count": _np([1000, 600]),
            "leaf_count": _np([400, 350, 250]),
            "left_child": _np([1, -2]), "right_child": _np([-1, -3])}
    return {"trace_dir": None, "trace": trace_reduce.reduce(path),
            "program_trace": program_trace.reduce(path),
            "mesh_trace": mesh_trace.reduce(path),
            "window_trees": [tree], "rows": 1000,
            "device": {"kind": "TPU v5 lite"},
            "ctx": {"config": {"features": 67}}}


def _np(values):
    import numpy as np
    return np.asarray(values)


@pytest.fixture(scope="module")
def mesh4(tmp_path_factory):
    return traced_run(tmp_path_factory, "mesh4_small.xplane.pb")


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    """A one-chip program: no collective, no ``exchange`` scope."""
    return traced_run(tmp_path_factory, "fused_small.xplane.pb")


def drive():
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("JAX made its backend before this file set XLA_FLAGS")
    return bench_run.run_cell(CELL, 20261002, 1.0, 0, need_chip=False,
                              size_override=SMALL)


def test_the_cell_runs_and_is_correct_at_a_cpu_size():
    line = drive()
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["info"]["path"] == {"learner": "ShardedWaveLearner",
                                    "fused": False, "pipelined": True}
    assert set(line["metrics"]) == {"train_iters_per_s", "peak_hbm_mib",
                                    "setup_s"}


@pytest.mark.parametrize("fault", sorted(MESH_FAULTS))
def test_fault_under_the_timed_path_is_not_correct(fault):
    with MESH_FAULTS[fault]():
        line = drive()
    assert not line["correct"], (fault, line["checks"])


def test_the_cell_is_as_the_issue_states_it():
    cell, config, traffic, bench = paths.load_cell(CELL)
    assert cell["chips"] == 4 and traffic["kind"] == "train_job"
    assert (config["rows"], config["features"]) == (53125000, 67)
    assert config["rows"] == 4 * config["rows_a_chip"] \
        and 128 * config["rows_a_chip"] == 1_700_000_000
    assert config["params"] == {
        "objective": "binary", "num_leaves": 255, "learning_rate": 0.1,
        "max_bin": 255, "min_data_in_leaf": 20,
        "min_sum_hessian_in_leaf": 0.001, "tree_learner": "data"}
    assert config["reduced"] == ["rows"]
    assert traffic["extra_params"]["tree_learner"] == "data"
    assert traffic["extra_params"]["tpu_wave_pallas_partition"] == "off"
    assert traffic["warmup_iters"] == 3 and not traffic["valid_set"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for metric in NEW:
        assert listed[metric]["workloads"] == [CELL]
        assert listed[metric]["moves"] == "train_iters_per_s"
    # what divides the GLOBAL rows by ONE chip's peak stays with the one-chip
    # cells; the Pallas scan does not run under a mesh
    for metric in ("step_mfu", "hist_roofline", "scan_ms_per_iter"):
        assert CELL not in listed[metric]["workloads"]
    four = [c for c in bench["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_scope_file_equals_the_programs_tuple():
    from lightgbm_tpu.observability import phases
    assert tuple(mesh_trace.names()["mesh_stages"]) == phases.MESH_STAGES
    # the accepted phase file does not know the name: an operation under it
    # is booked under its phase and stage, which is what is wanted
    known = program_trace.names()
    scopes = set(known["device_phases"] + known["device_stages"])
    assert not set(phases.MESH_STAGES) & scopes
    assert program_trace.phase_of(
        "jit(f)/shard_map/grow/while/body/hist/exchange/reduce_scatter:",
        scopes) == ("grow", "hist")


def test_readers_on_the_recorded_four_chip_trace(mesh4):
    mt = mesh4["mesh_trace"]
    assert mt["devices"] == 4 and mt["iterations"] >= 2
    assert len(mt["busy_s_by_device"]) == 4
    values = {m: read(m, mesh4) for m in NEW}
    for metric, value in values.items():
        assert isinstance(value, float) and value >= 0, metric
    # the histogram exchange is a reduce-scatter, the best splits an
    # all-gather, the counts an all-reduce: all three occur, and nearly
    # every one under the program's scope
    assert {"reduce-scatter", "all-gather", "all-reduce"} <= \
        set(mt["collective_count"])
    assert mt["scoped"] > 20 * mt["unscoped"]
    # one reduce-scatter a wave, in ``grow``
    assert mt["phase_count"]["reduce-scatter@grow"] >= 2
    assert 0 < values["collective_exposed_ms_per_iter"] \
        <= values["collective_ms_per_iter"]
    busy_ms = 1e3 * mesh4["trace"]["busy_s"] / mesh4["trace"]["iterations"]
    assert values["collective_ms_per_iter"] < busy_ms
    # 15 leaves, 67 -> 72 columns, 256 bins: a wave of 8 members hands over
    # 8 x 72 x 255 x 12 B = 1,762,560 B, and nothing near a GiB
    assert mt["payload_count"]["reduce-scatter:1762560"] >= 2
    assert 1.0 < values["exchange_mib_per_iter"] < 64.0
    assert values["device_skew_pct"] < 5.0
    assert 0 < values["mesh_step_mfu"] < 100
    assert 0 < values["mesh_hist_roofline"] < 100
    # the accepted readers find the same kernels on the four planes
    assert work.kernel_seconds_per_iter(mesh4, "hist") > 0
    assert mesh4["program_trace"]["has_scopes"]
    assert read("sorts_per_iter", mesh4) > 0
    # the one-chip shares read four times the mesh's: why they keep to
    # their one-chip cells
    assert read("hist_roofline", mesh4) == pytest.approx(
        4 * values["mesh_hist_roofline"])


def test_readers_return_nothing_for_a_program_on_one_chip(one_chip):
    assert not one_chip["mesh_trace"].get("collective_count")
    for metric in NEW:
        assert read(metric, one_chip) is None, metric


def test_device_table_counts_by_opcode_and_clips_to_the_window():
    def op(name, start, dur, tf_op=""):
        return {"name": name, "start_ns": start, "duration_ns": dur,
                "meta_stats": {"tf_op": tf_op}}
    ops = [op("%psum.3 = f32[3]{0} all-reduce(f32[3] %x)", 0, 10,
              "jit(f)/root/exchange/psum:"),
           op("%fusion.1 = f32[4] fusion(f32[4] %all-gather.1)", 10, 10,
              "jit(f)/grow/hist/x:"),
           op("%reduce_scatter.2 = (f32[2,8]{1,0:T(128)}) reduce-scatter-start("
              "f32[8,8] %y)", 20, 5,
              "jit(f)/grow/hist/vmap(exchange)/reduce_scatter:"),
           op("%fusion.7 = f32[4] fusion(f32[4] %p)", 22, 8,
              "jit(f)/grow/scan/x:"),
           op("%rs-done.2 = f32[2,8] reduce-scatter-done(f32[2,8] %z)", 25, 30,
              "jit(f)/grow/hist/vmap(exchange)/reduce_scatter:"),
           op("%all-gather.1", 90, 20, "jit(f)/grow/scan/exchange/ag:")]
    cfg = mesh_trace.names()
    t = mesh_trace.device_table(ops, 5, 100, cfg, {"root", "grow", "hist",
                                                   "scan"}, 4)
    assert t["collective_count"] == {"all-reduce": 1, "reduce-scatter": 2,
                                     "all-gather": 1}
    assert t["collective_seconds"]["all-reduce"] == pytest.approx(5e-9)
    assert t["collective_seconds"]["reduce-scatter"] == pytest.approx(35e-9)
    assert t["collective_seconds"]["all-gather"] == pytest.approx(10e-9)
    # the fusion at 22..30 hides 3 ns of the start and 5 ns of the done
    assert t["exposed_seconds"]["reduce-scatter"] == pytest.approx(27e-9)
    assert t["exposed_seconds"]["all-reduce"] == pytest.approx(5e-9)
    assert t["scoped"] == 4 and t["unscoped"] == 0
    # a pair is one call, counted under its phase with its operand's bytes
    assert t["phase_count"] == {"all-reduce@root": 1,
                                "reduce-scatter@grow": 1,
                                "all-gather@grow": 1}
    assert t["payload_count"] == {"all-reduce:12": 1,
                                  "reduce-scatter:256": 1}
    assert t["busy_s"] == pytest.approx((55 - 5 + 10) * 1e-9)
