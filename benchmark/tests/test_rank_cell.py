"""The ranking cell ``istella-letor.rank-fused``: its kind, generator,
reference and comparison at a size a CPU holds, the faults its comparison
must catch, and its readers on hand-made traces."""

import contextlib
import importlib.util
import os

import numpy as np
import pytest

import faults
from benchmark import run as bench_run
from benchmark.harness import paths, rank_data, rank_trace
from benchmark.reference import lambdarank_plain

CELL = "istella-letor.rank-fused"
NEW = ["rank_pairs_ms_per_iter", "rank_order_ms_per_iter",
       "rank_pair_fill_pct"]
SMALL = {"rows": 30000, "queries": 95, "holdout_rows": 5000,
         "params": {"num_leaves": 15}}


def longest_docs_left_out():
    """The ranking path's own fault: in every query, the documents past
    half of its length class take part in no pair (a query truncated)."""
    import jax.numpy as jnp
    from lightgbm_tpu.rank_objective import LambdarankNDCG

    def make(orig):
        def patched(self, s, lab, inv):
            q = lab.shape[-1]
            return orig(self, s, jnp.where(jnp.arange(q) < q // 2, lab, -1),
                        inv)
        return patched
    return faults._patched((LambdarankNDCG, "_batch", make))


RANK_FAULTS = dict(faults.FAULTS, longest_docs_left_out=longest_docs_left_out)


def drive(seed=20261015, fault=None):
    with (fault() if fault else contextlib.nullcontext()):
        return bench_run.run_cell(CELL, seed, 1.0, 0, need_chip=False,
                                  size_override=SMALL)


def read(metric, run):
    spec = importlib.util.spec_from_file_location(
        "reader_" + metric,
        os.path.join(paths.BENCH_DIR, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def test_the_cell_runs_and_is_correct_at_a_cpu_size():
    line = drive()
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["info"]["path"] == {"learner": "WaveTPUTreeLearner",
                                    "fused": True, "pipelined": True}
    assert set(line["metrics"]) == {"train_iters_per_s", "peak_hbm_mib",
                                    "setup_s"}
    assert 0 < line["info"]["ndcg@10_holdout"] <= 1
    assert set(line["checks"]) == {
        "count_mismatch", "leaf_value_gap", "leaf_value_gap_weighted",
        "root_gain_gap", "score_gap", "predict_gap", "trees_missing"}


@pytest.mark.parametrize("fault", sorted(RANK_FAULTS))
def test_fault_under_the_timed_path_is_not_correct(fault):
    line = drive(fault=RANK_FAULTS[fault])
    assert not line["correct"], (fault, line["checks"])


def test_the_cell_is_the_published_train_split():
    cell, config, traffic, bench = paths.load_cell(CELL)
    assert cell["chips"] == 1 and traffic["kind"] == "rank_job"
    assert (config["rows"], config["queries"], config["features"]) == \
        (7325625, 23219, 220)
    assert config["params"] == {
        "objective": "lambdarank", "num_leaves": 255, "learning_rate": 0.1,
        "max_bin": 255, "min_data_in_leaf": 0,
        "min_sum_hessian_in_leaf": 100.0}
    assert config["reduced"] == [] and config["job_iterations"] == 500
    assert traffic["warmup_iters"] == 3 and not traffic["valid_set"]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for metric in NEW:
        assert listed[metric]["workloads"] == [CELL]
        assert listed[metric]["layer"] == "objective"
        assert listed[metric]["moves"] == "train_iters_per_s"


def test_the_generator_gives_the_stated_queries_and_one_layout():
    _, config, _, _ = paths.load_cell(CELL)
    spec = rank_data.spec_of(config["generator"])
    a = rank_data.query_lengths(spec, config["queries"], config["rows"])
    b = rank_data.query_lengths(spec, config["queries"], config["rows"])
    assert a.size == 23219 and a.sum() == 7325625
    assert a.min() >= 2 and a.max() <= 4096
    np.testing.assert_array_equal(a, b)
    # two seeds: the same layout, other rows and labels
    X1, y1 = rank_data.make_queries(config["generator"], 1, 0, a[:50], 220)
    X2, y2 = rank_data.make_queries(config["generator"], 2, 0, a[:50], 220)
    assert X1.shape == X2.shape == (int(a[:50].sum()), 220)
    assert not np.array_equal(X1, X2)
    assert X1.min() > 0                       # shifted: the zero bin is bin 0
    shares = np.bincount(y1.astype(int), minlength=5) / y1.size
    np.testing.assert_allclose(shares, spec["label_shares"], atol=0.01)
    h = rank_data.holdout_lengths(spec, 100000, 315.5, 7)
    assert 90000 < h.sum() <= 100000


def test_reference_ndcg_of_a_perfect_and_a_reversed_order():
    y = np.array([3, 2, 1, 0, 0, 1], np.float32)
    bounds = np.array([0, 4, 6])
    assert lambdarank_plain.ndcg_at(y.astype(float), y, bounds, 10) == 1.0
    rev = lambdarank_plain.ndcg_at(-y.astype(float), y, bounds, 10)
    assert 0 < rev < 1


def _op(start, dur, tf_op):
    return {"name": "%fusion.1 = f32[8] fusion()", "start_ns": start,
            "duration_ns": dur, "meta_stats": {"tf_op": tf_op}}


def test_rank_trace_sums_busy_time_by_stage_inside_the_window():
    stages = tuple(rank_trace.names()["rank_stages"])
    ops = [_op(0, 10, "jit(step)/gradients/rank_order/gather:"),
           _op(10, 20, "jit(step)/gradients/while/body/rank_order/sort:"),
           _op(25, 30, "jit(step)/gradients/while/body/rank_pairs/reduce:"),
           _op(60, 5, "jit(step)/gradients/vmap(rank_writeback)/gather:"),
           _op(70, 40, "jit(step)/grow/partition/sort:")]
    t = rank_trace.device_table(ops, 5, 100, stages)
    assert t["rank_order"] == pytest.approx(25e-9)      # 5..30, clipped
    assert t["rank_pairs"] == pytest.approx(25e-9)      # 30..55, overlap once
    assert t["rank_writeback"] == pytest.approx(5e-9)
    assert set(t) == set(stages)
    run = {"rank_trace": {"iterations": 2, "devices": 1,
                          "stage_seconds": {"rank_pairs": 0.4,
                                            "rank_order": 0.1,
                                            "rank_writeback": 0.05}}}
    assert read("rank_pairs_ms_per_iter", run) == pytest.approx(200.0)
    assert read("rank_order_ms_per_iter", run) == pytest.approx(75.0)


def test_readers_return_nothing_for_a_program_without_the_layout():
    run = {"rank_trace": {"iterations": 2, "devices": 1,
                          "stage_seconds": {}}, "rank_layout": {}}
    for metric in NEW:
        assert read(metric, run) is None, metric
    run["rank_layout"] = {"real_pairs": 5, "computed_pairs": 10}
    assert read("rank_pair_fill_pct", run) == pytest.approx(50.0)


def test_rank_trace_finds_no_stage_in_a_recorded_chip_trace_without_them(
        tmp_path):
    """A one-chip fused step recorded on a v5e (binary objective): whole
    iterations and device operations, no rank stage, so the readers say
    nothing."""
    import gzip
    path = str(tmp_path / "fused_small.xplane.pb")
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "fused_small.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        dst.write(src.read())
    rt = rank_trace.reduce(path)
    assert rt["iterations"] >= 1 and rt["devices"] == 1
    assert rt["stage_seconds"] == {}
    run = {"rank_trace": rt}
    assert read("rank_pairs_ms_per_iter", run) is None
    assert read("rank_order_ms_per_iter", run) is None
