"""The trace reduction on a small trace recorded on a TPU v5e (three
``bench_iteration`` spans, each a matmul, a wait and a sort; recorded by
PR 24 with jax.profiler and TraceAnnotation)."""

import os

import pytest

from benchmark.harness import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_window_is_the_whole_iteration_spans(reduced):
    assert reduced["iterations"] == 3 and reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.01251122, rel=1e-6)


def test_busy_is_the_union_of_device_operations(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["busy_s"] == pytest.approx(0.000174064, rel=1e-6)
    # nothing overlaps in this trace, so the union equals the sum
    assert sum(reduced["op_seconds"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-6)


def test_operations_add_up_under_their_short_names(reduced):
    assert reduced["op_seconds"]["sort"] == pytest.approx(0.000159346, rel=1e-6)
    assert trace_reduce.seconds_matching(reduced["op_seconds"], ["sort"]) \
        == reduced["op_seconds"]["sort"]
    assert trace_reduce.seconds_matching(reduced["op_seconds"], ["_hist_kernel"]) is None
    assert trace_reduce.top(reduced["op_seconds"], 1)[0][0] == "sort"


def test_idle_gaps_are_named_by_the_host_span(reduced):
    gaps = reduced["gap_seconds"]
    assert set(gaps) <= {"bench_iteration", "host_dispatch_fused",
                         "host_flush_assemble", "no_host_span"}
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_short_name():
    assert trace_reduce.short_name(
        "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == "fusion"
    assert trace_reduce.short_name("%_hist_kernel_packed.3.1 = custom-call()") \
        == "_hist_kernel_packed"


def test_union_and_gaps():
    ev = [("a", 0, 10), ("b", 5, 12), ("c", 20, 30)]
    merged = trace_reduce.union(ev)
    assert merged == [[0, 12], [20, 30]]
    assert trace_reduce.gaps(merged, 0, 40) == [(12, 20), (30, 40)]
    assert trace_reduce.clip(ev, 8, 25) == [("a", 8, 10), ("b", 8, 12), ("c", 20, 25)]
