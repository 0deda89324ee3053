"""Tracing & service metrics: span recorder semantics, Chrome JSON
export, trace_id propagation through a live server, exact histogram
percentiles, Prometheus export, periodic stats snapshots, tracing-off
no-op invariants, and the bench_serving.py contract."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability import (BENCH_SERVING_SCHEMA,
                                        LatencyHistogram, TraceRecorder,
                                        new_trace_id, validate_report)
from lightgbm_tpu.observability.metrics_export import prometheus_text
from lightgbm_tpu.serving import ServerOverloaded, ServingClient


def _train(rng, trees=8, n=2000, f=6, **params):
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "min_data_in_leaf": 10}
    p.update(params)
    return lgb.train(p, lgb.Dataset(X, label=y), trees)


# -- recorder semantics ------------------------------------------------------

def test_span_nesting_and_ring_wrap():
    r = TraceRecorder(True, capacity=4)
    with r.span("outer", args={"k": 1}):
        with r.span("mid"):
            with r.span("inner"):
                pass
    ev = [e for e in r.export()["traceEvents"] if e["ph"] in "BE"]
    # B/E pairs, properly nested: outer opens first, closes last
    assert [(e["ph"], e["name"]) for e in ev] == [
        ("B", "outer"), ("B", "mid"), ("B", "inner"),
        ("E", "inner"), ("E", "mid"), ("E", "outer")]
    # ring wrap: capacity 4, 3 already recorded, 10 more overwrite oldest
    for i in range(10):
        with r.span(f"s{i}"):
            pass
    assert len(r) == 4
    assert r.dropped == 9
    names = {s[0] for s in r.spans()}
    assert names == {"s6", "s7", "s8", "s9"}   # newest 4 survive


def test_chrome_trace_json_loads_and_pairs_be():
    r = TraceRecorder(True)
    for i in range(5):
        with r.span(f"work{i % 2}", cat="test", trace_id=f"t{i}"):
            pass
    r.instant("marker", args={"note": "x"})
    exported = r.export()
    # round-trips as plain JSON (the Perfetto/chrome://tracing contract)
    trace = json.loads(json.dumps(exported))
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    per_key = {}
    for e in evs:
        assert e["ph"] == "M" or isinstance(e["ts"], (int, float))
        if e["ph"] in "BE":
            key = (e["tid"], e["name"])
            per_key.setdefault(key, [0, 0])
            per_key[key][0 if e["ph"] == "B" else 1] += 1
    assert per_key and all(b == e for b, e in per_key.values())
    assert any(e["ph"] == "i" and e["name"] == "marker" for e in evs)
    # every B span carries its trace_id in args
    b_ids = {e["args"]["trace_id"] for e in evs if e["ph"] == "B"}
    assert b_ids == {f"t{i}" for i in range(5)}


def test_disabled_recorder_records_nothing():
    r = TraceRecorder(False)
    with r.span("x"):
        pass
    r.add_complete("y", 0.0, 1.0)
    r.instant("z")
    assert len(r) == 0 and r.dropped == 0
    assert r.export()["traceEvents"] == []


def test_bind_propagates_trace_id_across_helpers():
    r = TraceRecorder(True)
    with r.bind("req-1"):
        with r.span("stage"):
            pass
    with r.span("unbound"):
        pass
    spans = {s[0]: s[6] for s in r.spans()}
    assert spans["stage"] == "req-1"
    assert spans["unbound"] is None


# -- histogram / Prometheus --------------------------------------------------

def test_histogram_percentiles_exact_vs_numpy(rng):
    h = LatencyHistogram()
    xs = rng.lognormal(mean=0.5, sigma=1.2, size=5000)   # < window
    for x in xs:
        h.record(x)
    got = h.percentiles((50, 95, 99))
    want = np.percentile(xs, [50, 95, 99])
    np.testing.assert_allclose(
        [got["p50"], got["p95"], got["p99"]], want, rtol=0, atol=0)
    snap = h.snapshot()
    assert snap["count"] == len(xs)
    np.testing.assert_allclose(snap["mean"], xs.mean())
    np.testing.assert_allclose(snap["max"], xs.max())


def test_histogram_prometheus_buckets_cumulative(rng):
    h = LatencyHistogram(bounds_ms=[1.0, 10.0, 100.0])
    for v in (0.5, 5.0, 50.0, 500.0):
        h.record(v)
    rows = h.cumulative_buckets()
    assert rows == [(1.0, 1), (10.0, 2), (100.0, 3), (float("inf"), 4)]
    lines = h.prometheus_lines("lat_seconds")
    assert lines[0] == "# TYPE lat_seconds histogram"
    assert 'lat_seconds_bucket{le="+Inf"} 4' in lines
    assert any(line.startswith("lat_seconds_count") for line in lines)
    text = prometheus_text(counters={"reqs_total": 4},
                           histograms={"lat_seconds": h})
    assert "# TYPE lgbt_reqs_total counter" in text
    assert text.endswith("\n")


# -- live server: trace_id propagation, metrics, snapshots -------------------

@pytest.mark.serving
def test_trace_id_propagation_through_live_server(rng, tmp_path):
    """Acceptance: one trace_id links the request span, its micro-batch
    span and the batch's stage spans, in a trace that loads as Chrome
    trace-event JSON; shed responses echo the id."""
    bst = _train(rng)
    trace_path = tmp_path / "serve_trace.json"
    server = bst.serve(port=0, min_bucket=32, max_batch_rows=64,
                       trace=True, trace_out=str(trace_path))
    tid = new_trace_id()
    try:
        with ServingClient(server.host, server.port, timeout=60) as c:
            got = np.asarray(c.predict(rng.randn(5, 6), trace_id=tid))
            assert got.shape == (5,)
            # the response frame echoes the id (raw call to see the frame)
            resp = c._call({"op": "predict", "data": rng.randn(3, 6),
                            "raw_score": False, "trace_id": "echo-42"})
            assert resp["trace_id"] == "echo-42"
            # shed echo: saturate admission, next predict must shed WITH
            # the id attached to the typed exception
            while server.admission.try_acquire():
                pass
            with pytest.raises(ServerOverloaded) as ei:
                c.predict(rng.randn(2, 6), trace_id="shed-1")
            assert ei.value.trace_id == "shed-1"
    finally:
        server.stop()
    trace = json.loads(trace_path.read_text())
    linked = {"serve.request": 0, "serve.batch": 0,
              "serve_bin": 0, "serve_traverse": 0, "serve_queue": 0}
    for e in trace["traceEvents"]:
        if e.get("ph") != "B":
            continue
        t = e.get("args", {}).get("trace_id")
        if t == tid or (isinstance(t, list) and tid in t):
            if e["name"] in linked:
                linked[e["name"]] += 1
    assert all(v >= 1 for v in linked.values()), linked
    # stats carry the latency histogram section
    rep = server.report()
    assert validate_report(rep) == []
    assert rep["serving"]["latency_ms"]["count"] >= 2


@pytest.mark.serving
def test_metrics_op_prometheus_snapshot(rng):
    bst = _train(rng)
    server = bst.serve(port=0, min_bucket=32, max_batch_rows=64)
    try:
        with ServingClient(server.host, server.port, timeout=60) as c:
            c.predict(rng.randn(4, 6))
            text = c.metrics()
    finally:
        server.stop()
    assert "# TYPE lgbt_serving_requests_total counter" in text
    assert "lgbt_serving_requests_total 1" in text
    assert 'lgbt_serving_request_latency_seconds_bucket{le="+Inf"} 1' in text
    assert "lgbt_serving_batch_occupancy" in text
    # reliability counters ride along (process-wide table)
    assert "lgbt_serving_inflight" in text


@pytest.mark.serving
def test_stats_out_periodic_snapshots(rng, tmp_path):
    """--stats-out: periodic atomic schema-validated snapshots appear
    without any socket op, and a final one lands at stop."""
    bst = _train(rng)
    out = tmp_path / "stats.json"
    server = bst.serve(port=0, min_bucket=32, max_batch_rows=64,
                       stats_out=str(out), stats_interval_s=0.2)
    try:
        with ServingClient(server.host, server.port, timeout=60) as c:
            c.predict(rng.randn(3, 6))
        deadline = time.monotonic() + 30
        while not out.exists() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert out.exists(), "no snapshot within 30s at 0.2s interval"
        snap = json.loads(out.read_text())
        assert validate_report(snap) == []
    finally:
        server.stop()
    final = json.loads(out.read_text())
    assert validate_report(final) == []
    assert final["serving"]["requests"] >= 1


# -- tracing-off invariants --------------------------------------------------

@pytest.mark.serving
def test_tracing_adds_no_recompiles_to_warm_buckets(rng):
    """With buckets warm, enabling tracing must not grow the jit caches:
    spans are host-side only, so the compiled programs are untouched."""
    bst = _train(rng)
    server = bst.serve(port=0, min_bucket=32, max_batch_rows=64)
    try:
        with ServingClient(server.host, server.port, timeout=60) as c:
            c.predict(rng.randn(5, 6))            # steady-state, untraced
            before = server.registry.jit_entries()
            tracer = TraceRecorder(True)
            server.tracer = tracer
            server.stats.attach_tracer(tracer)
            for n in (3, 9, 17):
                c.predict(rng.randn(n, 6), trace_id=new_trace_id())
            after = server.registry.jit_entries()
    finally:
        server.stop()
    if before is not None:
        assert after == before, (before, after)
    assert len(tracer) > 0                         # spans did record


def test_training_trace_off_is_noop_and_model_identical(rng):
    """telemetry=False: a span is kept when a recorder listens and only
    then (no recorder attached records nothing anywhere; an attached one
    records the training spans with their arguments), and training with
    trace_out produces the exact same model text as without (tracing
    cannot perturb training)."""
    X = rng.randn(1500, 5)
    y = (X[:, 0] > 0).astype(float)
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
         "seed": 7, "min_data_in_leaf": 10}
    plain = lgb.train(dict(p), lgb.Dataset(X.copy(), label=y.copy()), 6)
    # nobody listens: no recorder, no phase table, no counters
    tel = plain.gbdt.telemetry
    assert tel.tracer is None and not tel.enabled
    assert tel._phases == {} and tel._counters == {}
    # a telemetry-off booster with a recorder attached records its spans
    bst2 = lgb.Booster(dict(p), lgb.Dataset(X.copy(), label=y.copy()))
    rec = TraceRecorder(True)
    bst2.gbdt.telemetry.tracer = rec
    for _ in range(3):
        bst2.update()
    bst2.gbdt._flush_pending()
    by_name = {}
    for s in rec.spans():
        by_name.setdefault(s[0], []).append(s[7])
    assert [a["it"] for a in by_name["iteration"]] == [0, 1, 2]
    assert [a["queued"] for a in by_name["dispatch"]] == [0, 1, 2]
    assert [a["tree"] for a in by_name["assemble_tree"]] == [0, 1, 2]
    assert len(by_name["d2h_wait"]) == 3 and by_name["flush"]
    assert bst2.gbdt.telemetry._phases == {}    # the table needs telemetry
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        trace_path = os.path.join(td, "train_trace.json")
        traced = lgb.train(dict(p, trace_out=trace_path),
                           lgb.Dataset(X.copy(), label=y.copy()), 6)
        assert traced.model_to_string() == plain.model_to_string()
        trace = json.loads(open(trace_path).read())
    names = {e["name"] for e in trace["traceEvents"] if e.get("ph") == "B"}
    # training spans present; the tree span's name depends on the dispatch
    # path taken
    assert "iteration" in names
    assert names & {"tree_train", "tree_dispatch", "dispatch", "gradients",
                    "flush"}


# -- podtrace: per-rank export + cross-host merge ----------------------------

class _FakeNet:
    """Just enough DistributedNet surface for podtrace unit tests."""

    def __init__(self, rank, num_machines=2, clock_offset_s=0.0):
        self.rank = rank
        self.num_machines = num_machines
        self._off = clock_offset_s

    def allgather(self, payload):
        # rank 0's stamp on ITS clock: our clock minus the offset, posted
        # "now" (inside the caller's send/recv window, so midpoint error
        # is bounded by the call's rtt)
        return [("clk", 0, time.perf_counter() - self._off), payload]


def test_estimate_clock_offset_recovers_known_skew():
    from lightgbm_tpu.observability import podtrace
    off = podtrace.estimate_clock_offset(
        _FakeNet(rank=1, clock_offset_s=0.25), rounds=4)
    assert abs(off["offset_s"] - 0.25) < 0.01
    assert off["method"] == "kv-ping-midpoint"
    # rank 0 IS the reference clock, whatever its rounds measured
    off0 = podtrace.estimate_clock_offset(
        _FakeNet(rank=0, clock_offset_s=0.25), rounds=4)
    assert off0["offset_s"] == 0.0


def test_podtrace_merge_aligns_and_nests(tmp_path):
    from lightgbm_tpu.observability import podtrace

    clk = {"offset_s": 0.0, "rtt_s": 1e-4, "rounds": 8,
           "method": "kv-ping-midpoint"}
    r0 = TraceRecorder(True)
    with r0.span("iteration"):
        with r0.span("tree_dispatch"):
            pass
    time.sleep(0.02)
    r1 = TraceRecorder(True)      # later epoch, same host clock
    with r1.span("iteration"):
        pass
    base = str(tmp_path / "trace.json")
    p0 = podtrace.export_rank_trace(r0, base, net=_FakeNet(0),
                                    clock=dict(clk))
    p1 = podtrace.export_rank_trace(r1, base, net=_FakeNet(1),
                                    clock=dict(clk))
    assert p0.endswith(".rank0") and p1.endswith(".rank1")
    # single host: the path passes through unchanged
    assert podtrace.rank_trace_path(base, 0, 1) == base
    with open(p0) as fh:
        meta0 = json.load(fh)["otherData"]
    assert meta0["rank"] == 0 and meta0["process_count"] == 2
    assert "aligned_epoch_us" in meta0

    merged_path = str(tmp_path / "pod.json")
    merged = podtrace.merge_pod_trace([p0, p1], out=merged_path)
    with open(merged_path) as fh:            # valid Chrome trace JSON
        reloaded = json.load(fh)
    assert reloaded["otherData"]["pod_merge"] is True
    ev = merged["traceEvents"]
    assert {e["pid"] for e in ev} == {0, 1}  # pids rewritten to ranks
    pnames = {e["pid"]: e["args"]["name"] for e in ev
              if e.get("ph") == "M" and e["name"] == "process_name"}
    assert pnames[0].startswith("rank 0")
    assert pnames[1].startswith("rank 1")
    # same-host clocks (offset 0): rank 1's later-recorded span must land
    # LATER on the merged timeline than rank 0's earlier spans
    t0_end = max(e["ts"] for e in ev
                 if e["pid"] == 0 and e.get("ph") == "E")
    t1_beg = min(e["ts"] for e in ev
                 if e["pid"] == 1 and e.get("ph") == "B")
    assert t1_beg > t0_end
    # B/E well-nesting survives the merge on every (pid, tid) stream
    stacks = {}
    for e in ev:
        if e.get("ph") == "B":
            stacks.setdefault((e["pid"], e["tid"]), []).append(e["name"])
        elif e.get("ph") == "E":
            assert stacks[(e["pid"], e["tid"])].pop() == e["name"]
    assert not any(stacks.values())
    ts = [e["ts"] for e in ev if e.get("ph") in "BEi"]
    assert ts == sorted(ts)                  # merged timeline is monotone


def test_podtrace_offset_compensation(tmp_path):
    """A rank whose clock runs 0.5 s AHEAD exports aligned_epoch 0.5 s
    earlier; the merge therefore cancels the skew instead of showing the
    rank half a second late."""
    from lightgbm_tpu.observability import podtrace

    r0 = TraceRecorder(True)
    with r0.span("iteration"):
        pass
    r1 = TraceRecorder(True)
    with r1.span("iteration"):
        pass
    base = str(tmp_path / "t.json")
    clk0 = {"offset_s": 0.0, "rtt_s": 0.0, "rounds": 1, "method": "x"}
    p0 = podtrace.export_rank_trace(r0, base, net=_FakeNet(0), clock=clk0)
    skewed = {"offset_s": 0.5, "rtt_s": 0.0, "rounds": 1, "method": "x"}
    p1 = podtrace.export_rank_trace(r1, base, net=_FakeNet(1), clock=skewed)
    with open(p0) as fh:
        e0 = json.load(fh)["otherData"]["aligned_epoch_us"]
    with open(p1) as fh:
        e1 = json.load(fh)["otherData"]["aligned_epoch_us"]
    # r1 was created AFTER r0 on the same real clock, but claiming its
    # clock is 0.5 s ahead pulls its aligned epoch ~0.5 s BEFORE r0's
    assert e0 - e1 == pytest.approx(0.5e6, abs=0.1e6)
    merged = podtrace.merge_pod_trace([p0, p1])
    ranks = {m["rank"]: m for m in merged["otherData"]["ranks"]}
    assert ranks[1]["clock_offset_us"] == pytest.approx(0.5e6)


def test_podtrace_cli_merges(tmp_path, capsys):
    from lightgbm_tpu.observability import podtrace

    r = TraceRecorder(True)
    with r.span("iteration"):
        pass
    p0 = str(tmp_path / "a.json")
    r.save(p0)
    out = str(tmp_path / "merged.json")
    assert podtrace.main([out, p0, p0]) == 0
    assert "merged 2 rank trace(s)" in capsys.readouterr().out
    with open(out) as fh:
        merged = json.load(fh)
    # metadata-less inputs merge at offset 0 with list-index ranks
    assert {e["pid"] for e in merged["traceEvents"]} <= {0, 1}
    assert podtrace.main([out]) == 2         # usage error


# -- bench_serving.py --------------------------------------------------------

@pytest.mark.serving(timeout=300)
def test_bench_serving_smoke(tmp_path):
    """Tiny closed+open-loop run: exits 0, prints one JSON line, writes a
    BENCH_SERVING file that validates against the checked-in schema."""
    out = tmp_path / "BENCH_SERVING_smoke.json"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo_root, "bench_serving.py"),
         "--out", str(out), "--train-rows", "2000", "--trees", "5",
         "--requests", "24", "--clients", "2", "--qps", "30",
         "--open-seconds", "1", "--num-features", "6"],
        capture_output=True, text=True, env=env, timeout=280)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "closed_p99_ms" in line and "open_qps" in line
    report = json.loads(out.read_text())
    assert validate_report(report, BENCH_SERVING_SCHEMA) == []
    # schema v2: provenance pins the cost ledger the run was gated under
    assert report["schema_version"] == 2
    sha = report["provenance"]["cost_ledger_sha256"]
    assert isinstance(sha, str) and len(sha) == 64
    assert report["closed_loop"]["ok"] > 0
    assert report["open_loop"]["requests"] >= 30 * 1
    assert report["server"]["batches"] > 0
