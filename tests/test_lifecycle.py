"""Continuous train→serve lifecycle (`lightgbm_tpu/lifecycle/`).

Chaos-driven end to end on real code paths: continued training
(``init_model`` warm start + crash-safe resume interplay), the traffic
recorder, shadow validation gates (a regressed candidate is rejected
with a structured report and never served), gated atomic promotion
(zero dropped requests across the swap) and the post-promotion
watchdog's automatic rollback under an injected device fault.  Every
test is ``lifecycle``-marked so conftest's SIGALRM per-test timeout
guarantees a hung thread can never stall the tier-1 run.
"""

import glob
import os
import socket
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.lifecycle import (CandidateRejected, LifecycleController,
                                    TrafficRecorder)
from lightgbm_tpu.observability import validate_report
from lightgbm_tpu.reliability import (faults, find_resume_snapshot,
                                      list_snapshots, rel_get, rel_reset)
from lightgbm_tpu.serving import ServerUnavailable, ServingClient

pytestmark = pytest.mark.lifecycle


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm()
    rel_reset()
    yield
    faults.disarm()
    rel_reset()


_P = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 10,
      "verbosity": -1}


def _data(rng, n=500, flip=0.0):
    X = rng.randn(n, 4)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    if flip:
        sel = rng.rand(n) < flip
        y[sel] = 1.0 - y[sel]
    return X, y


def _train(X, y, rounds=5, **extra):
    p = dict(_P, **extra)
    return lgb.train(dict(p), lgb.Dataset(X, label=y, params=dict(p)),
                     rounds, verbose_eval=False)


def _serve(bst, **kw):
    kw.setdefault("port", 0)
    kw.setdefault("max_batch_rows", 64)
    kw.setdefault("min_bucket", 32)
    kw.setdefault("record_rows", 128)
    return bst.serve(**kw)


# -- traffic recorder --------------------------------------------------------

def test_traffic_recorder_ring_semantics(rng):
    rec = TrafficRecorder(8)
    assert len(rec) == 0 and rec.snapshot().size == 0
    rec.record(np.arange(12.0).reshape(6, 2))            # fills 6/8
    rec.record(np.arange(12.0, 20.0).reshape(4, 2))      # wraps: 10 rows in
    assert len(rec) == 8
    snap = rec.snapshot()
    assert snap.shape == (8, 2)
    # oldest-first: rows 2..9 of the 10 recorded survive
    np.testing.assert_array_equal(snap[:, 0], np.arange(4.0, 20.0, 2))
    assert rec.total_rows == 10
    # a request wider than the ring's schema is skipped, not recorded
    rec.record(np.zeros((3, 5)))
    assert len(rec) == 8 and rec.skipped_rows == 3
    # one request larger than the whole ring keeps its newest rows
    rec.record(np.arange(40.0).reshape(20, 2))
    np.testing.assert_array_equal(rec.snapshot()[-1], [38.0, 39.0])
    # disabled recorder is a no-op
    off = TrafficRecorder(0)
    off.record(np.ones((4, 2)))
    assert len(off) == 0 and not off.enabled


# -- continued training (init_model) -----------------------------------------

def test_init_model_continued_training_parity(rng):
    """Warm start: tree count = incumbent + new rounds, and the first
    trees ARE the incumbent's (truncated prediction matches)."""
    X, y = _data(rng)
    inc = _train(X, y, 4)
    X2, y2 = _data(rng)          # fresh data, same distribution
    p = dict(_P)
    cont = lgb.train(dict(p), lgb.Dataset(X2, label=y2, params=dict(p)),
                     3, init_model=inc, verbose_eval=False)
    assert cont.num_trees() == 4 + 3
    np.testing.assert_allclose(
        cont.predict(X[:64], num_iteration=4, raw_score=True),
        inc.predict(X[:64], raw_score=True), rtol=1e-12, atol=1e-12)
    # boosting continued: the new trees change the full prediction
    assert not np.allclose(cont.predict(X[:64], raw_score=True),
                           inc.predict(X[:64], raw_score=True))


def test_init_model_resume_interplay(rng, tmp_path):
    """``init_model`` + ``resume=True``: with no snapshot the incumbent
    warm-starts normally; a NEWER snapshot (which embeds the incumbent's
    trees) wins and the run still reaches the original total."""
    X, y = _data(rng)
    inc = _train(X, y, 4)
    out = str(tmp_path / "refit.txt")
    X2, y2 = _data(rng)

    def refit(rounds, **extra):
        p = dict(_P, output_model=out, snapshot_freq=1, **extra)
        return lgb.train(dict(p), lgb.Dataset(X2, label=y2, params=dict(p)),
                         rounds, init_model=inc, verbose_eval=False)

    # no snapshot on disk: resume=True falls through to the warm start
    full = refit(4, resume=True)
    assert rel_get("resume_runs") == 0
    assert full.num_trees() == 8
    full_text = full.model_to_string()

    # "killed" refit: only 2 of the 4 rounds ran (snapshots at 5 and 6)
    for f in glob.glob(out + ".snapshot_iter_*"):
        os.unlink(f)
    refit(2)
    assert [it for it, _ in list_snapshots(out)] == [5, 6]
    # relaunch: snapshot iter 6 > incumbent's 4 -> resume wins, trains
    # only iterations 7..8, and the result is bit-identical
    resumed = refit(4, resume=True)
    assert rel_get("resume_runs") == 1
    assert resumed.num_trees() == 8
    assert resumed.model_to_string() == full_text


def test_refit_killed_by_fault_resumes_bit_identical(rng, tmp_path):
    """Acceptance: a refit killed mid-run via ``LGBT_FAULTS``-style
    injection (``train.crash``) relaunches with resume and produces a
    bit-identical candidate."""
    X, y = _data(rng)
    inc = _train(X, y, 4, bagging_fraction=0.8, bagging_freq=1)
    out = str(tmp_path / "refit.txt")
    X2, y2 = _data(rng)

    def refit(resume=False):
        p = dict(_P, output_model=out, snapshot_freq=1, resume=resume,
                 bagging_fraction=0.8, bagging_freq=1)
        return lgb.train(dict(p), lgb.Dataset(X2, label=y2, params=dict(p)),
                         4, init_model=inc, verbose_eval=False)

    full_text = refit().model_to_string()
    for f in glob.glob(out + ".snapshot_iter_*"):
        os.unlink(f)

    faults.arm("train.crash:nth=2")
    with pytest.raises(RuntimeError, match="train.crash"):
        refit()
    faults.disarm()
    assert rel_get("fault.train.crash") == 1
    assert list_snapshots(out), "the killed refit left snapshots behind"

    resumed = refit(resume=True)
    assert rel_get("resume_runs") == 1
    assert resumed.model_to_string() == full_text


# -- snapshot rejection accounting (satellite) -------------------------------

def test_snapshot_rejection_reasons_counted(rng, tmp_path):
    """Rejected snapshots are classified into reliability counters
    (fingerprint mismatch vs truncation), not silently skipped."""
    X, y = _data(rng)
    out = str(tmp_path / "m.txt")
    p = dict(_P, output_model=out, snapshot_freq=2)
    lgb.train(dict(p), lgb.Dataset(X, label=y, params=dict(p)), 4,
              verbose_eval=False)
    snaps = list_snapshots(out)
    assert len(snaps) == 2
    # newest snapshot: truncate the model text
    with open(snaps[-1][1], "w") as fh:
        fh.write("tree\nversion=v3\n")          # no 'end of trees'
    with pytest.warns(UserWarning, match="truncated"):
        found = find_resume_snapshot(out, Config.from_params(dict(p)))
    assert found is not None and found[0] == snaps[0][0]
    assert rel_get("snapshots_rejected.truncated") == 1
    # different training config: fingerprint mismatch on the older one
    other = Config.from_params(dict(p, learning_rate=0.5))
    with pytest.warns(UserWarning):
        assert find_resume_snapshot(out, other) is None
    assert rel_get("snapshots_rejected.fingerprint_mismatch") >= 1


# -- registry rollback + health versions (satellite) -------------------------

def test_registry_rollback_and_health_versions(rng):
    X, y = _data(rng)
    inc = _train(X, y, 5)
    cand = _train(X, y, 8)
    server = _serve(inc)
    try:
        with ServingClient(server.host, server.port) as c:
            h = c.health()
            assert h["versions"]["default"] == {"version": 1,
                                                "previous": None}
            want_inc = c.predict(X[:16], raw_score=True)
            server.registry.load("default", booster=cand)
            h = c.health()
            assert h["versions"]["default"] == {"version": 2, "previous": 1}
            # rollback re-swaps the retained incumbent atomically
            restored = server.registry.rollback("default")
            assert restored == 1
            np.testing.assert_allclose(c.predict(X[:16], raw_score=True),
                                       want_inc, rtol=1e-6, atol=1e-6)
            h = c.health()
            assert h["versions"]["default"] == {"version": 1, "previous": 2}
        assert rel_get("serve.rollbacks") == 1
        with pytest.raises(KeyError):
            server.registry.rollback("nope")
    finally:
        server.stop()


# -- client retry-with-backoff (satellite) -----------------------------------

def test_client_retries_then_server_unavailable():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()                                    # nothing listens here
    t0 = time.monotonic()
    with pytest.raises(ServerUnavailable) as ei:
        ServingClient("127.0.0.1", port, timeout=2, retries=2,
                      backoff_s=0.01)
    assert time.monotonic() - t0 < 10
    assert ei.value.attempts == 3
    assert isinstance(ei.value, ConnectionError)     # typed, still generic
    assert rel_get("serve.client_connect_retries") == 3


def test_client_retries_transient_recv_then_recovers(rng):
    """A connection the server drops mid-stream is retried on a fresh
    socket; a shed frame is NOT retried (structured server decision)."""
    X, y = _data(rng)
    server = _serve(_train(X, y, 3))
    try:
        c = ServingClient(server.host, server.port, timeout=5, retries=2,
                          backoff_s=0.01)
        assert c.ping() is True
        # kill the client's socket out from under it: the next call hits
        # a transport error, reconnects and succeeds
        c._sock.close()
        assert c.ping() is True
        assert rel_get("serve.client_call_retries") >= 1
        c.close()
    finally:
        server.stop()


# -- shadow validation gates -------------------------------------------------

def _traffic(server, X, rows=96):
    with ServingClient(server.host, server.port) as c:
        for ofs in range(0, rows, 32):
            c.predict(X[ofs:ofs + 32])


def test_shadow_gate_rejects_regressed_candidate(rng):
    """Acceptance: a corrupted/regressed candidate is rejected by the
    shadow gate with a structured report and is NEVER served."""
    X, y = _data(rng)
    inc = _train(X, y, 5)
    server = _serve(inc)
    try:
        ctl = LifecycleController(server, divergence_max=0.15,
                                  metric="auc", metric_floor=0.75)
        _traffic(server, X)
        assert len(server.recorder) == 96
        # candidate trained on inverted labels: diverges AND regresses
        bad = _train(X, 1.0 - y, 5)
        labels = y[:len(server.recorder)]
        prepared, report = ctl.shadow(bad, labels=labels)
        assert prepared is None and report["passed"] is False
        assert not report["gates"]["divergence"]["passed"]
        assert not report["gates"]["metric"]["passed"]
        assert report["reasons"], "a rejection names its reasons"
        # never served: version unchanged, and run_cycle raises typed
        assert server.registry.versions() == {"default": 1}
        assert rel_get("lifecycle.shadow_rejections") == 1
        rep = server.report()
        assert rep["lifecycle"]["shadow"]["passed"] is False
        assert validate_report(rep) == []
    finally:
        server.stop()


def test_shadow_requires_a_recording(rng):
    X, y = _data(rng)
    server = _serve(_train(X, y, 3))
    try:
        ctl = LifecycleController(server, min_shadow_rows=8)
        prepared, report = ctl.shadow(_train(X, y, 4))
        assert prepared is None and not report["passed"]
        assert "recording too small" in report["reasons"][0]
    finally:
        server.stop()


# -- gated promotion + auto-rollback -----------------------------------------

def test_promotion_zero_dropped_requests(rng):
    """Acceptance: a healthy candidate promotes atomically — every
    in-flight and concurrent prediction is answered across the swap."""
    X, y = _data(rng)
    inc = _train(X, y, 4)
    server = _serve(inc)
    try:
        ctl = LifecycleController(server, divergence_max=0.75)
        _traffic(server, X)
        X2, y2 = _data(rng)
        p = dict(_P)
        train_set = lgb.Dataset(X2, label=y2, params=dict(p))

        stop = threading.Event()
        answered, failures = [], []
        lock = threading.Lock()

        def hammer():
            # retries=0: a single dropped/failed request fails the test —
            # the swap must be invisible to in-flight traffic on its own
            with ServingClient(server.host, server.port, timeout=30,
                               retries=0) as c:
                while not stop.is_set():
                    try:
                        s = c.predict(X[:8], raw_score=True)
                        with lock:
                            answered.append(s.shape)
                    except Exception as e:   # any drop is a test failure
                        with lock:
                            failures.append(repr(e))
                        return

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            result = ctl.run_cycle(train_set, 3, p, watch=False)
        finally:
            time.sleep(0.2)          # swap committed; keep hammering past it
            stop.set()
            for t in threads:
                t.join(timeout=30)
        assert result["version"] == 2
        assert result["shadow"]["passed"] is True
        assert server.registry.versions() == {"default": 2}
        assert failures == []
        assert len(answered) > 0
        # promoted model actually serves (4 incumbent + 3 new trees)
        assert server.registry.get("default").booster.num_trees() == 7
        assert rel_get("lifecycle.promotions") == 1
        rep = server.report()
        assert rep["lifecycle"]["promotions"] == 1
        assert rep["lifecycle"]["versions"]["default"]["previous"] == 1
        assert validate_report(rep) == []
    finally:
        server.stop()


def test_rejected_cycle_raises_typed(rng):
    X, y = _data(rng)
    server = _serve(_train(X, y, 4))
    try:
        ctl = LifecycleController(server, divergence_max=1e-9)
        _traffic(server, X, rows=32)
        p = dict(_P)
        X2, y2 = _data(rng)
        with pytest.raises(CandidateRejected) as ei:
            ctl.run_cycle(lgb.Dataset(X2, label=y2, params=dict(p)), 2, p,
                          watch=False)
        assert ei.value.report["reasons"]
        assert server.registry.versions() == {"default": 1}
    finally:
        server.stop()


# the watchdog drills are not about the shadow's latency gate: its p50 of two
# predictions read 6x the incumbent's on a box that six xdist workers load
# (ceiling 4x), and the promotion under test never happened
_NO_LATENCY_GATE = 1e9


def test_device_fault_after_promotion_triggers_auto_rollback(rng):
    """Acceptance: an injected device fault after promotion breaches the
    watchdog's health gates and rolls back to the retained incumbent
    within the configured deadline, observable in the lifecycle report
    section and the reliability counters."""
    X, y = _data(rng)
    inc = _train(X, y, 4)
    server = _serve(inc)
    try:
        ctl = LifecycleController(server, divergence_max=0.75,
                                  latency_max_ratio=_NO_LATENCY_GATE,
                                  rollback_deadline_s=20.0,
                                  watch_interval_s=0.05,
                                  error_rate_max=0.2)
        _traffic(server, X)
        X2, y2 = _data(rng)
        p = dict(_P)
        result = ctl.run_cycle(lgb.Dataset(X2, label=y2, params=dict(p)),
                               2, p, watch=True)
        assert result["version"] == 2
        t0 = time.monotonic()
        # the promoted model's device path starts failing: requests still
        # answer through the host fallback, and the fallback rate is the
        # breach signal
        faults.arm("serve.predict.fail:count=-1")
        with ServingClient(server.host, server.port, timeout=30) as c:
            deadline = time.monotonic() + 15
            while ctl.watchdog.result is None and time.monotonic() < deadline:
                c.predict(X[:8])
                time.sleep(0.02)
        assert ctl.watchdog.join(timeout=10)
        elapsed = time.monotonic() - t0
        assert ctl.watchdog.result == "rolled_back", ctl.watchdog.section()
        assert "fallback rate" in ctl.watchdog.breach
        assert elapsed < 20.0, "rollback landed within the deadline"
        # the incumbent is serving again
        assert server.registry.get("default").version == 1
        assert server.registry.get("default").booster is inc
        assert rel_get("lifecycle.auto_rollbacks") == 1
        assert rel_get("serve.rollbacks") == 1
        faults.disarm()
        rep = server.report()
        lc = rep["lifecycle"]
        assert lc["auto_rollbacks"] == 1 and lc["rollbacks"] == 1
        assert any(e["event"] == "auto_rollback" for e in lc["events"])
        assert lc["watchdog"]["result"] == "rolled_back"
        assert validate_report(rep) == []
        # and the rolled-back incumbent serves correctly
        with ServingClient(server.host, server.port) as c:
            got = c.predict(X[:16], raw_score=True)
        np.testing.assert_allclose(got, inc.predict(X[:16], raw_score=True),
                                   rtol=1e-6, atol=1e-6)
    finally:
        faults.disarm()
        server.stop()


def test_healthy_promotion_watchdog_clears(rng):
    """No breach inside the (short) deadline: the watchdog records a
    healthy promotion and does not roll back."""
    X, y = _data(rng)
    server = _serve(_train(X, y, 4))
    try:
        ctl = LifecycleController(server, divergence_max=0.75,
                                  latency_max_ratio=_NO_LATENCY_GATE,
                                  rollback_deadline_s=0.3,
                                  watch_interval_s=0.05)
        _traffic(server, X, rows=32)
        X2, y2 = _data(rng)
        p = dict(_P)
        ctl.run_cycle(lgb.Dataset(X2, label=y2, params=dict(p)), 2, p,
                      watch=True)
        with ServingClient(server.host, server.port) as c:
            c.predict(X[:8])
        assert ctl.watchdog.join(timeout=10)
        assert ctl.watchdog.result == "healthy"
        assert server.registry.get("default").version == 2
        assert rel_get("lifecycle.promotions_healthy") == 1
        assert rel_get("lifecycle.auto_rollbacks") == 0
    finally:
        server.stop()


def test_back_to_back_promotions_cancel_stale_watchdog(rng):
    """Regression: two rapid ``run_cycle`` calls must not leave the
    FIRST promotion's watchdog running against its stale baseline —
    errors injected between the promotions would otherwise count
    against promotion #2's health gates and roll it back spuriously.
    ``promote`` now cancels + joins the stale watchdog and the new one
    re-baselines off the CURRENT counters."""
    X, y = _data(rng)
    server = _serve(_train(X, y, 4))
    try:
        # a watch interval LONGER than this test may run (the lifecycle
        # marker's SIGALRM ends it at 120 s): the stale watchdog sits
        # armed for the whole drill unless promote() explicitly cancels
        # it, and it can never wake mid-drill.  At the old 10 s interval
        # a loaded machine (6 xdist workers, cold compile cache) took
        # longer than that between the two promotions, the stale
        # watchdog woke on the injected fallbacks and rolled back — the
        # test then failed on the clock, not on the code
        ctl = LifecycleController(server, divergence_max=0.75,
                                  latency_max_ratio=_NO_LATENCY_GATE,
                                  rollback_deadline_s=1200.0,
                                  watch_interval_s=600.0,
                                  error_rate_max=0.05)
        _traffic(server, X)
        X2, y2 = _data(rng)
        p = dict(_P)
        ctl.run_cycle(lgb.Dataset(X2, label=y2, params=dict(p)), 2, p,
                      watch=True)
        w1 = ctl.watchdog
        assert w1 is not None and w1.result is None

        # fallbacks between the promotions — exactly the counters whose
        # deltas a stale baseline would blame on promotion #2
        faults.arm("serve.predict.fail:count=2")
        with ServingClient(server.host, server.port) as c:
            for _ in range(4):
                c.predict(X[:8])
        faults.disarm()
        assert server.stats.fallback_batches >= 2

        X3, y3 = _data(rng)
        ctl.run_cycle(lgb.Dataset(X3, label=y3, params=dict(p)), 2, p,
                      watch=True)
        w2 = ctl.watchdog
        assert w2 is not w1
        # the stale watchdog is truly gone, not lingering mid-interval
        assert w1.join(timeout=10) and w1.result == "cancelled"
        # the new one re-baselined AFTER the injected fallbacks
        assert w2._base["fallback_batches"] == server.stats.fallback_batches
        assert w2.result is None
        # nothing rolled back: version 3 serves
        assert server.registry.get("default").version == 3
        assert rel_get("lifecycle.auto_rollbacks") == 0
        ctl.stop()
        assert w2.result == "cancelled"
    finally:
        faults.disarm()
        server.stop()
