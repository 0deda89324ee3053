#!/usr/bin/env python3
"""chip_smoke.py — lgb.train -> save -> serve on one TPU chip, checked.

The quickest proof that the system still starts on the chip.  One process,
normal entry points only (``lgb.Dataset``, ``lgb.train``,
``Booster.predict/save_model``, ``Booster.serve`` + ``ServingClient``), at
the Higgs-shaped benchmark width: 1,000,000 x 28, max_bin=255,
num_leaves=255, every other parameter at its default — so the ``auto``
knobs decide what runs, as they would for a user.

Default run (one chip).  Each phase prints one JSON line as it finishes:

  device  jax.devices(); anything but a TPU ends the run here, non-zero
  train   >= 8 fused+pipelined iterations (no validation set), held-out
          AUC; then 3 iterations with a validation set and early stopping
          (the synchronous path).  Asserts the learner and what ``auto``
          promised on a TPU, with no kernel in interpret mode
  parity  the same data and seed on the XLA path (Pallas scan off), and
          at 65,536 rows against the masked f32 learner
  serve   saved model -> in-process server on port 0 -> mixed-size
          requests through ServingClient == Booster.predict; zero
          host-fallback batches, compile-cache misses == warmed buckets

``--chips 4`` runs ONLY the multi-chip path and what it is compared with:
tree_learner=data on a 4-device mesh in this process against a serial
one-chip run of the same seed.

The last line of stdout is always one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Exit code 0 only when every phase passed on a TPU.  No phase is skipped on
an error (a failed phase fails the run; the next still runs) — except that
without a TPU nothing runs at all: a CPU number must never stand in for a
chip's.  ``--rehearse`` (with a small ``--rows``) walks the phases on
whatever JAX finds, to debug this script off-chip; it still ends
``"ok": false`` there.  JAX_PLATFORMS is neither read nor set here.

Seconds printed by the phases are ONE cold run each (compile included where
the label says so) — a smoke reading, not a benchmark.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import tempfile
import time
import traceback
import warnings

import numpy as np

FEATURES = 28            # Higgs width
HOLDOUT = 100_000        # held-out rows of the same generator
ITERS = 10               # fused/pipelined iterations (>= 8)
ITERS_VALID = 3          # synchronous-path iterations (validation set)
ITERS_MESH = 3           # --chips 4: iterations per side
SMALL_ROWS = 65_536      # masked-learner reference size
ITERS_SMALL = 4
PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
          "verbosity": -1}

# -- tolerances and bands, fixed before the first chip run -------------------
# Held-out AUC after ITERS iterations at learning_rate 0.1 on this
# generator (Bayes-optimal ~0.96).  A broken kernel lands near 0.5-0.8.
AUC_BAND_FULL = 0.90     # 1,000,000 training rows
AUC_BAND_SMALL = 0.85    # 65,536 rows / rehearsal sizes, fewer iterations
# Two learners that build the SAME trees differ by f32 rounding (~1e-7 in
# probability).  The Pallas scan, the bf16x3 histogram and the f32 one-hot
# histogram differ by summation-order ulps in split gains, so a near-tie
# can flip a split and move a few rows' predictions by much more than an
# ulp.  Agreement is therefore held on the mean |dp| and on AUC; the max
# |dp| is printed for the record.  (First chip run, PR 21: mean 2e-7 to
# 3e-7, max 1.6e-5 with no flip and 0.03 with one; a CPU rehearsal with
# flips reached mean 1e-5.  The limits were 2e-3 for that run and were
# tightened to these afterwards.)
TOL_MEAN_ABS = 1e-4
TOL_AUC = 5e-4
# serving answers f32 device sums over a float32 wire; Booster.predict on
# a few rows is the f64 host traversal
TOL_SERVE = 1e-5


def _require(cond, msg):
    """A check that survives ``python -O`` (``assert`` does not)."""
    if not cond:
        raise AssertionError(msg)


def _auc(y, p):
    """Rank-sum AUC in numpy (ties get their average rank), independent of
    the repo's metric code."""
    _, inverse, counts = np.unique(p, return_inverse=True,
                                   return_counts=True)
    ranks = (np.cumsum(counts) - (counts - 1) / 2.0)[inverse]
    pos = y > 0.5
    n1, n0 = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


def _make_data(seed, rows):
    """bench.py's Higgs-shaped generator, made in bulk from ``seed``."""
    rng = np.random.RandomState(seed)
    n = rows + HOLDOUT
    X = rng.randn(n, FEATURES)
    logit = (X[:, 0] * 1.5 + X[:, 1] * X[:, 2] * 0.5 + np.sin(X[:, 3])
             + 0.5 * rng.randn(n))
    y = (logit > 0).astype(np.float64)
    return X[:rows], y[:rows], X[rows:], y[rows:]


def _agreement(y, p_a, p_b):
    d = np.abs(p_a - p_b)
    return {"mean_abs_dp": float(d.mean()), "max_abs_dp": float(d.max()),
            "auc_a": _auc(y, p_a), "auc_b": _auc(y, p_b)}


def _require_agreement(tag, agree):
    _require(np.isfinite(agree["mean_abs_dp"]), f"{tag}: non-finite diff")
    _require(agree["mean_abs_dp"] <= TOL_MEAN_ABS,
             f"{tag}: mean |dp| {agree['mean_abs_dp']:.3g} > {TOL_MEAN_ABS}")
    _require(abs(agree["auc_a"] - agree["auc_b"]) <= TOL_AUC,
             f"{tag}: AUC {agree['auc_a']:.5f} vs {agree['auc_b']:.5f} "
             f"differ by more than {TOL_AUC}")


class _FirstIterClock:
    """after-iteration callback: waits for the FIRST iteration's device
    work (compile + one tree) and stamps it; later iterations stay
    asynchronous, so the pipelined path is what runs."""

    def __init__(self):
        self.first_done = None

    def __call__(self, env):
        if self.first_done is None:
            import jax
            jax.block_until_ready(env.model.gbdt.train_score.score)
            self.first_done = time.perf_counter()


def _timed_train(lgb, params, ds, iters, **kw):
    """``lgb.train`` with the first iteration stamped.  Returns (booster,
    seconds to the end of iteration 1, steady seconds per later iteration)
    — both read after ``block_until_ready``."""
    import jax
    clock = _FirstIterClock()
    t0 = time.perf_counter()
    bst = lgb.train(dict(params), ds, iters, callbacks=[clock],
                    verbose_eval=False, **kw)
    jax.block_until_ready(bst.gbdt.train_score.score)
    t1 = time.perf_counter()
    first = clock.first_done - t0
    done = bst.current_iteration
    steady = (t1 - clock.first_done) / max(done - 1, 1)
    return bst, first, steady


def _learner_flags(learner):
    return {k: getattr(learner, "_" + k, None)
            for k in ("use_pallas", "use_scan", "donate",
                      "scan_interpret")}


class Smoke:
    def __init__(self, args):
        self.args = args
        self.failed = []
        self.device = {"platform": None, "kind": None, "count": 0}
        self.on_tpu = False
        # persistent compilation cache traffic, as JAX itself reports it
        self.cache = {"hits": 0, "misses": 0}

    def _on_jax_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    @contextlib.contextmanager
    def phase(self, name):
        out = {"phase": name, "ok": False}
        t0 = time.perf_counter()
        before = dict(self.cache)
        try:
            yield out
            out["ok"] = True
        except Exception as e:  # boundary: a failed phase fails the run,
            out["error"] = f"{type(e).__name__}: {e}"   # the next still runs
            traceback.print_exc(file=sys.stderr)
        out["phase_seconds"] = round(time.perf_counter() - t0, 3)
        out["persistent_cache"] = {k: self.cache[k] - before[k]
                                   for k in self.cache}
        if not out["ok"]:
            self.failed.append(name)
        print(json.dumps(out), flush=True)

    def finish(self):
        ok = not self.failed        # no TPU = a failed device phase
        print(json.dumps({"ok": ok, "device": self.device,
                          **({} if ok else {"failed": self.failed})}),
              flush=True)
        return 0 if ok else 1

    # -- phase 1 -------------------------------------------------------------

    def run_device(self):
        with self.phase("device") as out:
            import jax
            jax.monitoring.register_event_listener(self._on_jax_event)
            devs = jax.devices()
            self.device = {"platform": devs[0].platform,
                           "kind": devs[0].device_kind, "count": len(devs)}
            out.update(self.device, jax=jax.__version__,
                       need_chips=self.args.chips)
            self.on_tpu = devs[0].platform == "tpu"
            _require(self.on_tpu,
                     f"JAX found no TPU (platform {devs[0].platform!r})")
            _require(len(devs) >= self.args.chips,
                     f"--chips {self.args.chips} needs that many TPU "
                     f"devices, JAX sees {len(devs)} (never shrunk)")

    # -- phase 2 -------------------------------------------------------------

    def run_train(self, lgb):
        a = self.args
        with self.phase("train") as out:
            import jax
            from lightgbm_tpu import native
            t0 = time.perf_counter()
            self.X, self.y, self.Xh, self.yh = _make_data(a.seed, a.rows)
            self.ds = lgb.Dataset(self.X, label=self.y,
                                  params=dict(PARAMS)).construct()
            out["rows"], out["features"] = self.X.shape
            out["dataset_seconds"] = round(time.perf_counter() - t0, 3)

            bst, first, steady = _timed_train(lgb, PARAMS, self.ds, ITERS)
            self.bst = bst
            g = bst.gbdt
            learner = g.learner
            out["learner"] = type(learner).__name__
            out["flags"] = _learner_flags(learner)
            out["fused"], out["pipelined"] = g._can_fuse(), g._can_pipeline()
            out["iterations"] = bst.current_iteration
            out["cold_first_iteration_seconds"] = round(first, 3)
            out["steady_seconds_per_iteration"] = round(steady, 4)
            leaves = [int(t.num_leaves) for t in g.models]
            out["leaves_min"] = min(leaves)
            t0 = time.perf_counter()
            self.p_hold = bst.predict(self.Xh)
            out["predict_holdout_seconds_cold"] = round(
                time.perf_counter() - t0, 3)
            out["auc_holdout"] = _auc(self.yh, self.p_hold)

            # the synchronous iteration path: validation sets + early
            # stopping.  The second set comes from a text file, so the
            # file parser (native parse.cpp, or its numpy fallback where
            # no compiler built it) runs once too
            with tempfile.TemporaryDirectory() as tmp:
                path = os.path.join(tmp, "holdout_head.tsv")
                head = min(2000, len(self.yh))
                np.savetxt(path, np.column_stack(
                    [self.yh[:head], self.Xh[:head]]), delimiter="\t",
                    fmt="%.17g")
                dv = self.ds.create_valid(self.Xh, label=self.yh)
                dv_file = lgb.Dataset(path, reference=self.ds)
                evals = {}
                bst2, first2, steady2 = _timed_train(
                    lgb, dict(PARAMS, metric="auc"), self.ds, ITERS_VALID,
                    valid_sets=[dv, dv_file], valid_names=["hold", "file"],
                    early_stopping_rounds=2, evals_result=evals)
            out["parser"] = "native" if native._lib is not None else "numpy"
            g2 = bst2.gbdt
            out["valid"] = {
                "learner": type(g2.learner).__name__,
                "pipelined": g2._can_pipeline(),
                "iterations": bst2.current_iteration,
                "cold_first_iteration_seconds": round(first2, 3),
                "steady_seconds_per_iteration": round(steady2, 4),
                "auc_hold": evals["hold"]["auc"],
                "auc_file": evals["file"]["auc"]}
            p2 = bst2.predict(self.Xh)
            auc2 = _auc(self.yh, p2)
            auc2_head = _auc(self.yh[:head], p2[:head])

            stats = jax.devices()[0].memory_stats() or {}
            out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
            out["bytes_limit"] = stats.get("bytes_limit")
            prov = bst.get_telemetry()["provenance"]
            out["provenance"] = {k: prov[k] for k in (
                "platform", "device_kind", "num_devices", "emulated",
                "jax_version")}

            # -- checks, after everything above is on the line
            want = self.on_tpu      # what `auto` promises on this platform
            _require(out["learner"] == "WaveTPUTreeLearner", out["learner"])
            for k in ("use_pallas", "use_scan", "donate"):
                _require(out["flags"][k] is want,
                         f"_{k} is {out['flags'][k]}, auto promises {want}")
            _require(out["flags"]["scan_interpret"] is False,
                     "_scan_interpret is on")
            _require(out["fused"] and out["pipelined"],
                     "the no-validation run left the fused pipelined path")
            _require(out["iterations"] == ITERS, "stopped early")
            full = a.rows >= 1_000_000
            _require(min(leaves) > (200 if full else 2),
                     f"a tree has only {min(leaves)} leaves")
            band = AUC_BAND_FULL if full else AUC_BAND_SMALL
            _require(np.isfinite(self.p_hold).all(), "non-finite prediction")
            _require(out["auc_holdout"] > band,
                     f"held-out AUC {out['auc_holdout']:.4f} <= {band}")
            _require(not out["valid"]["pipelined"],
                     "the validation run did not take the synchronous path")
            _require(out["valid"]["iterations"] == ITERS_VALID,
                     "validation run stopped early")
            _require(abs(evals["hold"]["auc"][-1] - auc2) < 1e-4,
                     f"repo AUC {evals['hold']['auc'][-1]} vs numpy {auc2}")
            _require(abs(evals["file"]["auc"][-1] - auc2_head) < 1e-4,
                     f"file-loaded valid AUC {evals['file']['auc'][-1]} vs "
                     f"in-memory {auc2_head}: the parser read other data")
            _require(prov["platform"] == self.device["platform"]
                     and prov["emulated"] is (not self.on_tpu),
                     f"provenance hides the device: {out['provenance']}")
            del bst2, dv, dv_file
            gc.collect()

    # -- phase 3 -------------------------------------------------------------

    def run_parity(self, lgb):
        with self.phase("parity") as out:
            _require(hasattr(self, "bst"), "train phase left no model")
            # (a) same data, same seed, the XLA scan instead of the kernel
            xla = dict(PARAMS, tpu_wave_pallas_scan="off")
            b_xla, first, steady = _timed_train(lgb, xla, self.ds, ITERS)
            out["xla_path"] = {
                "flags": _learner_flags(b_xla.gbdt.learner),
                "cold_first_iteration_seconds": round(first, 3),
                "steady_seconds_per_iteration": round(steady, 4),
                **_agreement(self.yh, self.p_hold, b_xla.predict(self.Xh))}
            del b_xla
            gc.collect()
            # (b) the plain reference: the masked f32 learner, small size
            n = min(SMALL_ROWS, len(self.y))
            ds_s = lgb.Dataset(self.X[:n], label=self.y[:n],
                               params=dict(PARAMS))
            b_wave = lgb.train(dict(PARAMS), ds_s, ITERS_SMALL,
                               verbose_eval=False)
            b_mask = lgb.train(dict(PARAMS, tpu_learner="masked"), ds_s,
                               ITERS_SMALL, verbose_eval=False)
            out["masked_reference"] = {
                "rows": n,
                "learners": [type(b_wave.gbdt.learner).__name__,
                             type(b_mask.gbdt.learner).__name__],
                **_agreement(self.yh, b_wave.predict(self.Xh),
                             b_mask.predict(self.Xh))}
            out["tolerance"] = {"mean_abs_dp": TOL_MEAN_ABS, "auc": TOL_AUC}
            _require(out["xla_path"]["flags"]["use_scan"] is False,
                     "the XLA-path run still used the Pallas scan")
            _require(out["masked_reference"]["learners"]
                     == ["WaveTPUTreeLearner", "TPUTreeLearner"],
                     str(out["masked_reference"]["learners"]))
            _require_agreement("pallas vs xla path", out["xla_path"])
            _require_agreement("wave vs masked", out["masked_reference"])
            _require(out["masked_reference"]["auc_b"] > AUC_BAND_SMALL,
                     "masked reference AUC below the band")

    # -- phase 4 -------------------------------------------------------------

    def run_serve(self, lgb):
        with self.phase("serve") as out:
            _require(hasattr(self, "bst"), "train phase left no model")
            from lightgbm_tpu.serving import ServingClient
            rng = np.random.RandomState(self.args.seed + 1)
            with tempfile.TemporaryDirectory() as tmp:
                path = os.path.join(tmp, "model.txt")
                self.bst.save_model(path)
                out["model_bytes"] = os.path.getsize(path)
                loaded = lgb.Booster(model_file=path)
            out["trees"] = loaded.num_trees()
            t0 = time.perf_counter()
            server = loaded.serve(port=0, max_batch_rows=256, min_bucket=32)
            out["warm_seconds"] = round(time.perf_counter() - t0, 3)
            try:
                out["buckets"] = [int(b) for b in server.buckets]
                sizes = rng.choice([1, 3, 17, 32, 33, 64, 100, 200, 256],
                                   size=36)
                worst = 0.0
                with ServingClient(server.host, server.port,
                                   timeout=120) as c:
                    for n in sizes:
                        i = rng.randint(0, len(self.yh) - int(n))
                        # float32-representable rows: the wire is float32
                        Xq = self.Xh[i:i + int(n)].astype(np.float32) \
                            .astype(np.float64)
                        got = np.asarray(c.predict(Xq))
                        want = self.bst.predict(Xq)
                        _require(got.shape == want.shape,
                                 f"shape {got.shape} != {want.shape}")
                        worst = max(worst, float(np.abs(got - want).max()))
                    out["protocol"] = c.protocol
                    srv = c.stats()["serving"]
            finally:
                server.stop()
            out["requests"] = int(srv["requests"])
            out["max_abs_diff"] = worst
            out["fallback_batches"] = int(srv["fallback_batches"])
            out["errors"] = int(srv["errors"])
            out["compile_cache"] = srv["compile_cache"]
            out["bucket_batches"] = srv["buckets"]
            _require(out["trees"] == ITERS, f"{out['trees']} trees loaded")
            _require(out["requests"] == len(sizes), "requests were lost")
            _require(worst <= TOL_SERVE,
                     f"served vs Booster.predict differ by {worst:.3g}")
            _require(out["fallback_batches"] == 0 and out["errors"] == 0,
                     "the device path failed: a batch was answered by the "
                     "host fallback")
            _require(srv["compile_cache"]["misses"] == len(out["buckets"]),
                     "a request shape escaped the warmed bucket ladder")

    # -- --chips 4 -----------------------------------------------------------

    def run_mesh(self, lgb):
        a = self.args
        with self.phase("mesh") as out:
            import jax
            X, y, Xh, yh = _make_data(a.seed, a.rows)
            ds = lgb.Dataset(X, label=y, params=dict(PARAMS)).construct()
            out["rows"], out["features"] = X.shape
            serial, first_s, steady_s = _timed_train(lgb, PARAMS, ds,
                                                     ITERS_MESH)
            out["serial"] = {
                "learner": type(serial.gbdt.learner).__name__,
                "cold_first_iteration_seconds": round(first_s, 3),
                "steady_seconds_per_iteration": round(steady_s, 4)}
            p_serial = serial.predict(Xh)
            del serial
            gc.collect()
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                mesh, first_m, steady_m = _timed_train(
                    lgb, dict(PARAMS, tree_learner="data"), ds, ITERS_MESH)
            g = mesh.gbdt
            learner = g.learner
            out["sharded"] = {
                "learner": type(learner).__name__,
                "mesh": None if g._mesh is None else dict(g._mesh.shape),
                "cold_first_iteration_seconds": round(first_m, 3),
                "steady_seconds_per_iteration": round(steady_m, 4)}
            out["serial_fallback_warnings"] = [
                str(w.message) for w in caught
                if "ONE device" in str(w.message)]
            _require(not out["serial_fallback_warnings"],
                     "tree_learner=data trained serial")
            _require(out["sharded"]["learner"] == "ShardedWaveLearner",
                     out["sharded"]["learner"])
            bins_on = len(learner.sharded_bins().sharding.device_set)
            score_on = len(g.train_score.score.sharding.device_set)
            out["bins_devices"], out["score_devices"] = bins_on, score_on
            # a fact for the README, not something this script changes:
            # under shard_map the sharded learners histogram through the
            # XLA one-hot path, not the Pallas kernels
            out["sharded_histogram_path"] = (
                "pallas" if learner._use_pallas else "xla one-hot")
            hlo = learner.lowered_hlo_text()
            out["reduce_scatter_in_hlo"] = "reduce-scatter" in hlo
            out.update(_agreement(yh, p_serial, mesh.predict(Xh)))
            out["tolerance"] = {"mean_abs_dp": TOL_MEAN_ABS, "auc": TOL_AUC}
            out["peak_bytes_in_use"] = [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in jax.devices()]
            _require(bins_on == a.chips and score_on == a.chips,
                     f"bins on {bins_on} devices, score on {score_on}: "
                     f"not sharded over {a.chips}")
            _require(out["reduce_scatter_in_hlo"],
                     "no reduce-scatter in the sharded tree program")
            _require_agreement("serial vs data-parallel", out)
            _require(out["auc_b"] > AUC_BAND_SMALL, "sharded AUC below band")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the data-parallel path on a 4-device "
                         "mesh and the serial run it is compared with")
    ap.add_argument("--rows", type=int, default=1_000_000,
                    help="training rows (cut only to rehearse off-chip)")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the phases without a TPU to debug this "
                         "script; the result is still ok=false")
    args = ap.parse_args(argv)

    smoke = Smoke(args)
    smoke.run_device()
    if smoke.failed and not args.rehearse:
        return smoke.finish()
    try:
        import lightgbm_tpu as lgb
    except ImportError as e:      # the script alone, without the program
        print(f"chip_smoke.py needs the lightgbm_tpu package beside it: {e}",
              file=sys.stderr)
        smoke.failed.append("import")
        return smoke.finish()
    print(json.dumps({"compile_cache_dir": lgb.use_compile_cache()}),
          flush=True)
    if args.chips == 4:
        smoke.run_mesh(lgb)
    else:
        smoke.run_train(lgb)
        smoke.run_parity(lgb)
        smoke.run_serve(lgb)
    return smoke.finish()


if __name__ == "__main__":
    sys.exit(main())
