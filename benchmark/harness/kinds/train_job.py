"""Traffic kind ``train_job``: one ``lightgbm_tpu.train`` call that spans
warm-up and the measured window.

The clock is a harness-owned after-iteration callback.  It syncs with the
device during warm-up only; inside the window it reads the host clock and
counts the iterations that were started.  When ``--seconds`` have passed it
stops the loop, every started iteration is waited for behind a
``block_until_ready`` on the train score, and the rate is all of them over
the seconds the window really lasted.
"""

import gc
import os
import shutil
import time

import numpy as np

from benchmark.harness import data, spans
from benchmark.harness.paths import ROOT


class WindowClock:
    """after-iteration callback (runs last: ``order`` is high)."""

    order = 1000

    def __init__(self, warmup_iters, seconds, trace, events):
        self.warmup_iters, self.seconds = warmup_iters, seconds
        self.trace = trace            # None, or dict(dir, skip, iters)
        self.events = events
        self.first_iter_done = None
        self.t0 = None
        self.started = 0              # iterations started inside the window
        self.compiles_at_t0 = None
        self.trace_state = "off" if trace is None else "armed"

    def _sync(self, env):
        import jax
        jax.block_until_ready(env.model.gbdt.train_score.score)

    def __call__(self, env):
        done = env.iteration - env.begin_iteration + 1
        if done == 1:
            self._sync(env)
            self.first_iter_done = time.perf_counter()
        if done < self.warmup_iters:
            return
        if done == self.warmup_iters:
            self._sync(env)
            gc.collect()
            self.compiles_at_t0 = self.events.compile_requests
            self.t0 = time.perf_counter()
            return
        self.started += 1
        now = time.perf_counter()
        if self.trace_state == "armed" and self.started == self.trace["skip"]:
            import jax
            jax.profiler.start_trace(self.trace["dir"])
            self.trace_state = "on"
        elif (self.trace_state == "on"
              and self.started == self.trace["skip"] + self.trace["iters"]):
            self.stop_trace()
        if now - self.t0 >= self.seconds:
            from lightgbm_tpu.callback import EarlyStopException
            raise EarlyStopException(env.iteration, [])

    def stop_trace(self):
        if self.trace_state == "on":
            import jax
            jax.profiler.stop_trace()
            self.trace_state = "done"


class IterSpan:
    """before-iteration callback: closes the host span of the last iteration
    and opens the next one's, in the profiler's own trace."""

    before_iteration = True
    order = -1000

    def __init__(self):
        self.open = None

    def __call__(self, env):
        self.close()
        import jax
        self.open = jax.profiler.TraceAnnotation("bench_iteration")
        self.open.__enter__()

    def close(self):
        if self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None


def _params(ctx):
    size = ctx.get("size_override") or {}
    params = dict(ctx["config"]["params"], **ctx["traffic"]["extra_params"])
    params.update(size.get("params", {}))
    params.update(ctx.get("param_override") or {})
    return params


def prepare(ctx):
    """Rows from the seed and the binned dataset: what every job on this seed
    shares."""
    import lightgbm_tpu as lgb

    cfg = ctx["config"]
    size = ctx.get("size_override") or {}
    rows = int(size.get("rows", cfg["rows"]))
    hold = int(size.get("holdout_rows", cfg["holdout_rows"]))
    clocks = {}
    t = time.perf_counter()
    X, y = data.make_rows(cfg["generator"], ctx["seed"], data.TRAIN_STREAM,
                          rows, cfg["features"])
    Xh, yh = data.make_rows(cfg["generator"], ctx["seed"],
                            data.HOLDOUT_STREAM, hold, cfg["features"])
    clocks["generate_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, params=_params(ctx)).construct()
    clocks["dataset_construct_s"] = time.perf_counter() - t
    return {"X": X, "y": y, "Xh": Xh, "yh": yh, "ds": ds, "clocks": clocks}


def run(ctx):
    """Drive one cell.  ``ctx`` carries config, traffic, seed, seconds, trace,
    t_start, events; returns the dict that ``run.py`` turns into the line."""
    return drive(ctx, ctx.get("prepared") or prepare(ctx))


def drive(ctx, prepared):
    """One training job on prepared rows: warm-up, window, and what the timed
    path produced."""
    import jax
    import lightgbm_tpu as lgb

    traffic = ctx["traffic"]
    X, y, Xh, yh = (prepared[k] for k in ("X", "y", "Xh", "yh"))
    ds, clocks = prepared["ds"], dict(prepared["clocks"])
    rows, hold = X.shape[0], Xh.shape[0]
    params = _params(ctx)
    kw = dict(traffic["train_args"])
    evals = {}
    if traffic["valid_set"]:
        kw.update(valid_sets=[ds.create_valid(Xh, label=yh)],
                  valid_names=["holdout"], evals_result=evals)

    trace = None
    if ctx["trace"]:
        tdir = os.path.join(ROOT, ".bench_out", "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        os.makedirs(tdir, exist_ok=True)
        trace = {"dir": tdir, "skip": int(traffic["trace_skip_iters"]),
                 "iters": int(traffic["trace_iters"])}
    spans.wrap_program_calls()
    spans.RECORD = bool(trace)
    clock = WindowClock(int(traffic["warmup_iters"]), ctx["seconds"], trace,
                        ctx["events"])
    callbacks = [clock]
    iter_span = None
    if trace:
        iter_span = IterSpan()
        callbacks.append(iter_span)

    t_train = time.perf_counter()
    bst = lgb.train(params, ds, int(ctx["config"]["job_iterations"]),
                    callbacks=callbacks, verbose_eval=False, **kw)
    jax.block_until_ready(bst.gbdt.train_score.score)
    t1 = time.perf_counter()
    compiles_in_window = ctx["events"].compile_requests - (
        clock.compiles_at_t0 or 0)
    if iter_span:
        iter_span.close()
    clock.stop_trace()
    if clock.t0 is None:
        raise RuntimeError("the job ended inside its warm-up")

    window_s = t1 - clock.t0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices()[:ctx["chips"]])
    clocks["first_iter_s"] = clock.first_iter_done - t_train
    g = bst.gbdt
    learner = type(g.learner).__name__
    path = {"learner": learner, "fused": bool(g._can_fuse()),
            "pipelined": bool(g._can_pipeline())}

    # what the timed path produced, taken before its state is freed
    model_text = bst.model_to_string()
    n_hold = min(int(traffic.get("check_holdout_rows", hold)), hold)
    p_hold = np.asarray(bst.predict(Xh[:n_hold]), np.float64)
    sample = np.sort(data.rng_for(ctx["seed"], data.CHECK_STREAM).choice(
        rows, size=min(rows, int(traffic.get("check_train_rows", 100000))),
        replace=False))
    score_sample = np.asarray(g.train_score.score[0], np.float64)[sample]
    del bst, g, ds, prepared
    gc.collect()

    return {
        "window_s": window_s, "started": clock.started,
        "setup_s": clock.t0 - ctx["t_start"],
        "peak_bytes": int(peak), "clocks": clocks,
        "compiles_in_window": compiles_in_window,
        "path": path,
        "warmup_iters": clock.warmup_iters,
        "trace_dir": trace["dir"] if trace else None,
        "produced": {"X": X, "y": y, "Xh": Xh[:n_hold], "yh": yh[:n_hold],
                     "model_text": model_text, "p_holdout": p_hold,
                     "score_sample": score_sample, "sample_idx": sample,
                     "valid_auc": (evals.get("holdout", {}).get("auc")
                                   if traffic["valid_set"] else None),
                     "params": params},
    }


def check(run):
    """(correct, checks, info).  Also leaves ``failed``, ``rows`` and the
    window's parsed trees on ``run`` for the readers."""
    from benchmark.harness import check_train
    from benchmark.reference import gbdt_plain

    ctx, produced = run["ctx"], run.pop("produced")
    trees = gbdt_plain.parse_model(produced["model_text"])["trees"]
    window = trees[run["warmup_iters"]:]
    run["rows"] = produced["X"].shape[0]
    run["window_trees"] = window
    # started in the window, and either never finished or grew no tree
    run["failed"] = (max(run["started"] - len(window), 0)
                     + sum(1 for t in window if t["num_leaves"] <= 1))
    numbers, info = check_train.audit(
        produced, ctx["seed"], int(ctx["traffic"]["check_trees"]),
        control=ctx.get("control"))
    numbers["trees_missing"] = run["failed"]
    correct, checks = check_train.decide(numbers)
    return correct, checks, info
