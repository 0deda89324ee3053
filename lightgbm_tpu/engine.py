"""Training engine: ``train`` / ``cv`` and the ``Booster`` facade.

Mirrors the reference python package (`python-package/lightgbm/engine.py:19-447`
``train``/``cv`` and `basic.py:1577+` ``Booster``): same signatures, callback
protocol (``CallbackEnv``), early stopping and evaluation-history semantics,
so user code written against the reference's ``lgb.train`` runs unchanged.
"""

from __future__ import annotations

import collections
import copy
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from . import callback as callback_mod
from .boosting import create_boosting
from .boosting.gbdt import GBDT
from .config import Config
from .dataset import Dataset
from .metrics import create_metric
from .objectives import create_objective


class Booster:
    """User-facing booster handle (`python-package/lightgbm/basic.py:1577`)."""

    def __init__(self, params: Optional[Dict] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        params = dict(params or {})
        self.params = params
        self.cfg = Config.from_params(params)
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_set = train_set
        self.gbdt: Optional[GBDT] = None
        # multi-host pod: join the jax.distributed cluster BEFORE the first
        # device touch (dataset construct uploads arrays); the per-iteration
        # liveness heartbeat rides the same coordinator (parallel/multihost)
        self._mh_net = None
        self._last_step_s: Optional[float] = None
        from .parallel import multihost
        if multihost.initialize_from_config(self.cfg) and train_set is not None:
            self._mh_net = multihost.net_for_run(self.cfg)
        if train_set is not None:
            import time as _time
            _t0 = _time.perf_counter()
            train_set.construct()
            _bin_s = _time.perf_counter() - _t0
            objective = create_objective(self.cfg)
            self.gbdt = create_boosting(self.cfg)
            train_metrics = []
            if self.cfg.is_provide_training_metric:
                train_metrics = self._make_metrics(train_set)
            self.gbdt.init(train_set, objective, train_metrics)
            # binning happened before the GBDT (and its Telemetry) existed
            # — credit it to the report's "binning" phase after the fact
            self.gbdt.telemetry.add_phase_time("binning", _bin_s)
            if self._mh_net is not None:
                self.gbdt.telemetry.set_distributed(
                    process_count=int(self._mh_net.num_machines),
                    process_index=int(self._mh_net.rank))
                if self.cfg.elastic:
                    self.gbdt.telemetry.set_elastic(
                        epoch=int(self.cfg.elastic_epoch),
                        members=int(self._mh_net.num_machines))
        elif model_file is not None:
            with open(model_file) as fh:
                self._load_from_string(fh.read())
        elif model_str is not None:
            self._load_from_string(model_str)
        else:
            raise ValueError("At least one of params/train_set, model_file "
                             "or model_str should be provided")

    def _load_from_string(self, s: str) -> None:
        self.gbdt = GBDT(self.cfg)
        self.gbdt.load_model_from_string(s)

    def _make_metrics(self, dataset: Dataset):
        metrics = []
        for name in self.cfg.metric:
            m = create_metric(name, self.cfg)
            if m is not None:
                m.init(dataset.constructed.metadata, dataset.constructed.num_data)
                metrics.append(m)
        return metrics

    # -- training-side API ---------------------------------------------------

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.construct()
        self.gbdt.add_valid_data(data, name, self._make_metrics(data))
        return self

    def update(self, train_set: Optional[Dataset] = None,
               fobj: Optional[Callable] = None) -> bool:
        """One boosting iteration (`basic.py:1842`); returns True if training
        should stop."""
        tel = self.gbdt.telemetry
        if self._mh_net is not None:
            # pre-step liveness agreement: a host that died since the last
            # iteration surfaces HERE as a ConnectionError naming the dead
            # rank (within the collective deadline) instead of a hang
            # inside the next XLA collective.  With telemetry on, the LAST
            # step's host duration rides the same allgather — straggler
            # detection without an extra collective
            payload = self._last_step_s if tel.enabled else None
            with tel.phase("heartbeat"):
                from .parallel.multihost import RankDeathError
                try:
                    peers = self._mh_net.heartbeat(self.gbdt.iter_,
                                                   payload=payload)
                except RankDeathError as e:
                    # the engine's abort verdict: which iteration of which
                    # membership epoch died — the elastic controller keys
                    # its recovery on exactly this (epoch, dead_ranks) pair
                    raise RankDeathError(
                        f"training aborted before iteration "
                        f"{self.gbdt.iter_ + 1} (membership epoch "
                        f"{e.epoch}): {e}", dead_ranks=e.dead_ranks,
                        epoch=e.epoch) from None
            if tel.enabled:
                self._note_rank_skew(peers)
        if not tel.enabled:
            if fobj is None:
                return self.gbdt.train_one_iter()
            grad, hess = fobj(self._curr_preds(), self._train_set)
            return self.__boost(grad, hess)
        import time as _time
        _t0 = _time.perf_counter()
        if fobj is None:
            ret = self.gbdt.train_one_iter()
        else:
            grad, hess = fobj(self._curr_preds(), self._train_set)
            ret = self.__boost(grad, hess)
        self._last_step_s = _time.perf_counter() - _t0
        return ret

    def _note_rank_skew(self, peers) -> None:
        """Land rank-skew gauges from the heartbeat's gathered step
        timings; past ``telemetry_skew_warn_ratio`` emit a warning NAMING
        the slowest rank."""
        tel = self.gbdt.telemetry
        times: Dict[int, Optional[float]] = {}
        for p in peers or ():
            if isinstance(p, tuple) and len(p) >= 4 and p[0] == "hb":
                times[int(p[1])] = None if p[3] is None else float(p[3])
        vals = sorted(s for s in times.values() if s is not None)
        if not vals:
            return
        tel.set_distributed(rank_step_s={str(r): s for r, s
                                         in sorted(times.items())})
        if len(vals) < 2:
            return
        m = len(vals)
        med = vals[m // 2] if m % 2 else \
            0.5 * (vals[m // 2 - 1] + vals[m // 2])
        slow_s, slow_rank = max(
            (s, r) for r, s in times.items() if s is not None)
        ratio = (slow_s / med) if med > 0 else 0.0
        warn_ratio = float(getattr(self.cfg,
                                   "telemetry_skew_warn_ratio", 0.0))
        tel.set_distributed(skew_ratio=ratio, slowest_rank=int(slow_rank),
                            skew_warn_ratio=warn_ratio)
        if warn_ratio > 0 and ratio > warn_ratio:
            tel.inc("straggler_warnings")
            warnings.warn(
                f"straggler: rank {slow_rank} last step "
                f"{slow_s * 1e3:.1f} ms is {ratio:.2f}x the pod median "
                f"({med * 1e3:.1f} ms)")

    def __boost(self, grad: np.ndarray, hess: np.ndarray) -> bool:
        return self.gbdt.train_one_iter(grad, hess)

    def _curr_preds(self) -> np.ndarray:
        return self.gbdt.train_score.np_score()

    def rollback_one_iter(self) -> "Booster":
        self.gbdt.rollback_one_iter()
        return self

    @property
    def current_iteration(self) -> int:
        return self.gbdt.iter_

    def num_trees(self) -> int:
        return len(self.gbdt.models)

    # -- evaluation ----------------------------------------------------------

    def eval_train(self, feval=None) -> List[Tuple]:
        return self._eval_set("training", self.gbdt.train_score,
                              self.gbdt.training_metrics, feval,
                              self._train_set)

    def eval_valid(self, feval=None) -> List[Tuple]:
        out = []
        with self.gbdt.telemetry.phase("eval_valid", it=self.gbdt.iter_):
            for i, name in enumerate(self.gbdt.valid_names):
                out.extend(self._eval_set(
                    name, self.gbdt.valid_scores[i],
                    self.gbdt.valid_metrics[i], feval, None))
        return out

    def _eval_set(self, name, updater, metrics, feval, dataset) -> List[Tuple]:
        results = []
        score = updater.np_score()
        for m in metrics:
            for mname, val in m.eval(score, self.gbdt.objective):
                results.append((name, mname, val, m.is_higher_better))
        if feval is not None:
            ds = dataset if dataset is not None else None
            fname, fval, higher_better = feval(score, ds)
            results.append((name, fname, fval, higher_better))
        # keep the per-iteration history that cv()/sklearn evals_result_ read
        for dname, mname, val, _ in results:
            self.gbdt.eval_history.setdefault(dname, {}).setdefault(
                mname, []).append(val)
        return results

    # -- prediction / persistence -------------------------------------------

    def predict(self, data, num_iteration: int = -1, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                **kwargs) -> np.ndarray:
        if hasattr(data, "dtypes") and hasattr(data, "columns") \
                and not isinstance(data, np.ndarray):
            data = self._predict_data_from_pandas(data)
        elif hasattr(data, "values") and not isinstance(data, np.ndarray):
            data = data.values
        data = np.asarray(data, dtype=np.float64)
        if pred_contrib:
            from .contrib import predict_contrib
            return predict_contrib(self.gbdt, data, num_iteration)
        return self.gbdt.predict(data, num_iteration, raw_score, pred_leaf)

    def save_model(self, filename: str, num_iteration: int = -1,
                   start_iteration: int = 0) -> "Booster":
        if num_iteration < 0:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        self.gbdt.save_model_to_file(filename, start_iteration, num_iteration)
        return self

    def model_to_string(self, num_iteration: int = -1,
                        start_iteration: int = 0) -> str:
        if num_iteration < 0:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        return self.gbdt.save_model_to_string(start_iteration, num_iteration)

    def dump_model(self, num_iteration: int = -1, start_iteration: int = 0
                   ) -> Dict:
        """Model as a JSON-able dict (`basic.py:2102` / ``DumpModel``,
        `gbdt_model_text.cpp:15`)."""
        if num_iteration < 0:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        ret = self.gbdt.dump_model(start_iteration, num_iteration)
        # the python layer appends pandas category mappings (`basic.py:2233`);
        # None for non-pandas-categorical training data
        ret["pandas_categorical"] = self.gbdt.pandas_categorical
        return ret

    def _predict_data_from_pandas(self, df) -> np.ndarray:
        """Predict-time DataFrame conversion: re-apply the category lists
        recorded at training (`basic.py:262-304` — the stored order defines
        the code space; unseen values → NaN)."""
        stored = self.gbdt.pandas_categorical
        cat_cols = [j for j, c in enumerate(df.columns)
                    if str(df.dtypes.iloc[j]) == "category"]
        if not cat_cols:
            return np.asarray(df.values, dtype=np.float64)
        if stored is None or len(stored) != len(cat_cols):
            raise ValueError(
                "train and predict dataset categorical_feature do not "
                f"match ({0 if stored is None else len(stored)} recorded "
                f"category columns vs {len(cat_cols)} in this DataFrame)")
        from .dataset import recode_pandas
        return recode_pandas(df, cat_cols, stored)

    def refit(self, data, label, decay_rate: float = 0.9,
              **kwargs) -> "Booster":
        """Refit the existing model's leaf values on new data
        (`basic.py:2284` Booster.refit → ``GBDT::RefitTree``,
        `gbdt.cpp:262-286`)."""
        leaf_preds = self.predict(data, pred_leaf=True, **kwargs)
        leaf_preds = np.atleast_2d(np.asarray(leaf_preds))
        new_train = Dataset(data, label=label, params=dict(self.params))
        new_booster = Booster(params=dict(self.params), train_set=new_train)
        import copy as _copy
        new_booster.gbdt.models = [_copy.deepcopy(t) for t in self.gbdt.models]
        new_booster.gbdt.iter_ = len(new_booster.gbdt.models) // max(
            new_booster.gbdt.num_tree_per_iteration, 1)
        for tree in new_booster.gbdt.models:
            # inner bin-space fields refer to the OLD dataset; rebuild lazily
            # if this booster continues training (`_continue_training`)
            tree.needs_rebind = True
        new_booster.gbdt.refit_leaf_preds(leaf_preds, decay_rate)
        return new_booster

    def refit_file(self, data_path: str, decay_rate: float = 0.9) -> "Booster":
        """CLI ``task=refit``: refit in place from a data file."""
        from .io.parser import load_data_file
        mat, label, _, _ = load_data_file(data_path, self.params)
        refitted = self.refit(mat, label, decay_rate)
        self.gbdt = refitted.gbdt
        return self

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        return self.gbdt.feature_importance(importance_type, iteration)

    def get_telemetry(self, light: bool = False) -> Dict:
        """Training telemetry report (``telemetry=True`` in params; see
        README "Telemetry & profiling" and observability/schema.json)."""
        return self.gbdt.get_telemetry(light=light)

    # -- serving (lightgbm_tpu/serving/) -------------------------------------

    def to_server(self, replicas: int = 0, **kwargs) -> "Any":
        """An UNSTARTED server with this booster registered as the
        ``default`` model (see README "Serving").  ``replicas=0`` (the
        default) builds the single-replica threaded ``PredictionServer``;
        any other value builds the async binary-protocol ``FleetServer``
        (``-1`` = one replica per local device, N>0 = exactly N).
        Keyword args are forwarded (host/port/max_batch_rows/deadline_ms/
        min_bucket/warmup/max_inflight/telemetry_out, the observability
        knobs trace/trace_out/trace_capacity/stats_out/stats_interval_s,
        and the lifecycle traffic-ring capacity record_rows)."""
        from . import use_compile_cache
        use_compile_cache()
        if replicas:
            from .serving import FleetServer

            return FleetServer(booster=self,
                               replicas=max(int(replicas), 0), **kwargs)
        from .serving import PredictionServer

        return PredictionServer(booster=self, **kwargs)

    def serve(self, **kwargs) -> "Any":
        """Start serving this booster over a socket; returns the running
        server (``.host``/``.port``/``.stop()``)."""
        return self.to_server(**kwargs).start()

    def feature_name(self) -> List[str]:
        return list(self.gbdt.feature_names)

    def num_feature(self) -> int:
        return self.gbdt.max_feature_idx + 1

    def __getstate__(self):
        state = {"model_str": self.model_to_string(num_iteration=-1),
                 "params": self.params,
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score}
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        self.cfg = Config.from_params(self.params)
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]
        self._train_set = None
        self._load_from_string(state["model_str"])


def train(params: Dict, train_set: Dataset, num_boost_round: int = 100,
          valid_sets: Optional[Sequence[Dataset]] = None,
          valid_names: Optional[Sequence[str]] = None,
          fobj: Optional[Callable] = None, feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          feature_name: str = "auto", categorical_feature: str = "auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None, verbose_eval=True,
          learning_rates=None, keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          resume: Optional[bool] = None) -> Booster:
    """`python-package/lightgbm/engine.py:19-245` semantics.

    Beyond the reference: ``snapshot_freq > 0`` checkpoints the model text
    every K iterations (atomic write + config-fingerprint sidecar +
    retention — `reliability/resume.py`), and ``resume=True`` (or config
    ``resume``/CLI ``--resume``) continues a killed run from the newest
    valid snapshot, training only the REMAINING iterations so the result
    is identical to an uninterrupted run.  Resume composes with
    ``init_model`` continued training (the lifecycle refit path): a
    snapshot NEWER than the incumbent wins — it already embeds the
    incumbent's trees — and the run still targets the original total of
    incumbent iterations + ``num_boost_round``; with no (or an older)
    snapshot the incumbent warm-starts as usual."""
    from . import use_compile_cache
    use_compile_cache()
    params = dict(params or {})
    cfg_probe = Config.from_params(params)
    if "num_iterations" not in params and num_boost_round is not None:
        params["num_iterations"] = num_boost_round
    num_boost_round = Config.from_params(params).num_iterations
    if fobj is not None:
        params["objective"] = "none"
    if cfg_probe.fault_spec:
        from .reliability import faults
        faults.arm(cfg_probe.fault_spec)

    # warm start: an init_model (continued training / refit) seeds the
    # incumbent's trees and replayed scores before boosting continues on
    # the fresh data.  Loaded up front so the crash-safe resume decision
    # below can compare snapshot iterations against the incumbent's.
    init_booster: Optional[Booster] = None
    resume_base_iter = 0
    if init_model is not None:
        init_booster = init_model if isinstance(init_model, Booster) else \
            Booster(model_file=init_model, params=params)
        resume_base_iter = init_booster.current_iteration

    # crash-safe resume: the newest valid snapshot becomes the init model.
    # Composes with init_model (a refit killed mid-run): the snapshot
    # already EMBEDS the incumbent's trees, so it wins whenever it is
    # newer than the incumbent, and the round target stays the original
    # refit's total (incumbent iterations + num_boost_round)
    resumed_iter: Optional[int] = None
    snapshot_state_path: Optional[str] = None
    if (resume if resume is not None else cfg_probe.resume):
        from .reliability.metrics import rel_inc
        from .reliability.resume import find_resume_snapshot
        found = find_resume_snapshot(cfg_probe.output_model, cfg_probe)
        if found is not None and found[0] > resume_base_iter:
            resumed_iter, snapshot_state_path = found
            init_booster = Booster(model_file=snapshot_state_path,
                                   params=params)
            rel_inc("resume_runs")

    train_set.params = {**params, **(train_set.params or {})}
    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    # structured span recorder (observability/trace.py): host-side only —
    # attaching it cannot change a traced program (and needs no
    # ``telemetry``: a span is kept when a recorder listens), and with
    # trace_out unset nothing is allocated.  Created BEFORE the Booster (and
    # registered process-wide) so the streaming loader's ingestion-chunk
    # spans — recorded during dataset construction, before the GBDT's
    # Telemetry exists — land in the same flight recorder
    _tracer = None
    if cfg_probe.trace_out:
        from .observability.trace import TraceRecorder, set_global_tracer
        _tracer = TraceRecorder(True, capacity=cfg_probe.trace_capacity)
        set_global_tracer(_tracer)
    booster = Booster(params=params, train_set=train_set)
    if _tracer is not None:
        booster.gbdt.telemetry.tracer = _tracer
    if init_booster is not None:
        _continue_training(booster, init_booster)
        if snapshot_state_path is not None:
            # exact continuation: the state sidecar restores the LIVE
            # float32 score array and RNG streams, making the resumed
            # run bit-identical to an uninterrupted one (the traversal
            # replay above is a ulp-level approximation of it)
            from .reliability.resume import (load_snapshot_state,
                                             restore_training_state)
            state = load_snapshot_state(snapshot_state_path)
            if state is not None:
                restore_training_state(booster.gbdt, state)

    valid_sets = list(valid_sets or [])
    names = []
    for i, vs in enumerate(valid_sets):
        if vs is train_set:
            continue
        name = (valid_names[i] if valid_names and i < len(valid_names)
                else f"valid_{i}")
        booster.add_valid(vs, name)
        names.append(name)

    callbacks = list(callbacks or [])
    if verbose_eval is True:
        callbacks.append(callback_mod.print_evaluation())
    elif isinstance(verbose_eval, int) and verbose_eval >= 1:
        callbacks.append(callback_mod.print_evaluation(verbose_eval))
    if early_stopping_rounds is not None and early_stopping_rounds > 0:
        callbacks.append(callback_mod.early_stopping(
            early_stopping_rounds, verbose=bool(verbose_eval)))
    if evals_result is not None:
        callbacks.append(callback_mod.record_evaluation(evals_result))
    if learning_rates is not None:
        callbacks.append(callback_mod.reset_parameter(
            learning_rate=learning_rates))
    callbacks_before = [cb for cb in callbacks
                        if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks
                       if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    init_iter = booster.current_iteration
    # resumed runs train to the ORIGINAL target — the incumbent's
    # iterations (0 for a from-scratch run) plus the requested rounds —
    # while init_model continuation keeps the reference's "N more
    # rounds" semantics
    end_iter = init_iter + num_boost_round if resumed_iter is None \
        else max(resume_base_iter + num_boost_round, init_iter)
    snapshot_freq = cfg_probe.snapshot_freq
    evaluation_result_list: List[Tuple] = []
    # opt-in jax.profiler device trace around the training loop — real
    # per-op device timings
    _tracing = False
    if cfg_probe.profile_trace_dir:
        try:
            import jax as _jax
            _jax.profiler.start_trace(cfg_probe.profile_trace_dir)
            _tracing = True
        except Exception as e:
            warnings.warn(f"profile_trace_dir set but the profiler trace "
                          f"could not start: {e}")
    for i in range(init_iter, end_iter):
        env = callback_mod.CallbackEnv(
            model=booster, params=params, iteration=i,
            begin_iteration=init_iter,
            end_iteration=end_iter,
            evaluation_result_list=None)
        for cb in callbacks_before:
            cb(env)
        finished = booster.update(fobj=fobj)
        if snapshot_freq > 0 and cfg_probe.output_model \
                and (i + 1) % snapshot_freq == 0:
            from .reliability.resume import save_snapshot
            save_snapshot(booster.gbdt, cfg_probe.output_model, i + 1,
                          cfg_probe)
        # chaos seam: `train.crash[:nth=K]` kills the run after its K-th
        # completed iteration (snapshot, if due, already written) so the
        # lifecycle tests exercise the REAL kill-mid-refit → resume path
        from .reliability import faults as _faults
        if _faults.fire("train.crash") is not None:
            raise RuntimeError(
                f"injected fault train.crash at iteration {i + 1}")
        evaluation_result_list = []
        if booster.gbdt.valid_metrics or booster.gbdt.training_metrics or feval:
            if booster.gbdt.training_metrics or (
                    feval and cfg_probe.is_provide_training_metric):
                evaluation_result_list.extend(booster.eval_train(feval))
            evaluation_result_list.extend(booster.eval_valid(feval))
        env = env._replace(evaluation_result_list=evaluation_result_list)
        try:
            for cb in callbacks_after:
                cb(env)
        except callback_mod.EarlyStopException as es:
            booster.best_iteration = es.best_iteration + 1
            for name, mname, val, _ in es.best_score:
                booster.best_score.setdefault(name, {})[mname] = val
            break
        if finished:
            break
    if _tracing:
        try:
            import jax as _jax
            _jax.profiler.stop_trace()
        except Exception as e:
            warnings.warn(f"profiler trace did not stop cleanly: {e}")
    if booster.best_iteration <= 0:
        for name, mname, val, _ in (evaluation_result_list or []):
            booster.best_score.setdefault(name, {})[mname] = val
    if booster._mh_net is not None and (
            (cfg_probe.telemetry and cfg_probe.telemetry_out)
            or cfg_probe.trace_out):
        # one clock-offset handshake serves both the report's
        # distributed.clock and the per-rank trace metadata below
        from .observability import podtrace as _podtrace
        _clk = _podtrace.estimate_clock_offset(booster._mh_net)
        booster.gbdt.telemetry.set_distributed(clock={
            "offset_us": _clk["offset_s"] * 1e6,
            "rtt_us": _clk["rtt_s"] * 1e6,
            "rounds": _clk["rounds"], "method": _clk["method"]})
    else:
        _clk = None
    if cfg_probe.telemetry and cfg_probe.telemetry_out:
        from .observability import write_report
        write_report(booster.get_telemetry(), cfg_probe.telemetry_out)
    if cfg_probe.telemetry and cfg_probe.telemetry_prom_out:
        from .observability.metrics_export import training_prometheus
        import os
        _prom_tmp = cfg_probe.telemetry_prom_out + ".tmp"
        with open(_prom_tmp, "w") as _fh:
            _fh.write(training_prometheus(booster.get_telemetry()))
        os.replace(_prom_tmp, cfg_probe.telemetry_prom_out)
    if _tracer is not None:
        # annotate the span timeline with the collective ledger's static
        # sites (op/phase/cadence/bytes), then write the Chrome JSON —
        # per-rank (`<trace_out>.rank<r>`) on a pod, with the clock
        # handshake stamped into otherData for podtrace.merge_pod_trace
        ledger = getattr(booster.gbdt.learner, "_ledger", None)
        if ledger is not None:
            for site in ledger.sites():
                _tracer.instant(f"collective:{site['op']}",
                                cat="collective", args=dict(site))
        from .observability import podtrace as _podtrace
        from .observability.trace import set_global_tracer
        _podtrace.export_rank_trace(_tracer, cfg_probe.trace_out,
                                    net=booster._mh_net, clock=_clk)
        set_global_tracer(None)
    return booster


def _continue_training(booster: Booster, init_booster: Booster) -> None:
    """Continue-training: seed models and replay their scores
    (`boosting.cpp:43-62`, `application.cpp:88-93` init-score threading)."""
    from .boosting.gbdt import _traverse_tree_binned, rebind_tree_to_dataset
    gbdt = booster.gbdt
    src = init_booster.gbdt
    gbdt.models = [copy.deepcopy(t) for t in src.models]
    gbdt.num_tree_per_iteration = src.num_tree_per_iteration
    gbdt.iter_ = len(gbdt.models) // max(gbdt.num_tree_per_iteration, 1)
    for tree in gbdt.models:
        # the copied inner fields (split_feature_inner / threshold_in_bin)
        # are in the SOURCE dataset's bin space — always rebind against the
        # new training data's bins (rebind also drops the traversal cache)
        tree.needs_rebind = True
        rebind_tree_to_dataset(tree, gbdt.train_data)
    for idx, tree in enumerate(gbdt.models):
        k = idx % gbdt.num_tree_per_iteration
        if tree.num_leaves > 1:
            delta = _traverse_tree_binned(gbdt.train_data, tree)
            gbdt.train_score.score = gbdt.train_score.score.at[k].add(delta)
            for vs in gbdt.valid_scores:
                vs.add_by_tree(tree, k)
        else:
            gbdt.train_score.add_constant(float(tree.leaf_value[0]), k)
            for vs in gbdt.valid_scores:
                vs.add_constant(float(tree.leaf_value[0]), k)
    gbdt.train_score.has_init_score = True


class CVBooster:
    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def _append(self, booster):
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler


def cv(params: Dict, train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, fobj=None, feval=None, init_model=None,
       feature_name="auto", categorical_feature="auto",
       early_stopping_rounds=None, fpreproc=None, verbose_eval=None,
       show_stdv: bool = True, seed: int = 0, callbacks=None,
       eval_train_metric: bool = False) -> Dict[str, List[float]]:
    """K-fold cross-validation (`engine.py:334-447`)."""
    params = dict(params or {})
    if metrics is not None:
        params["metric"] = metrics
    # params-carried round counts (num_iterations/n_estimators/...) win,
    # like train()
    if "num_iterations" not in params and num_boost_round is not None:
        params["num_iterations"] = num_boost_round
    num_boost_round = Config.from_params(params).num_iterations
    train_set.construct()
    full = train_set
    n = full.num_data()
    label = np.asarray(full.get_label())
    rng = np.random.RandomState(seed)
    if folds is None:
        idx = np.arange(n)
        if stratified and Config.from_params(params).objective in (
                "binary", "multiclass", "multiclassova"):
            folds = _stratified_folds(label, nfold, rng, shuffle)
        else:
            if shuffle:
                rng.shuffle(idx)
            folds = [(np.setdiff1d(idx, idx[f::nfold], assume_unique=False),
                      idx[f::nfold]) for f in range(nfold)]

    results = collections.defaultdict(list)
    cvbooster = CVBooster()
    raw = full._load_raw(full._raw_data)
    weights = full.get_weight()
    for train_idx, test_idx in folds:
        dtrain = Dataset(raw[train_idx], label=label[train_idx],
                         weight=None if weights is None else weights[train_idx],
                         params=params,
                         categorical_feature=full.categorical_feature)
        dtest = Dataset(raw[test_idx], label=label[test_idx],
                        weight=None if weights is None else weights[test_idx],
                        reference=dtrain, params=params)
        if fpreproc is not None:
            dtrain, dtest, params = fpreproc(dtrain, dtest, dict(params))
        params_fold = dict(params)
        params_fold.pop("early_stopping_round", None)
        bst = Booster(params=params_fold, train_set=dtrain)
        bst.add_valid(dtest, "valid")
        cvbooster._append(bst)

    # lockstep boosting: one round across ALL folds, then aggregate and run
    # the early-stopping logic (and user callbacks) on the AGGREGATED means
    # — the reference's cv structure (`engine.py:334-447` +
    # ``_agg_cv_result``), not a post-hoc truncation of independent folds
    callbacks = list(callbacks or [])
    if early_stopping_rounds:
        callbacks.append(callback_mod.early_stopping(
            early_stopping_rounds, verbose=bool(verbose_eval)))
    if isinstance(verbose_eval, int) and not isinstance(verbose_eval, bool) \
            and verbose_eval > 0:
        callbacks.append(callback_mod.print_evaluation(verbose_eval,
                                                       show_stdv))
    elif verbose_eval is True:
        callbacks.append(callback_mod.print_evaluation(show_stdv=show_stdv))
    cbs_before = sorted((cb for cb in callbacks
                         if getattr(cb, "before_iteration", False)),
                        key=lambda cb: getattr(cb, "order", 0))
    cbs_after = sorted((cb for cb in callbacks
                        if not getattr(cb, "before_iteration", False)),
                       key=lambda cb: getattr(cb, "order", 0))
    stopped_at = -1
    for it in range(num_boost_round):
        env = callback_mod.CallbackEnv(
            model=cvbooster, params=params, iteration=it,
            begin_iteration=0, end_iteration=num_boost_round,
            evaluation_result_list=None)
        for cb in cbs_before:
            cb(env)
        finished = False
        agg: Dict[str, List[float]] = collections.defaultdict(list)
        hb_map: Dict[str, bool] = {}
        for bst in cvbooster.boosters:
            if bst.update(fobj=fobj):
                finished = True
            for dname, mname, val, hb in bst.eval_valid(feval):
                agg[mname].append(val)
                hb_map[mname] = hb
        agg_list = []
        for mname, vals in agg.items():
            results[f"{mname}-mean"].append(float(np.mean(vals)))
            results[f"{mname}-stdv"].append(float(np.std(vals)))
            agg_list.append(("cv_agg", mname, float(np.mean(vals)),
                             hb_map[mname], float(np.std(vals))))
        try:
            env = env._replace(evaluation_result_list=agg_list)
            for cb in cbs_after:
                cb(env)
        except callback_mod.EarlyStopException as e:
            stopped_at = getattr(e, "best_iteration", it)
            break
        if finished:
            break
    if stopped_at >= 0:
        for key in list(results):
            results[key] = results[key][:stopped_at + 1]
    return dict(results)


def _stratified_folds(label, nfold, rng, shuffle):
    classes = np.unique(label)
    test_folds = [[] for _ in range(nfold)]
    for c in classes:
        idx = np.where(label == c)[0]
        if shuffle:
            rng.shuffle(idx)
        for f in range(nfold):
            test_folds[f].extend(idx[f::nfold])
    n = len(label)
    out = []
    for f in range(nfold):
        test = np.asarray(sorted(test_folds[f]))
        train_idx = np.setdiff1d(np.arange(n), test)
        out.append((train_idx, test))
    return out
