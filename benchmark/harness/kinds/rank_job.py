"""Traffic kind ``rank_job``: one ``lightgbm_tpu.train`` call on rows grouped
in queries (``group=``) that spans warm-up and the measured window.

The window, its clock and what the timed path produced are ``train_job``'s
own (``train_job.drive``).  This kind makes the rows and their queries
(``harness/rank_data.py``), reads the objective's pair-layout counters off
the booster before its first iteration, and judges the trees with lambdarank
gradients (``harness/check_rank.py``).
"""

import contextlib
import time

from benchmark.harness import data, rank_data
from benchmark.harness.kinds import train_job


def prepare(ctx):
    """Rows and queries from the seed, and the binned dataset."""
    import lightgbm_tpu as lgb

    cfg = ctx["config"]
    size = ctx.get("size_override") or {}
    rows = int(size.get("rows", cfg["rows"]))
    queries = int(size.get("queries", cfg["queries"]))
    hold = int(size.get("holdout_rows", cfg["holdout_rows"]))
    spec = rank_data.spec_of(cfg["generator"])
    clocks = {}
    t = time.perf_counter()
    group = rank_data.query_lengths(spec, queries, rows)
    X, y = rank_data.make_queries(cfg["generator"], ctx["seed"],
                                  data.TRAIN_STREAM, group, cfg["features"])
    group_h = rank_data.holdout_lengths(spec, hold, rows / queries,
                                        ctx["seed"])
    Xh, yh = rank_data.make_queries(cfg["generator"], ctx["seed"],
                                    data.HOLDOUT_STREAM, group_h,
                                    cfg["features"])
    clocks["generate_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ds = lgb.Dataset(X, label=y, group=group,
                     params=train_job._params(ctx)).construct()
    clocks["dataset_construct_s"] = time.perf_counter() - t
    return {"X": X, "y": y, "Xh": Xh, "yh": yh, "ds": ds, "clocks": clocks,
            "group": group, "group_holdout": group_h}


class NoPairLayout(RuntimeError):
    """The program reports no pair layout for its ranking objective."""


@contextlib.contextmanager
def _reading_layout(into):
    """``lightgbm_tpu.train`` with one more before-iteration callback, which
    copies the objective's pair-layout counters (``pair_layout``) into
    ``into`` once the booster is built, before the first iteration runs.  A
    program that reports none pads every query to the longest one: at this
    cell's size its first iteration compiles for longer than a run may last
    and exhausts the host's memory (PERF.md section 4), so the run stops here
    with a plain error instead."""
    import lightgbm_tpu as lgb

    train = lgb.train

    def read_layout(env):
        if env.iteration == env.begin_iteration:
            layout = getattr(env.model.gbdt.objective, "pair_layout", None)
            if not layout:
                raise NoPairLayout(
                    "the ranking objective reports no pair layout "
                    "(lightgbm_tpu/rank_objective.py: pair_layout); this "
                    "kind runs only a program that buckets queries by "
                    "length")
            into.update(layout)
    read_layout.before_iteration = True

    def train_reading(*a, callbacks=None, **kw):
        return train(*a, callbacks=list(callbacks or []) + [read_layout],
                     **kw)
    lgb.train = train_reading
    try:
        yield
    finally:
        lgb.train = train


def run(ctx):
    prepared = [ctx.get("prepared") or prepare(ctx)]
    groups = {k: prepared[0][k] for k in ("group", "group_holdout")}
    layout = {}
    with _reading_layout(layout):
        # the list hands drive the only reference, which it frees before
        # the check
        out = train_job.drive(ctx, prepared.pop())
    out["rank_layout"] = layout
    out["produced"].update(groups)
    return out


def check(run):
    """(correct, checks, info).  Also leaves ``failed``, ``rows`` and the
    window's parsed trees on ``run`` for the readers."""
    from benchmark.harness import check_rank
    from benchmark.reference import gbdt_plain

    ctx, produced = run["ctx"], run.pop("produced")
    trees = gbdt_plain.parse_model(produced["model_text"])["trees"]
    window = trees[run["warmup_iters"]:]
    run["rows"] = produced["X"].shape[0]
    run["window_trees"] = window
    run["failed"] = (max(run["started"] - len(window), 0)
                     + sum(1 for t in window if t["num_leaves"] <= 1))
    numbers, info = check_rank.audit(
        produced, int(ctx["traffic"]["check_trees"]),
        int(ctx["config"].get("eval_at", 10)), control=ctx.get("control"))
    numbers["trees_missing"] = run["failed"]
    correct, checks = check_rank.decide(numbers)
    return correct, checks, info
