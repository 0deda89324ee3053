"""The ranking generator: rows grouped in queries, graded labels.

A generator is a data file under ``benchmark/generators/`` (here
``istella_like.json``); the same seed gives the same rows.  The query
lengths come from one fixed stream (``query_lengths.layout_seed``), the same
for every seed, so the objective's pair layout and work are the same in
every run; features and labels come from ``--seed``.
"""

import numpy as np

from benchmark.harness import data
from benchmark.harness.paths import BENCH_DIR, load_json

LAYOUT_STREAM = 0


def spec_of(generator):
    return load_json(BENCH_DIR, "generators", generator + ".json")


def _fit(sizes, total, lo, hi, rng):
    """Integer sizes within [lo, hi] that sum to ``total``: scaled to it,
    then the remainder spread one document at a time over queries drawn
    from ``rng``."""
    sizes = np.asarray(sizes, np.float64)
    for _ in range(8):
        sizes = np.clip(np.rint(sizes * total / sizes.sum()), lo, hi)
    sizes = sizes.astype(np.int64)
    while sizes.sum() != total:
        step = 1 if sizes.sum() < total else -1
        room = np.flatnonzero((sizes + step >= lo) & (sizes + step <= hi))
        if not room.size:
            raise ValueError(f"{sizes.size} queries of {lo} to {hi} "
                             f"documents cannot hold {total}")
        need = min(abs(int(total - sizes.sum())), room.size)
        pick = rng.choice(room, size=need, replace=False)
        sizes[pick] += step
    return sizes


def query_lengths(spec, queries, rows):
    """``queries`` lengths summing to exactly ``rows``, drawn from the fixed
    layout stream (the same for every seed)."""
    q = spec["query_lengths"]
    if q["law"] != "lognormal":
        raise ValueError(f"unknown query-length law {q['law']!r}")
    rng = data.rng_for(q["layout_seed"], LAYOUT_STREAM)
    sigma = float(q["sigma"])
    mu = np.log(rows / queries) - sigma * sigma / 2
    raw = rng.lognormal(mu, sigma, queries)
    return _fit(raw, rows, int(q["min"]), int(q["max"]), rng)


def holdout_lengths(spec, docs, mean, seed):
    """Whole queries of about ``docs`` documents, from a stream of their
    own, under the same law at the mean length ``mean``."""
    q = spec["query_lengths"]
    rng = data.rng_for(seed, data.HOLDOUT_STREAM)
    sigma = float(q["sigma"])
    raw = rng.lognormal(np.log(mean) - sigma * sigma / 2, sigma,
                        max(1, int(2 * docs / mean)))
    sizes = np.clip(np.rint(raw), int(q["min"]), int(q["max"])).astype(np.int64)
    keep = max(1, int(np.searchsorted(np.cumsum(sizes), docs, "right")))
    return sizes[:keep]


def _labels(spec, latent, sizes):
    """Graded labels from where each document's latent relevance ranks in
    its query."""
    n = latent.size
    qid = np.repeat(np.arange(sizes.size), sizes)
    order = np.lexsort((-latent, qid))          # by query, best first
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n) - np.repeat(starts, sizes)
    frac = (rank + 0.5) / sizes[qid]
    shares = np.asarray(spec["label_shares"], np.float64)
    top = len(shares) - 1
    # cumulative share from the best label down: label 4 below 1 %, ...
    cuts = np.cumsum(shares[::-1])[:-1]
    return (top - np.searchsorted(cuts, frac, side="right")).astype(np.float32)


def make_queries(generator, seed, stream, sizes, features):
    """Rows of the queries ``sizes`` (lengths, in order): ``features`` raw
    values a row and graded labels."""
    spec = spec_of(generator)
    if spec["feature_dist"] != "standard_normal":
        raise ValueError(f"unknown feature_dist {spec['feature_dist']!r}")
    dtype = np.dtype(spec["dtype"])
    rng = data.rng_for(seed, stream)
    rows = int(np.sum(sizes))
    X = rng.standard_normal((rows, features), dtype=dtype)
    latent = data._logit(spec, X, rng.standard_normal(rows, dtype=dtype))
    y = _labels(spec, latent, np.asarray(sizes, np.int64))
    X += dtype.type(spec.get("feature_shift", 0.0))
    return X, y
