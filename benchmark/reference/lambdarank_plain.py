"""Plain reference for LightGBM's lambdarank objective and NDCG@k.

Straightforward numpy in float64, written from the published definitions
(LightGBM ``src/objective/rank_objective.hpp``, ``GetGradientsForOneQuery``;
``src/metric/dcg_calculator.cpp``): each query's documents are ranked by a
stable sort on descending score; every pair of documents with different
labels (high, low) adds

    delta  = (gain[high] - gain[low]) * |1/log2(2+pos_high) - 1/log2(2+pos_low)|
             / maxDCG@k,  divided by (0.01 + |s_high - s_low|) unless the
             query's scores are all equal
    lambda = -delta * 2 / (1 + exp(2 * sigma * (s_high - s_low)))
    hess   = 2 * delta * p * (2 - p),  p = 2 / (1 + exp(2 * sigma * (s_high - s_low)))

to the high document and (-lambda, hess) to the low one, with the gain
2^label - 1.  It imports nothing of the program.  Queries are taken one at a
time, grouped by length class (the power of two at or above the length) so
that a thread pool gets work of like size; a query is walked in blocks of
the documents that can be the high one of a pair (a label above the
query's lowest).
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.reference.gbdt_plain import THREADS

BLOCK = 512          # high documents a block of one query's pair walk


def default_label_gain(top=31):
    return 2.0 ** np.arange(top + 1) - 1.0


def _discounts(n):
    return 1.0 / np.log2(np.arange(n) + 2.0)


def max_dcg(labels, k, gain):
    """The best DCG@k a query's labels allow."""
    top = np.sort(labels)[::-1][:k].astype(np.int64)
    return float(np.sum(gain[top] * _discounts(top.size)))


def query_grads(score, label, k, sigma=1.0, gain=None):
    """(lambdas, hessians) of one query, float64."""
    gain = default_label_gain() if gain is None else gain
    n = score.size
    lam, hes = np.zeros(n), np.zeros(n)
    best = max_dcg(label, k, gain)
    if n < 2 or best <= 0:
        return lam, hes
    inv = 1.0 / best
    order = np.argsort(-score, kind="stable")
    pos = np.empty(n, np.int64)
    pos[order] = np.arange(n)
    disc = _discounts(n)[pos]
    lab = label.astype(np.int64)
    g = gain[lab]
    all_equal = score.max() == score.min()
    # a document of the query's lowest label is the high one of no pair
    highs = np.flatnonzero(lab > lab.min())
    for lo in range(0, highs.size, BLOCK):
        hi = highs[lo:lo + BLOCK]
        is_pair = lab[hi, None] > lab[None, :]            # (high, low)
        ds = score[hi, None] - score[None, :]
        delta = (g[hi, None] - g[None, :]) * np.abs(disc[hi, None]
                                                   - disc[None, :]) * inv
        if not all_equal:
            delta = delta / (0.01 + np.abs(ds))
        p = 2.0 / (1.0 + np.exp(2.0 * sigma * ds))
        p_lambda = np.where(is_pair, -delta * p, 0.0)
        p_hess = np.where(is_pair, 2.0 * delta * p * (2.0 - p), 0.0)
        lam[hi] += p_lambda.sum(axis=1)
        hes[hi] += p_hess.sum(axis=1)
        lam -= p_lambda.sum(axis=0)
        hes += p_hess.sum(axis=0)
    return lam, hes


def _classes(bounds):
    sizes = np.diff(bounds)
    cls = np.ceil(np.log2(np.maximum(sizes, 1)))
    return [np.flatnonzero(cls == c) for c in np.unique(cls)[::-1]]


def grad_hess(score, label, bounds, k, sigma=1.0, gain=None):
    """Lambdas and hessians of every document; ``bounds`` are the query
    boundaries (``bounds[q]:bounds[q+1]`` is query ``q``)."""
    score = np.asarray(score, np.float64)
    lam, hes = np.zeros(score.size), np.zeros(score.size)

    def one(q):
        a, b = bounds[q], bounds[q + 1]
        lam[a:b], hes[a:b] = query_grads(score[a:b], label[a:b], k, sigma,
                                         gain)

    with ThreadPoolExecutor(THREADS) as pool:
        for queries in _classes(bounds):          # the longest class first
            list(pool.map(one, queries))
    return lam, hes


def ndcg_at(score, label, bounds, k, gain=None):
    """Mean NDCG@k over the queries; a query whose labels are all zero
    counts 1, as LightGBM's metric counts it."""
    gain = default_label_gain() if gain is None else gain
    out = []
    for q in range(len(bounds) - 1):
        s, y = score[bounds[q]:bounds[q + 1]], label[bounds[q]:bounds[q + 1]]
        best = max_dcg(y, k, gain)
        if best <= 0:
            out.append(1.0)
            continue
        top = np.argsort(-s, kind="stable")[:k]
        dcg = float(np.sum(gain[y[top].astype(np.int64)]
                           * _discounts(top.size)))
        out.append(dcg / best)
    return float(np.mean(out))
