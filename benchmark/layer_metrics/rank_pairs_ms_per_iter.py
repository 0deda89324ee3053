"""Device trace: busy time of the lambdarank objective's pair pass (scope
``rank_pairs`` inside ``gradients``: every length class's (qb, Q, Q) passes),
per traced iteration."""

from benchmark.harness import rank_trace


def read(run):
    return rank_trace.ms_per_iter(run, "rank_pairs")
