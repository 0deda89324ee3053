"""Device trace: busy time of the lambdarank objective's ordering and
write-back (scopes ``rank_order``: the scores gathered into the pair layout
and each query's sorts; ``rank_writeback``: the lambdas and hessians gathered
back to row order), per traced iteration."""

from benchmark.harness import rank_trace


def read(run):
    return rank_trace.ms_per_iter(run, "rank_order", "rank_writeback")
