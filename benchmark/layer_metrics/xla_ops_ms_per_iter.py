"""Device trace: busy time outside the Pallas kernels (sorts, replay,
corrections, bookkeeping), per traced iteration."""

from benchmark.harness import work


def read(run):
    t = work.traced(run)
    if t is None:
        return None
    kernels = work.kernel_seconds_per_iter(run) or 0.0
    return 1e3 * (t["busy_s"] / t["iterations"] - kernels)
