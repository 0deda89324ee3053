"""Device trace: durations of the scan kernels' events, per traced iteration."""

from benchmark.harness import work


def read(run):
    s = work.kernel_seconds_per_iter(run, "scan")
    return None if s is None else 1e3 * s
