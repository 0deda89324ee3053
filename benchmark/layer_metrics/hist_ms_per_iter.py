"""Device trace: durations of the hist kernels' events, per traced iteration."""

from benchmark.harness import work


def read(run):
    s = work.kernel_seconds_per_iter(run, "hist")
    return None if s is None else 1e3 * s
