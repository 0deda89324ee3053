"""Device trace: durations of the partition kernels' events, per traced iteration."""

from benchmark.harness import work


def read(run):
    s = work.kernel_seconds_per_iter(run, "partition")
    return None if s is None else 1e3 * s
