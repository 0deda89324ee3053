"""Device trace: time of the collective operations (all-reduce,
reduce-scatter, all-gather, all-to-all, collective-permute; both events of an
asynchronous pair) on the device's operation line, per traced iteration, mean
over the device planes."""

from benchmark.harness import mesh_trace


def read(run):
    return mesh_trace.ms_per_iter(run, "collective_seconds")
