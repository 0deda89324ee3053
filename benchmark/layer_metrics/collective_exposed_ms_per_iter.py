"""Device trace: of ``collective_ms_per_iter``, the time in which the device
runs no other operation: what the exchange really costs the iteration
(``tpu_wave_hist_buffers`` = 2 exists to hide it)."""

from benchmark.harness import mesh_trace


def read(run):
    return mesh_trace.ms_per_iter(run, "exposed_seconds")
