"""Median of ``queued`` over the traced ``lgbt.dispatch`` spans: how many
iterations the host runs ahead of the device; 0 means the device waits for
the host."""

from benchmark.harness import program_trace


def read(run):
    pt = program_trace.of(run)
    return None if pt is None else pt["queued_at_dispatch"]
