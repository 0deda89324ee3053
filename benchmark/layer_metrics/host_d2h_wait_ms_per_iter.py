"""The program's span ``lgbt.d2h_wait`` (the host blocks on a tree's
records), per traced iteration, on the profiler's clock."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.span_ms_per_iter(run, "d2h_wait")
