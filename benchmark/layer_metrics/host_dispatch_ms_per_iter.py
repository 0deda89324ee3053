"""The program's span ``lgbt.dispatch`` (the call of the jitted step), self
time per traced iteration, on the profiler's clock."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.span_ms_per_iter(run, "dispatch", self_time=True)
