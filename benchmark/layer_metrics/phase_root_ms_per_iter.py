"""Device trace: busy time under the program's scope ``root`` (the root histogram and its split scan, kernels included),
per traced iteration."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms_per_iter(run, "root")
