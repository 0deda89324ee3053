"""Device trace: busy time under the program's scope ``replay`` (the exact greedy replay and its stall corrections),
per traced iteration."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms_per_iter(run, "replay")
