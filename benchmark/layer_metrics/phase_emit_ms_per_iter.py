"""Device trace: busy time under the program's scope ``emit`` (records, speculative-leaf map and the sort back to row order),
per traced iteration."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms_per_iter(run, "emit")
