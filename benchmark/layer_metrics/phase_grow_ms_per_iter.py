"""Device trace: busy time under the program's scope ``grow`` (the growth waves: selection, partition, member histograms, child scans),
per traced iteration."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms_per_iter(run, "grow")
