"""Device trace: busy time under the program's scopes ``gradients`` and
``score_update`` (the two passes over score and label around the tree), per
traced iteration."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms_per_iter(run, "gradients", "score_update")
