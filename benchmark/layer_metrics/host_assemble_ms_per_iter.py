"""The program's span ``lgbt.assemble_tree`` (host tree from the records,
and its shrinkage), self time per traced iteration."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.span_ms_per_iter(run, "assemble_tree",
                                          self_time=True)
