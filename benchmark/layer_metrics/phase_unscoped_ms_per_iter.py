"""Device trace: busy time under the program's scope ``unscoped`` (operations whose ``op_name`` holds no scope of the program (compiler-inserted copies)),
per traced iteration."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.phase_ms_per_iter(run, "unscoped")
