"""Device trace: ``sort`` operations per traced iteration, all phases."""

from benchmark.harness import program_trace


def read(run):
    pt = program_trace.of(run)
    if pt is None or not pt["has_scopes"]:
        return None
    return sum(pt["sort_count"].values()) / pt["iterations"]
