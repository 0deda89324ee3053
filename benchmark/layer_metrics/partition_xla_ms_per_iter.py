"""Device trace: busy time of every ``partition`` scope (decide pass, keys,
re-compaction and materialisation sorts) outside the Pallas kernels, per
traced iteration: what the Pallas partition would replace."""

from benchmark.harness import program_trace


def read(run):
    pt = program_trace.of(run)
    if pt is None or not pt["has_scopes"]:
        return None
    return 1e3 * pt["partition_xla_seconds"] / pt["iterations"]
