"""Share of the device's idle time in the traced window that falls under no
``lgbt.*`` span below ``lgbt.iteration``: idle time the program's spans do
not explain."""

from benchmark.harness import program_trace


def read(run):
    pt = program_trace.of(run)
    if pt is None or not pt["has_spans"]:
        return None
    idle = sum(pt["gap_seconds"].values())
    if idle <= 0:
        return 0.0
    whole = program_trace.names()["span_prefix"] + "iteration"
    loose = sum(v for k, v in pt["gap_seconds"].items()
                if k in (whole, "no_program_span"))
    return 100.0 * loose / idle
