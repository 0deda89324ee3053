"""MiB one device hands to the program's exchange sites in a traced
iteration: the payloads of the trace's collective calls, which add up to
what the program's ``CollectiveLedger`` reckons from the shapes it was traced
with (bytes a call of each site, times the calls).  Beside it stands what
the algorithm needs: waves x W x features x bins x 12 B (PERF.md)."""

from benchmark.harness import mesh_trace


def read(run):
    nbytes = mesh_trace.exchange_bytes_per_iter(run)
    return None if nbytes is None else nbytes / 2**20
