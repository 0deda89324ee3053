"""(the busiest device's busy time - the least busy one's) / the mean, over
the traced window: uneven leaves across the shards make the other chips wait
in the next collective."""

from benchmark.harness import mesh_trace


def read(run):
    mt = mesh_trace.of(run)
    if mt is None or len(mt["busy_s_by_device"]) < 2:
        return None
    busy = mt["busy_s_by_device"]
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) - min(busy)) / mean if mean > 0 else None
