"""The device stamp and the table of peaks (``benchmark/peaks.json``)."""

from benchmark.harness.paths import BENCH_DIR, load_json


class NoChip(RuntimeError):
    pass


def stamp():
    """Platform, kind and count of the devices JAX found."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips):
    """The stamp, or ``NoChip``: a measurement path that finds no chip fails,
    it never falls back."""
    found = stamp()
    if found["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU: {found}")
    if found["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {found}")
    return found


def peaks(device_kind):
    table = load_json(BENCH_DIR, "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json: add a sourced entry")
    return table[device_kind]
