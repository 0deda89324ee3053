"""1 - union of the device's operation intervals over the traced window."""


def read(run):
    t = run.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
