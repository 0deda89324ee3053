"""Device trace: the gaps in which no operation runs, per traced iteration."""


def read(run):
    t = run.get("trace")
    if not t or not t["iterations"]:
        return None
    return 1e3 * (t["window_s"] - t["busy_s"]) / t["iterations"]
