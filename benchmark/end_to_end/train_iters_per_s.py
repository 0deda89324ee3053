"""Boosting iterations started in the window, all of them waited for, over
the seconds the window really lasted."""


def read(run):
    return run["started"] / run["window_s"]
