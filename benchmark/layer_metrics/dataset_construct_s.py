"""Harness clock around ``Dataset.construct()``."""


def read(run):
    return run["clocks"]["dataset_construct_s"]
