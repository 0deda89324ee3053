"""Harness clock from the ``train`` call to the end of iteration 1, behind a
``block_until_ready``."""


def read(run):
    return run["clocks"]["first_iter_s"]
