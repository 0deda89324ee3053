"""Process start to the start of the window: device, data from the seed,
``Dataset.construct``, warm-up with compilation."""


def read(run):
    return run["setup_s"]
