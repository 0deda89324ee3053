"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device, read by the
harness after the window and before the reference runs."""


def read(run):
    return run["peak_bytes"] / 2**20
