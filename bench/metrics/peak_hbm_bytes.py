"""Peak device memory in use after the window: the most of
``memory_stats()["peak_bytes_in_use"]`` over the cell's chips, read from
the device runtime."""


def read(run):
    return run.peak
