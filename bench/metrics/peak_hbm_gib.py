"""Highest ``peak_bytes_in_use`` over the cell's devices after the window,
in GiB, as the device's allocator reports it."""


def read(run):
    peak = max(run.peak_bytes or [0])
    return peak / 2 ** 30 if peak else None
