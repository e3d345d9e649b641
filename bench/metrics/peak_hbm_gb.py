"""Largest ``peak_bytes_in_use`` over the cell's chips at the end of the
window, in GB (the device allocator's own counter)."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e9
