"""Input bytes (keys + payload) of every sort completed in the window,
over the window's wall time from the first call to the end of the last
sort's host copy (host clock)."""


def read(run):
    if not run.sorts or run.window_s <= 0:
        return None
    return len(run.sorts) * run.bytes_per_sort / run.window_s / 1e9
