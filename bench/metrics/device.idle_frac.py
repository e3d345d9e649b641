"""1 - (union of device op intervals) / traced window, mean over chips."""


def read(run):
    return run.trace.idle_frac
