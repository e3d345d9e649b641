"""Set-up seconds: process start to the first timed call (host clock),
compiles, data generation and the warm-up sort included."""


def read(run):
    return run.setup_s
