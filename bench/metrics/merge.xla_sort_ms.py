"""Device ms per sort in XLA ``sort`` ops (self time, mean over chips)."""


def read(run):
    t = run.trace.class_s("sort")
    return run.trace.per_sort_ms(t) if t > 0 else None
