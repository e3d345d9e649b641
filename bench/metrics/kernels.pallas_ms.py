"""Device ms per sort in Pallas custom calls (self time, mean over chips)."""


def read(run):
    t = run.trace.class_s("pallas")
    return run.trace.per_sort_ms(t) if t > 0 else None
