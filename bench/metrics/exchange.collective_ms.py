"""Device ms per sort in collectives (all-to-all, all-gather, all-reduce,
collective-permute, reduce-scatter), the largest over the chips."""


def read(run):
    t = max(d.collective_ns for d in run.trace.devices)
    return t / run.trace.n_sorts / 1e6 if t > 0 else None
