"""Device ms per sort of collective time during which no other op runs on
that chip, the largest over the chips: what the exchange does not hide."""


def read(run):
    devs = run.trace.devices
    if max(d.collective_ns for d in devs) <= 0:
        return None
    return max(d.exposed_ns for d in devs) / run.trace.n_sorts / 1e6
