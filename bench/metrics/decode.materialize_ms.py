"""Host ms per sort from the library call returning to the caller holding
``out.keys`` and ``out.values`` on the host: the decode's wait and the D2H
copy (the harness's own span, host clock)."""


def read(run):
    if not run.sorts:
        return None
    return sum(s.t_done - s.t_returned for s in run.sorts) / len(run.sorts) * 1e3
