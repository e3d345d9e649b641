"""Capacity-ladder retries (``SortOutput.meta.retries``) summed over the
run's sorts: each is a whole extra sort after an overflowing exchange."""


def read(run):
    return sum(s.retries for s in run.sorts) if run.sorts else None
