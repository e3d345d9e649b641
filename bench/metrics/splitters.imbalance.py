"""Largest max/mean of ``SortOutput.counts`` (per-shard sizes after the
splitters) over the traced sorts."""
import numpy as np


def read(run):
    worst = None
    for s in run.sorts:
        if s.counts is None or np.size(s.counts) == 0 or np.mean(s.counts) == 0:
            continue
        r = float(np.max(s.counts) / np.mean(s.counts))
        worst = r if worst is None else max(worst, r)
    return worst
