"""The whole sort's share of the HBM roofline, in %: one read and one
write of each chip's input bytes per sort, over the chip's busy time per
sort times its peak HBM bandwidth. It counts the same work whatever
implements the sort."""


def read(run):
    tr = run.trace
    if tr.busy_s <= 0 or tr.n_sorts == 0:
        return None
    need = 2.0 * run.bytes_per_sort / run.chips
    return 100.0 * need / (tr.busy_s / tr.n_sorts * run.peak.hbm_bytes_per_s)
