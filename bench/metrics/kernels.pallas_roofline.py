"""Pallas custom calls' share of the HBM roofline, in %: the bytes of
their operands and results (from the shapes in each op's HLO) over their
device time times the chip's peak HBM bandwidth. Bandwidth is the bound
that applies: the bitonic network does a few integer compares per byte."""


def read(run):
    devs = [d for d in run.trace.devices if d.class_ns["pallas"] > 0]
    if not devs:
        return None
    byts = sum(d.pallas_bytes for d in devs)
    secs = sum(d.class_ns["pallas"] for d in devs) / 1e9
    return 100.0 * byts / (secs * run.peak.hbm_bytes_per_s)
