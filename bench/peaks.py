"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

A kind that is not in the table is an error, never a default: a roofline
share against a guessed peak is not a measurement.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS: dict[str, Peak] = {
    # JAX names a TPU v5e chip "TPU v5 lite"
    "TPU v5 lite": Peak(
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 16 GB of HBM2 at 819 GB/s per chip',
    ),
}


class UnknownDevice(KeyError):
    pass


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peak for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
