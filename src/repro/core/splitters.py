"""Sampling, splitter selection and the paper's *investigator* (§IV, Fig. 3).

This module is the heart of the reproduction: the buffer-sized regular
sampling rule (step 2), replicated splitter selection (step 3 — the TPU
replacement for the master, see DESIGN.md §2), and the investigator that
equalizes tied splitter ranges (step 4) — the mechanism that keeps load
balance under heavy key duplication (paper Table II).

Everything here is pure jnp over *local* (per-device) data, shared verbatim
between the virtual-processor simulator (``sim.py``) and the shard_map
distributed implementation (``sample_sort.py``).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SortConfig:
    """Tuning knobs of the PGX.D sort, with the paper's defaults.

    buffer_bytes: the PGX.D read-buffer size that bounds the *total* sample
      volume arriving at splitter selection (paper: 64 KB — "each processor
      has to send only 64/p KByte"). The Fig. 9-11 ablation sweeps this.
    capacity_factor: slack over the perfectly-balanced shard size for the
      static all_to_all buckets (TPU adaptation of the ragged exchange).
      The investigator keeps realized imbalance ~1e-3, so 1.25 is generous;
      overflow is detected and reported, never silent.
    tile: VMEM tile width for the local bitonic sort phase.
    use_pallas: False routes local sorting through jax.lax.sort (baseline).
    samples_per_shard: explicit override of the buffer rule (ablations).
    """

    buffer_bytes: int = 65536
    capacity_factor: float = 1.25
    tile: int = 1024
    use_pallas: bool = True
    samples_per_shard: int | None = None

    def num_samples(self, p: int, n_local: int, key_bytes: int = 4) -> int:
        """Paper rule: 64KB / p per processor, clamped to the shard size."""
        if self.samples_per_shard is not None:
            s = self.samples_per_shard
        else:
            s = max(1, self.buffer_bytes // (p * key_bytes))
        return max(1, min(s, n_local))

    def capacity(self, p: int, n_local: int) -> int:
        """Static per-destination bucket size for the fixed-shape exchange.

        ideal * capacity_factor + an additive floor: splitter noise is
        O(sqrt) in the sample count, so for small shards the *relative*
        slack must grow — the +32 floor keeps tiny test/bucketing rounds
        overflow-free without changing production asymptotics."""
        ideal = (n_local + p - 1) // p
        cap = int(ideal * self.capacity_factor) + 32
        return min(cap, n_local)


def regular_sample(xs_sorted: jnp.ndarray, s: int) -> jnp.ndarray:
    """Regularly-spaced samples from a locally sorted shard (paper step 2)."""
    n = xs_sorted.shape[0]
    # centered strides — same estimator as PSRS regular sampling. Static
    # int64 host math: (2s-1)*n passes 2^31 once n*s does (2^23 keys per
    # shard at the default 2048 samples)
    idx = ((2 * np.arange(s, dtype=np.int64) + 1) * n) // (2 * s)
    return xs_sorted[idx.astype(np.int32)]


def select_splitters(all_samples: jnp.ndarray, p: int) -> jnp.ndarray:
    """Replicated splitter selection (paper step 3, master-free on TPU).

    ``all_samples`` is the all-gathered (p*s,) sample set — identical on
    every device, so every device deterministically computes the same p-1
    splitters and no broadcast is needed.
    """
    srt = jnp.sort(all_samples)
    m = srt.shape[0]
    idx = (jnp.arange(1, p, dtype=jnp.int32) * m) // p
    return srt[idx]


def investigator_bounds(xs_sorted: jnp.ndarray, splitters: jnp.ndarray) -> jnp.ndarray:
    """Destination boundaries with the paper's investigator (step 4, Fig. 3).

    Plain sample sort does one binary search per splitter; with duplicated
    splitters (heavy key repetition) every tied element lands on a single
    destination (Fig. 3b). The investigator detects the tied range
    [L, R) = [searchsorted(left), searchsorted(right)) of each splitter and
    divides it among the duplicated splitters so that every destination gets
    an **equal share** (Fig. 3c / Table II).

    Implementation: within a tied run any assignment preserves sortedness,
    so boundary j is free to sit anywhere in [L_j, R_j]. We pin it to the
    destination's *ideal local rank* j*n/p, clipped into the tied range:

        bound[j] = clip(j*n/p, L_j, R_j)

    This reduces to plain binary search for unique splitters on distinct
    data (L = R), to the paper's equal division when a tied run spans
    several splitters (consecutive ideal ranks are n/p apart -> equal
    slices), and — beyond the literal Fig. 3c rule — stays balanced when a
    tied run only partially overlaps a destination's ideal range. It
    reproduces the exact-equal shard sizes of paper Table II.

    Monotone by construction (L, R and the ideal ranks are all
    non-decreasing in j). Exact int32 arithmetic.

    Returns bounds of shape (p+1,): bounds[j]..bounds[j+1] is the local
    slice destined to processor j.
    """
    n = xs_sorted.shape[0]
    m = splitters.shape[0]  # p - 1
    p = m + 1
    left = jnp.searchsorted(xs_sorted, splitters, side="left").astype(jnp.int32)
    right = jnp.searchsorted(xs_sorted, splitters, side="right").astype(jnp.int32)

    # ideal = j * n / p for j = 1..p-1, exact int32 (no overflow):
    j = jnp.arange(1, p, dtype=jnp.int32)
    ideal = (n // p) * j + ((n % p) * j) // p

    bound = jnp.clip(ideal, left, right)
    zero = jnp.zeros((1,), jnp.int32)
    full = jnp.full((1,), n, jnp.int32)
    return jnp.concatenate([zero, bound, full])


def naive_bounds(xs_sorted: jnp.ndarray, splitters: jnp.ndarray) -> jnp.ndarray:
    """Plain sample-sort boundaries (no investigator) — the paper's Fig. 3b
    failure mode, kept as the ablation baseline for Table II / benchmarks."""
    n = xs_sorted.shape[0]
    bound = jnp.searchsorted(xs_sorted, splitters, side="left").astype(jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    full = jnp.full((1,), n, jnp.int32)
    return jnp.concatenate([zero, bound, full])
