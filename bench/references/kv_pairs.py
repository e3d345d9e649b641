"""Plain reference of a key/value sort: numpy on the host, nothing of the
program.

The guarantees compared (configuration files state them): every key and
payload comes back once, keys ascending, each payload with its own key;
the order of payloads among equal keys is free (the kv sort is not
stable). So the reference sorts the (key, payload) pairs of the input and
of the output, each packed into one uint64 (key high, payload low), and
compares them position by position: equal lists mean the same multiset
of pairs. To use the host's cores both lists are cut into key ranges at
quantiles of the output keys and the ranges are sorted on threads
(numpy's sort releases the interpreter lock).

Numbers returned, each exact (limit 0):
  length_diff       |len(out keys) - n| + |len(out payload) - n|
  keys_out_of_order positions i with out_keys[i+1] < out_keys[i]
  pairs_mismatch    positions at which the sorted pair lists differ, plus
                    the size differences of the key ranges
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def pack(keys: np.ndarray, values: np.ndarray) -> np.ndarray:
    """(key, payload) as one uint64 that orders like the pair (signed
    32-bit keys: the sign bit is flipped)."""
    k = keys.astype(np.int64) - np.iinfo(np.int32).min
    v = values.astype(np.int64) - np.iinfo(np.int32).min
    return (k.astype(np.uint64) << np.uint64(32)) | v.astype(np.uint64)


def _chunks(n: int, parts: int):
    edges = np.linspace(0, n, parts + 1).astype(np.int64)
    return list(zip(edges[:-1], edges[1:]))


def compare(keys_in, values_in, keys_out, values_out, workers: int | None = None) -> dict:
    keys_in, values_in = np.asarray(keys_in).ravel(), np.asarray(values_in).ravel()
    keys_out, values_out = np.asarray(keys_out), np.asarray(values_out)
    n = keys_in.size
    length_diff = abs(keys_out.size - n) + abs(values_out.size - n)
    if length_diff or keys_out.ndim != 1 or values_out.ndim != 1:
        return {"length_diff": max(length_diff, 1), "keys_out_of_order": n,
                "pairs_mismatch": n}
    workers = workers or min(32, os.cpu_count() or 1)
    pool = ThreadPoolExecutor(workers)
    with pool:
        keys_out_of_order = sum(pool.map(
            lambda c: int(np.count_nonzero(keys_out[c[0] + 1:c[1] + 1] < keys_out[c[0]:c[1]])),
            _chunks(max(n - 1, 0), workers)))

        # key ranges (lo, hi]: bounds at quantiles of the output keys
        bounds = np.unique(keys_out[np.linspace(0, n - 1, workers + 1).astype(np.int64)[1:-1]])
        out_cuts = np.concatenate([[0], np.searchsorted(keys_out, bounds, "right"), [n]])

        def partition(c):
            k, v = keys_in[c[0]:c[1]], values_in[c[0]:c[1]]
            b = np.searchsorted(bounds, k, "left").astype(np.int32)
            order = np.argsort(b, kind="stable")
            cuts = np.searchsorted(b[order], np.arange(bounds.size + 2), "left")
            return k[order], v[order], cuts

        parts = list(pool.map(partition, _chunks(n, workers)))

        def check_range(r):
            k = np.concatenate([p[0][p[2][r]:p[2][r + 1]] for p in parts])
            v = np.concatenate([p[1][p[2][r]:p[2][r + 1]] for p in parts])
            lo, hi = out_cuts[r], max(out_cuts[r], out_cuts[r + 1])
            ref = np.sort(pack(k, v))
            got = np.sort(pack(keys_out[lo:hi], values_out[lo:hi]))
            m = min(ref.size, got.size)
            return int(np.count_nonzero(ref[:m] != got[:m])) + abs(ref.size - got.size)

        pairs_mismatch = sum(pool.map(check_range, range(bounds.size + 1)))
    return {"length_diff": 0, "keys_out_of_order": keys_out_of_order,
            "pairs_mismatch": pairs_mismatch}
