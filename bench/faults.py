"""The control and the planted faults that the comparison must catch.

Each entry wraps the cell's library call and returns a call of the same
shape, whose answer (``.keys``, ``.values``, ``.meta.retries``,
``.counts``) is wrong in one known way:

  control         the plain sort put in the program's place, on keys
                  narrowed to the next precision below the configuration's
                  (int32 -> int16: only the top 16 bits are compared)
  unchanged       the input handed back as it came: a sort that did nothing
  half_dropped    the program's answer with half of the elements left out
  no_exchange     each chip's share sorted alone and the shares concatenated:
                  the exchange between chips left out
  altered_payload the program's answer with one payload changed
  altered_key     the program's answer with one key changed

``bench/seeds.py`` runs them on the chip at a cell's own size; the tests
under ``bench/tests`` run them at a small size on the CPU.
"""
from __future__ import annotations

import types

import numpy as np


class Answer:
    def __init__(self, keys, values, retries: int = 0, counts=None):
        self.keys, self.values = keys, values
        self.meta = types.SimpleNamespace(retries=retries)
        self.counts = counts


def _inputs(call, arrays):
    names = call.arg_names
    return np.asarray(arrays[names[0]]), np.asarray(arrays[names[1]])


def _program(call, arrays):
    out = call(arrays)
    return (np.array(out.keys), np.array(out.values), int(out.meta.retries),
            out.counts)


def control(call):
    def run(arrays):
        k, v = _inputs(call, arrays)
        narrow = (k >> 16).astype(np.int16)
        order = np.argsort(narrow, kind="stable")
        return Answer(k[order], v[order])
    return run


def unchanged(call):
    def run(arrays):
        k, v = _inputs(call, arrays)
        return Answer(k.copy(), v.copy())
    return run


def half_dropped(call):
    def run(arrays):
        k, v, r, c = _program(call, arrays)
        keep = k.size // 2
        return Answer(k[:keep], v[:keep], r, c)
    return run


def no_exchange(call, shares: int = 4):
    def run(arrays):
        k, v = _inputs(call, arrays)
        ks, vs = [], []
        for part_k, part_v in zip(np.array_split(k, shares), np.array_split(v, shares)):
            order = np.argsort(part_k, kind="stable")
            ks.append(part_k[order])
            vs.append(part_v[order])
        return Answer(np.concatenate(ks), np.concatenate(vs))
    return run


def altered_payload(call):
    def run(arrays):
        k, v, r, c = _program(call, arrays)
        v[v.size // 3] ^= 1
        return Answer(k, v, r, c)
    return run


def altered_key(call):
    def run(arrays):
        k, v, r, c = _program(call, arrays)
        k[k.size // 3] += 1
        return Answer(k, v, r, c)
    return run


FAULTS = {
    "control": control,
    "unchanged": unchanged,
    "half_dropped": half_dropped,
    "no_exchange": no_exchange,
    "altered_payload": altered_payload,
    "altered_key": altered_key,
}
