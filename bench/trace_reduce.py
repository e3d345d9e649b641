"""From a profiler trace (``.xplane.pb``) to device times by class, idle
gaps and the breakdown.

On a TPU every event on a device plane's ``XLA Ops`` line is named by its
whole HLO instruction, ``%name = <result shape> <opcode>(<operands>), ...``,
so the class of an op and the bytes of its operands and results are read
from the name itself:

  pallas      ``custom-call`` with ``custom_call_target="tpu_custom_call"``
  sort        XLA ``sort``
  collective  ``all-to-all``, ``all-gather``, ``all-reduce``,
              ``collective-permute``, ``reduce-scatter`` (and -start/-done)
  other       everything else

Ops nest (a ``while`` holds its body's ops), so each instant is charged to
the innermost op that covers it: a class's time is the self time of its
ops. Busy time is the union of the ``XLA Ops`` intervals; the window runs
from the start of the first harness span (``bench.*`` on the host plane,
on the same clock) to the end of the last. Asynchronous collectives
(``Async XLA Ops``) count as collective time; the exposed part is where
no non-collective op is innermost.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

CLASSES = ("pallas", "sort", "collective", "other")
_COLLECTIVES = {"all-to-all", "all-gather", "all-reduce", "collective-permute",
                "reduce-scatter"}
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
DEVICE_LINES = ("XLA Ops",)
ASYNC_LINES = ("Async XLA Ops",)
SPAN_PREFIX = "bench."


def _close(text: str, i: int) -> int:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] in "({":
            depth += 1
        elif text[j] in ")}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def parse_op(text: str):
    """``(name, opcode, result_part, operand_part)`` of an HLO instruction."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), "", "", ""
    end = _close(rest, 0) if rest.startswith("(") else rest.find(" ")
    if end < 0:
        return name.lstrip("%"), "", rest, ""
    result = rest[:end]
    tail = rest[end:].lstrip()
    paren = tail.find("(")
    opcode = tail[:paren] if paren >= 0 else tail
    operands = tail[paren:_close(tail, paren)] if paren >= 0 else ""
    return name.lstrip("%"), opcode, result, operands


def op_class(text: str) -> str:
    _, opcode, _, _ = parse_op(text)
    if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in text:
        return "pallas"
    if opcode == "sort":
        return "sort"
    base = opcode.removesuffix("-start").removesuffix("-done")
    if base in _COLLECTIVES:
        return "collective"
    return "other"


def shape_bytes(part: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(part):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def op_bytes(text: str) -> int:
    """Bytes of an op's operands and results, from the shapes in its HLO."""
    _, _, result, operands = parse_op(text)
    return shape_bytes(result) + shape_bytes(operands)


# ------------------------------------------------------------- intervals

def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the (disjoint, sorted) intervals ``a`` outside ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


@dataclasses.dataclass
class Op:
    start: float
    end: float
    text: str
    cls: str
    self_ns: float = 0.0


def attribute(ops) -> list:
    """Charge each instant to the innermost op covering it; returns the
    intervals in which a non-collective op was innermost."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    stack, compute = [], []
    t = None

    def run_to(x):
        nonlocal t
        while stack:
            top = stack[-1]
            stop = min(top.end, x)
            if stop > t:
                top.self_ns += stop - t
                if top.cls != "collective":
                    compute.append((t, stop))
                t = stop
            if top.end <= x:
                stack.pop()
            else:
                return
        t = max(t, x)

    for op in ops:
        if t is None:
            t = op.start
        run_to(op.start)
        stack.append(op)
    if t is not None:
        run_to(float("inf"))
    return union(compute)


# ------------------------------------------------------------- reduction

@dataclasses.dataclass
class Device:
    busy_ns: float
    class_ns: dict
    pallas_bytes: int
    collective_ns: float
    exposed_ns: float
    op_ns: dict


@dataclasses.dataclass
class Reduced:
    window_ns: float
    n_sorts: int
    devices: list
    gaps: list          # (seconds, name) of the longest idle gaps, any device

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def busy_s(self) -> float:
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def class_s(self, cls: str) -> float:
        """Seconds of ``cls`` self time, averaged over the devices."""
        return sum(d.class_ns[cls] for d in self.devices) / len(self.devices) / 1e9

    def per_sort_ms(self, seconds: float) -> float:
        return seconds / self.n_sorts * 1e3

    def breakdown(self, top: int = 10) -> dict:
        ops = defaultdict(float)
        for d in self.devices:
            for name, ns in d.op_ns.items():
                ops[name] += ns / len(self.devices) / 1e9
        best = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in best],
                "idle_gaps": [[n, s] for s, n in self.gaps[:top]]}


def _host_spans(planes):
    spans, events = [], []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                (spans if ev.name.startswith(SPAN_PREFIX) else events).append(iv)
    return sorted(spans), sorted(events)


def _gap_name(gap, spans, events) -> str:
    """The harness span open over most of a gap, and the innermost host
    event at its middle."""
    s, e = gap
    best, best_cover = "no harness span", 0.0
    for a, b, name in spans:
        cover = min(b, e) - max(a, s)
        if cover > best_cover:
            best, best_cover = name.removeprefix(SPAN_PREFIX), cover
    mid = (s + e) / 2
    inner = [(a, name) for a, b, name in events if a <= mid < b]
    if inner:
        best += "/" + max(inner)[1]
    return best


def reduce_file(path: str, device_ids) -> Reduced:
    import jax

    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    spans, events = _host_spans(planes)
    if not spans:
        raise ValueError("the trace holds no harness span (bench.*)")
    w0, w1 = spans[0][0], max(b for _, b, _ in spans)
    n_sorts = sum(1 for _, _, n in spans if n == SPAN_PREFIX + "call")
    wanted = {f"/device:TPU:{i}" for i in device_ids}
    devices, all_gaps = [], []
    for plane in planes:
        if plane.name not in wanted:
            continue
        ops, async_coll = [], []
        for line in plane.lines:
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                if line.name in DEVICE_LINES:
                    ops.append(Op(s, e, ev.name, op_class(ev.name)))
                elif line.name in ASYNC_LINES and op_class(ev.name) == "collective":
                    async_coll.append((s, e))
        compute = attribute(ops)
        busy = union((o.start, o.end) for o in ops)
        class_ns = dict.fromkeys(CLASSES, 0.0)
        op_ns = defaultdict(float)
        pallas_bytes = 0
        for o in ops:
            class_ns[o.cls] += o.self_ns
            name, _, _, _ = parse_op(o.text)
            op_ns[f"{o.cls}:{name}"] += o.self_ns
            if o.cls == "pallas":
                pallas_bytes += op_bytes(o.text)
        coll = union([(o.start, o.end) for o in ops if o.cls == "collective"]
                     + async_coll)
        gaps = subtract([[w0, w1]], busy)
        devices.append(Device(length(busy), class_ns, pallas_bytes, length(coll),
                              length(subtract(coll, compute)), dict(op_ns)))
        all_gaps += [((e - s) / 1e9, _gap_name((s, e), spans, events)) for s, e in gaps]
    if len(devices) != len(wanted):
        found = sorted(p.name for p in planes if p.name.startswith("/device:"))
        raise ValueError(f"trace has device planes {found}, wanted {sorted(wanted)}")
    return Reduced(w1 - w0, n_sorts, devices, sorted(all_gaps, reverse=True))


def reduce_dir(d: str, device_ids) -> Reduced:
    found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {d}, found {found}")
    return reduce_file(found[0], device_ids)
