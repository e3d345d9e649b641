"""From a profiler trace (``.xplane.pb``) to device time per library phase
and the library's own host spans.

The library runs each paper step of its in-core sort programs under a
``jax.named_scope`` (``PHASES``) and wraps each host phase in a
``jax.profiler.TraceAnnotation`` named ``repro.<span>``. On a TPU the
trace keeps an op's name-stack path (``jit(f)/vmap(local_sort)/sort``)
as the ``tf_op`` stat of the op's event metadata. ``jax.profiler.
ProfileData`` does not expose metadata stats, so this module reads the
``XSpace`` protobuf itself, in its wire format, with the standard library
alone.

As in ``bench/trace_reduce.py``: the window runs from the first harness
span (``bench.*``) to the end of the last, device ops are the events of a
device plane's ``XLA Ops`` line, and each instant is charged to the
innermost op that covers it. An op's phase is the innermost ``PHASES``
scope on its path, else ``unscoped``, so the phases' self times add up
to the device's busy time.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

from bench import trace_reduce as tr

PHASES = ("local_sort", "splitter", "exchange", "merge", "decode")
UNSCOPED = "unscoped"
LIBRARY_PREFIX = "repro."
MODULE_LINE = "XLA Modules"


# -------------------------------------------------------- protobuf wire

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return (out - (1 << 64) if out >> 63 else out), i


def _fields(buf):
    """``(field number, value)`` of one message: an int for varint and
    fixed-width fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _message(buf) -> dict:
    """Field number -> list of values."""
    out = defaultdict(list)
    for f, v in _fields(buf):
        out[f].append(v)
    return out


def _text(values) -> str:
    return bytes(values[0]).decode() if values else ""


@dataclasses.dataclass
class Event:
    start_ns: float
    end_ns: float
    name: str
    tf_op: str


def _plane_events(plane: dict):
    """``{line name: [Event]}`` of one XPlane message (field numbers of
    ``tsl/profiler/protobuf/xplane.proto``)."""
    stat_names = {}
    for entry in plane[5]:                       # map<int64, XStatMetadata>
        e = _message(entry)
        stat_names[e[1][0] if e[1] else 0] = _text(_message(e[2][0])[2])
    metas = {}
    for entry in plane[4]:                       # map<int64, XEventMetadata>
        e = _message(entry)
        meta = _message(e[2][0])
        tf_op = ""
        for stat in meta[5]:
            s = _message(stat)
            if stat_names.get(s[1][0] if s[1] else 0) == "tf_op":
                tf_op = (_text(s[5]) if s[5]
                         else stat_names.get(s[7][0], "") if s[7] else "")
        metas[e[1][0] if e[1] else 0] = (_text(meta[2]), tf_op)
    lines = {}
    for raw in plane[3]:
        line = _message(raw)
        t0 = line[3][0] if line[3] else 0
        events = []
        for raw_ev in line[4]:
            ev = _message(raw_ev)
            # whole nanoseconds, as jax.profiler.ProfileData gives them
            start = t0 + (ev[2][0] if ev[2] else 0) // 1000
            name, tf_op = metas.get(ev[1][0] if ev[1] else 0, ("", ""))
            events.append(Event(float(start),
                                float(start + (ev[3][0] if ev[3] else 0) // 1000),
                                name, tf_op))
        lines.setdefault(_text(line[2]), []).extend(events)
    return lines


def read_planes(path: str) -> dict:
    """``{plane name: {line name: [Event]}}`` of an ``.xplane.pb``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for field, raw in _fields(space):
        if field == 1:
            plane = _message(raw)
            planes[_text(plane[2])] = _plane_events(plane)
    return planes


# ------------------------------------------------------------ reduction

def phase_of(tf_op: str) -> str:
    """The innermost ``PHASES`` scope on an op's path, else ``unscoped``.
    A scope entered under ``vmap`` reads ``vmap(<scope>)``; a jitted
    function of the same name reads ``jit(<name>)`` and is no scope."""
    found = UNSCOPED
    for part in tf_op.split("/"):
        part = part.partition(":")[0]
        while part.startswith("vmap(") and part.endswith(")"):
            part = part[5:-1]
        if part in PHASES:
            found = part
    return found


@dataclasses.dataclass
class Scopes:
    window_ns: float
    n_sorts: int
    busy_ns: list       # per device
    phase_ns: list      # per device: {phase or "unscoped": self ns}
    host_ns: dict       # {"repro.<span>": summed ns inside the window}
    gaps: list          # (seconds, "<harness span>/<innermost repro span>")

    def phase_ms(self, phase: str) -> float:
        """Device ms per sort under ``phase``, mean over the devices."""
        ns = sum(d.get(phase, 0.0) for d in self.phase_ns) / len(self.phase_ns)
        return ns / self.n_sorts / 1e6

    def host_ms(self, *names: str) -> float:
        """Host ms per sort in the named library spans (``repro.`` implied)."""
        ns = sum(self.host_ns.get(LIBRARY_PREFIX + n, 0.0) for n in names)
        return ns / self.n_sorts / 1e6


def _inherit_phases(ops, modules) -> None:
    """Give the unscoped ops (``cls``) a phase where the program implies
    one. XLA's own ops (copies, loop control, the loops it makes of a
    transpose) carry no ``tf_op``: such an op takes the phase of the
    innermost scoped op enclosing it, else of the last scoped op before
    it in the same program run (an event of the ``XLA Modules`` line). Ops of a program
    without scopes (the front end's own small programs) stay
    ``unscoped``."""
    ops.sort(key=lambda o: (o.start, -o.end))
    runs = sorted((m.start_ns, m.end_ns) for m in modules)
    stack, last, run, k = [], UNSCOPED, None, 0
    for o in ops:
        while k < len(runs) and runs[k][1] <= o.start:
            k += 1
        here = k if k < len(runs) and runs[k][0] <= o.start else None
        if here != run:
            run, last = here, UNSCOPED
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if o.cls == UNSCOPED:
            o.cls = next((p.cls for p in reversed(stack) if p.cls != UNSCOPED),
                         last)
        else:
            last = o.cls
        stack.append(o)


def _innermost(spans, t: float, default: str) -> str:
    inner = [(a, name) for a, b, name in spans if a <= t < b]
    return max(inner)[1] if inner else default


def reduce_file(path: str, device_ids) -> Scopes:
    planes = read_planes(path)
    harness, library = [], []
    for name, lines in planes.items():
        if not name.startswith("/host:"):
            continue
        for events in lines.values():
            for ev in events:
                iv = (ev.start_ns, ev.end_ns, ev.name)
                if ev.name.startswith(tr.SPAN_PREFIX):
                    harness.append(iv)
                elif ev.name.startswith(LIBRARY_PREFIX):
                    library.append(iv)
    if not harness:
        raise ValueError("the trace holds no harness span (bench.*)")
    harness.sort()
    w0, w1 = harness[0][0], max(b for _, b, _ in harness)
    n_sorts = sum(1 for _, _, n in harness if n == tr.SPAN_PREFIX + "call")

    host_ns = defaultdict(float)
    for a, b, name in library:
        if min(b, w1) > max(a, w0):
            host_ns[name] += min(b, w1) - max(a, w0)

    busy, phases, gaps = [], [], []
    for i in device_ids:
        lines = planes.get(f"/device:TPU:{i}")
        if lines is None:
            found = sorted(n for n in planes if n.startswith("/device:"))
            raise ValueError(f"trace has device planes {found}, wanted TPU:{i}")
        ops = [tr.Op(max(ev.start_ns, w0), min(ev.end_ns, w1), ev.name,
                     phase_of(ev.tf_op))
               for line in tr.DEVICE_LINES for ev in lines.get(line, [])
               if min(ev.end_ns, w1) > max(ev.start_ns, w0)]
        _inherit_phases(ops, lines.get(MODULE_LINE, []))
        tr.attribute(ops)
        per_phase = dict.fromkeys(PHASES + (UNSCOPED,), 0.0)
        for o in ops:
            per_phase[o.cls] += o.self_ns
        occupied = tr.union((o.start, o.end) for o in ops)
        busy.append(tr.length(occupied))
        phases.append(per_phase)
        for s, e in tr.subtract([[w0, w1]], occupied):
            mid = (s + e) / 2
            where = _innermost(harness, mid, "no harness span")
            gaps.append(((e - s) / 1e9,
                         where.removeprefix(tr.SPAN_PREFIX) + "/"
                         + _innermost(library, mid, "no library span")))
    return Scopes(w1 - w0, n_sorts, busy, phases, dict(host_ns),
                  sorted(gaps, reverse=True))


def reduce_dir(d: str, device_ids) -> Scopes:
    found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {d}, found {found}")
    return reduce_file(found[0], device_ids)
