"""Reduce a profiler trace (``.xplane.pb``) to busy time, op times, kernel
times, exposed collectives and idle gaps.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds every operation the chip ran, and a host plane (``/host:CPU``)
whose threads hold the host spans (``jax.profiler.TraceAnnotation``).  All
event times are nanoseconds on one clock.  The reduction reads only the
trace; what a metric makes of it lives in the metric's reader.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"\bsend\b|\brecv\b|send-done|recv-done|collective", re.I)


@dataclass
class Op:
    name: str
    start: int       # ns
    end: int         # ns


@dataclass
class Trace:
    devices: dict[int, list[Op]] = field(default_factory=dict)
    host: list[Op] = field(default_factory=list)     # host spans, all threads

    @property
    def n_devices(self) -> int:
        return len(self.devices)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, host_names: set[str] | None = None) -> Trace:
    """Read device ops and, where ``host_names`` is given, the host spans of
    those names."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops: list[Op] = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    ops.append(Op(e.name, s, s + int(e.duration_ns)))
            ops.sort(key=lambda o: o.start)
            tr.devices[int(m.group(1))] = ops
        elif plane.name == HOST_PLANE and host_names:
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        s = int(e.start_ns)
                        tr.host.append(Op(e.name, s, s + int(e.duration_ns)))
    tr.host.sort(key=lambda o: o.start)
    return tr


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: list[tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(ops: list[Op]) -> int:
    return length(union([(o.start, o.end) for o in ops]))


def busy_s(tr: Trace) -> float:
    """Seconds in which an op ran, averaged over the chips."""
    if not tr.devices:
        return 0.0
    return sum(busy_ns(o) for o in tr.devices.values()) / len(tr.devices) / 1e9


def op_seconds(tr: Trace, pattern: str | None = None) -> float:
    """Summed device time of the ops whose name matches ``pattern`` (all ops
    where None), over all chips."""
    rx = re.compile(pattern) if pattern else None
    tot = 0
    for ops in tr.devices.values():
        for o in ops:
            if rx is None or rx.search(o.name):
                tot += o.end - o.start
    return tot / 1e9


def op_count(tr: Trace, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(1 for ops in tr.devices.values() for o in ops if rx.search(o.name))


OP_KIND = re.compile(r"\s([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """``%copy.65 = bf16[24,4097,16,2,64]{..} copy(...)`` -> ``%copy.65 copy
    bf16[24,4097,16,2,64]``: the HLO op, its kind and its result's shape (a
    tuple's first 60 characters), without operands and layouts."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = OP_KIND.search(rhs)
    kind = m.group(1) if m else ""
    shape = re.sub(r"\{[^{}]*\}", "", rhs[: m.start() if m else len(rhs)])
    return f"{lhs} {kind} {shape.strip()[:60]}".strip()


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    """The ops that took most device time, summed over chips and over ops of
    one short name: [[name, s]]."""
    by: dict[str, int] = {}
    for ops in tr.devices.values():
        for o in ops:
            k = short_name(o.name)
            by[k] = by.get(k, 0) + o.end - o.start
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def exposed_collective_s(tr: Trace) -> float:
    """Collective time during which no other op runs on that chip, averaged
    over the chips."""
    if not tr.devices:
        return 0.0
    tot = 0
    for ops in tr.devices.values():
        coll = union([(o.start, o.end) for o in ops if COLLECTIVE.search(o.name)])
        comp = union([(o.start, o.end) for o in ops
                      if not COLLECTIVE.search(o.name)])
        tot += length(subtract(coll, comp))
    return tot / len(tr.devices) / 1e9


def idle_gaps(tr: Trace, t0: int, t1: int, n: int = 10,
              default: str = "host outside any span") -> list[list]:
    """Device idle time in [t0, t1] by what the host was doing: each gap (on
    each chip) goes to the innermost host span that covers its middle.
    Returns the ``n`` largest totals, [[span name, seconds averaged over
    chips]]."""
    by: dict[str, int] = {}
    hosts = tr.host
    for ops in tr.devices.values():
        busy = union([(max(o.start, t0), min(o.end, t1)) for o in ops
                      if o.end > t0 and o.start < t1])
        gaps = subtract([(t0, t1)], busy)
        for s, e in gaps:
            mid = (s + e) // 2
            name, best = default, None
            for h in hosts:
                if h.start > mid:
                    break
                if h.end >= mid and (best is None or h.end - h.start < best):
                    name, best = h.name, h.end - h.start
            by[name] = by.get(name, 0) + e - s
    k = max(len(tr.devices), 1)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, v / k / 1e9] for name, v in top]


def extent(tr: Trace) -> tuple[int, int]:
    """First and last instant of any device op or host span."""
    xs = [o for ops in tr.devices.values() for o in ops] + tr.host
    if not xs:
        return 0, 0
    return min(o.start for o in xs), max(o.end for o in xs)


def describe(path: str, n: int = 40) -> dict:
    """A first look at a trace: planes, lines, event counts, top names."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            by: dict[str, int] = {}
            for e in evs:
                by[e.name] = by.get(e.name, 0) + int(e.duration_ns)
            top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
            first = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in evs[:3]]
            lines.append({"line": line.name, "events": len(evs), "top": top,
                          "first": first})
        out.append({"plane": plane.name, "lines": lines})
    return {"path": path, "planes": out}
