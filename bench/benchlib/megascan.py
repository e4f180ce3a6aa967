"""MegaScan's spans and the chip's programs in a profiler trace.

Under the profiler every MegaScan scope of the program is a host span named
``megascan.<name>`` whose stats are the tracer's ``rank`` and the scope's
scalar args (``repro.core.tracing.tracer``).  A serving tick is one
``megascan.tick``; inside it the host spans ``admit``, ``upload``, ``pull``
and ``publish`` and the device calls ``prefill``, ``prefill_chunk``,
``decode`` and ``verify``.  Each chip's ``XLA Modules`` line holds one event
per program execution, named ``<module>(<fingerprint>)``: the serving steps
are ``jit_serve_decode``, ``jit_serve_prefill``, ``jit_serve_chunk`` and
``jit_serve_verify``.

``load`` reads both from a trace; the functions below reduce them to the
numbers a per-layer reader reports, each ``None`` where the trace holds
nothing to read (a program without these spans or module names).
``benchlib.xplane`` reads the device ops.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from statistics import median

from benchlib.xplane import DEVICE_PLANE, HOST_PLANE, Op, union, length

PREFIX = "megascan."
MODULES_LINE = "XLA Modules"
DEVICE_CALLS = tuple(PREFIX + n for n in
                     ("prefill", "prefill_chunk", "decode", "verify"))


@dataclass
class Span(Op):
    stats: dict = field(default_factory=dict)


@dataclass
class Spans:
    host: list[Span] = field(default_factory=list)     # sorted by start
    modules: dict[int, list[Op]] = field(default_factory=dict)  # by chip


def load(path: str, prefix: str = PREFIX) -> Spans:
    """The host spans whose name starts with ``prefix``, with their stats,
    and every chip's program executions."""
    from jax.profiler import ProfileData

    out = Spans()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            mods = [Op(e.name, int(e.start_ns),
                       int(e.start_ns) + int(e.duration_ns))
                    for line in plane.lines if line.name == MODULES_LINE
                    for e in line.events]
            out.modules[int(m.group(1))] = sorted(mods, key=lambda o: o.start)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        s = int(e.start_ns)
                        out.host.append(Span(e.name, s, s + int(e.duration_ns),
                                             dict(e.stats)))
    out.host.sort(key=lambda o: o.start)
    return out


def tick_host_ms(sp: Spans) -> float | None:
    """Median host self time of the ticks that called the device (ms): each
    ``megascan.tick`` that holds a device-call span, less the union of those
    spans (the device calls end on ``block_until_ready``, so what is left is
    the tick's host work)."""
    calls = [s for s in sp.host if s.name in DEVICE_CALLS]
    selfs = []
    for t in sp.host:
        if t.name != PREFIX + "tick":
            continue
        inner = [c for c in calls if c.start >= t.start and c.end <= t.end]
        if inner:
            busy = length(union([(c.start, c.end) for c in inner]))
            selfs.append((t.end - t.start - busy) / 1e6)
    return median(selfs) if selfs else None


def executions(sp: Spans, module: str) -> list[tuple[int, Op]]:
    """(chip, execution) of every run of the program ``module``."""
    key = module + "("
    return [(chip, m) for chip, mods in sorted(sp.modules.items())
            for m in mods if m.name.startswith(key)]


def module_ms(sp: Spans, module: str) -> float | None:
    """Median device time of one execution of the program ``module`` (ms)."""
    runs = executions(sp, module)
    return median((m.end - m.start) / 1e6 for _, m in runs) if runs else None


def module_outside_ms(sp: Spans, ops: dict[int, list[Op]], module: str,
                      kernel: str) -> float | None:
    """Median device time of one execution of ``module`` outside the ops
    whose name holds ``kernel`` (ms): the execution's interval less the
    union of those ops that run inside it on its chip."""
    runs = executions(sp, module)
    if not runs:
        return None
    out = []
    for chip, m in runs:
        k = [(o.start, o.end) for o in ops.get(chip, ())
             if kernel in o.name and o.start >= m.start and o.end <= m.end]
        out.append((m.end - m.start - length(union(k))) / 1e6)
    return median(out)
