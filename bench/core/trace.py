"""Reduce a profiler trace to device busy time, program and kernel time,
and idle gaps named by what the host was doing.

The JAX profiler writes an XSpace (``*.xplane.pb``). Device planes are
named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event
per operation run and the ``XLA Modules`` line one per compiled program
run. The host plane ``/host:CPU`` holds the benchmark's own
``TraceAnnotation`` spans (names starting ``bench.``). All are on one
clock, in nanoseconds.

``summarize`` turns the file into plain interval lists; everything after
it is arithmetic on those lists, tested on a small recorded trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import math
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "bench."

Interval = tuple[float, float, str]  # (start_ns, end_ns, name)


@dataclasses.dataclass
class TraceSummary:
    ops: dict[int, list[Interval]]  # device id -> operations, by start
    modules: dict[int, list[Interval]]  # device id -> program runs, named
    # by ``program_key``
    host: list[Interval]  # the benchmark's annotations, by start

    @property
    def devices(self) -> list[int]:
        return sorted(self.ops)


def summarize(path) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: dict[int, list[Interval]] = {}
    modules: dict[int, list[Interval]] = {}
    host: list[Interval] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events
                    )
                elif line.name == MODULES_LINE:
                    modules.setdefault(dev, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         program_key(e))
                        for e in line.events
                    )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX)
                )
    for d in (ops, modules):
        for v in d.values():
            v.sort()
    host.sort()
    return TraceSummary(ops=ops, modules=modules, host=host)


def program_key(event) -> str:
    """A program run's name with its program id, which tells apart
    programs that share a name (every jitted ``step`` is ``jit_step``).
    A TPU trace puts the id in the name (``jit_step(7848...)``); a CPU
    trace gives it as a stat."""
    if "(" in event.name:
        return event.name
    for key, value in event.stats:
        if key == "program_id":
            return f"{event.name}#{value}"
    return event.name


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``intervals`` clipped to [lo, hi], as disjoint sorted
    (start, end) pairs."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] in which no interval runs."""
    return gaps_between(merge(intervals, lo, hi), lo, hi)


def gaps_between(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] outside ``busy`` (disjoint sorted pairs, as
    ``merge`` gives them)."""
    i = max(0, bisect.bisect_right(busy, (lo, math.inf)) - 1)
    gaps, t = [], lo
    for s, e in busy[i:]:
        if s >= hi:
            break
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


class HostSpans:
    """The benchmark's annotations, to ask which was open at an instant.
    Spans of one name never overlap one another (one thread makes them),
    so each name is one sorted list; the innermost open span is the one
    that started last."""

    def __init__(self, host: list[Interval]):
        self._by: dict[str, list[tuple[float, float]]] = {}
        for s, e, name in host:
            self._by.setdefault(name, []).append((s, e))
        for v in self._by.values():
            v.sort()

    def at(self, t: float) -> str:
        best = None
        for name, spans in self._by.items():
            i = bisect.bisect_right(spans, (t, math.inf)) - 1
            if i >= 0 and spans[i][1] >= t and (
                best is None or spans[i][0] >= best[0]
            ):
                best = (spans[i][0], name)
        return best[1] if best else "outside bench spans"


def idle_by_host(
    ops, host: list[Interval], spans: list[tuple[float, float]]
) -> dict[str, float]:
    """Device idle nanoseconds inside ``spans``, summed by the host
    annotation open at each gap's midpoint."""
    names = HostSpans(host)
    out: dict[str, float] = {}
    for lo, hi in spans:
        for s, e in idle_gaps(ops, lo, hi):
            name = names.at((s + e) / 2)
            out[name] = out.get(name, 0.0) + (e - s)
    return out


def leaves(intervals) -> list[Interval]:
    """The operations that hold no other: a ``while`` or ``conditional``
    on the ops line spans the operations of its body, so summing it too
    would count that time twice."""
    ivs = sorted(intervals)
    out = []
    for i, (s, e, name) in enumerate(ivs):
        if i + 1 == len(ivs) or ivs[i + 1][0] >= e:
            out.append((s, e, name))
    return out


def time_by_name(intervals, lo: float, hi: float) -> dict[str, float]:
    """Nanoseconds per event name, each event clipped to [lo, hi]."""
    out: dict[str, float] = {}
    for s, e, name in intervals:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            out[name] = out.get(name, 0.0) + d
    return out


def matching(intervals, pattern: str, lo: float, hi: float):
    """Events whose name matches ``pattern`` (a regular expression) and
    that lie within [lo, hi]."""
    rx = re.compile(pattern)
    return [
        (s, e, n) for s, e, n in intervals
        if s >= lo and e <= hi and rx.search(n)
    ]
