"""From a profiler trace to device busy and idle time, per-op and kernel
time, and the idle gaps labelled by what the host was doing.

`load` reads an ``.xplane.pb`` with `jax.profiler.ProfileData`: each
device plane (``/device:TPU:<i>``) gives its ``XLA Ops`` line (every
operation; a ``while`` op's event encloses its body's) and its ``XLA
Modules`` line (one event per program run); the host planes give every
annotation (``jax.profiler.TraceAnnotation``).  The rest
works on plain event lists, so the arithmetic is tested on hand-made
events as well as on a recorded trace.

All times are nanoseconds on the profiler's clock.  Host spans recorded on
another clock (the program's `perf_counter` spans) are moved onto it by
the offset that a sync annotation gives (`clock_offset`).
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NAME_CHARS = 120         # op names in the breakdown are cut to this length


class Event(NamedTuple):
    name: str
    start: float          # ns
    end: float            # ns


class Span(NamedTuple):
    name: str
    start: float
    end: float
    depth: int


def load(path: str) -> Dict:
    """``{"devices": {plane: [Event]}, "modules": {plane: [Event]},
    "host": [Event]}`` of one trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.end_ns)
                       for e in line.events]
                if line.name == OPS_LINE:
                    devices[plane.name] = evs
                elif line.name == MODULES_LINE:
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            host += [Event(e.name, e.start_ns, e.end_ns)
                     for line in plane.lines for e in line.events]
    return {"devices": devices, "modules": modules, "host": host}


def clock_offset(host: Sequence[Event], name: str, host_start_ns: int):
    """Profiler time minus host time, from the annotation ``name`` that
    was entered at ``host_start_ns`` on the host's clock."""
    for e in host:
        if e.name == name:
            return e.start - host_start_ns
    raise KeyError(f"no annotation {name!r} in the trace")


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals."""
    out: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float):
    """Idle intervals of [lo, hi] between busy ones, edges included."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class HostTimeline:
    """Which host span is innermost at any time: the span boundaries cut
    time into pieces, each labelled once, then looked up by bisection."""

    def __init__(self, spans: Sequence[Span]):
        cuts = sorted({t for s in spans for t in (s.start, s.end)})
        self._cuts = cuts
        self._labels = []
        by_start = sorted(spans, key=lambda s: s.start)
        open_: List[Span] = []
        i = 0
        for a in cuts:
            while i < len(by_start) and by_start[i].start <= a:
                open_.append(by_start[i])
                i += 1
            open_ = [s for s in open_ if s.end > a]
            best = max(open_, key=lambda s: s.depth, default=None)
            self._labels.append(best.name if best is not None else "none")

    def split(self, a: float, b: float):
        """``(label, length)`` of each piece of [a, b] under one innermost
        span (``"none"`` where no span is open)."""
        cuts = self._cuts
        i = bisect.bisect_right(cuts, a) - 1
        t = a
        while t < b:
            end = cuts[i + 1] if i + 1 < len(cuts) else b
            end = min(max(end, t), b)
            if end > t:
                yield (self._labels[i] if i >= 0 else "none"), end - t
            t = end
            i += 1
            if i >= len(cuts):
                if t < b:
                    yield "none", b - t
                return


def reduce(devices: Dict[str, List[Event]], lo: float, hi: float,
           spans: Sequence[Span] = (),
           modules: Dict[str, List[Event]] = None,
           programs: Dict[str, str] = None, top: int = 10) -> Dict:
    """Reduce the device events of the window [lo, hi].

    ``programs`` maps a name to a regular expression of program names on
    the modules line; ``program_s`` is their device time on the plane
    that runs them longest (a program of one chip runs on one plane, one
    laid over the mesh on each).  Busy and op times are means over the
    device planes; ``idle_gaps``
    sums each device's idle time under the innermost host span open in
    each part of each gap, also as a mean over the planes.
    """
    if not devices:
        raise ValueError("the trace holds no device plane")
    n = len(devices)
    programs = programs or {}
    busy_ns = 0.0
    ops: Dict[str, float] = {}
    program_ns = {k: 0.0 for k in programs}
    program_runs = {k: 0 for k in programs}
    idle: Dict[str, float] = {}
    timeline = HostTimeline(spans)
    for evs in (modules or {}).values():
        ns = {k: 0.0 for k in programs}
        runs = {k: 0 for k in programs}
        for e in clip(evs, lo, hi):
            for k, pat in programs.items():
                if re.search(pat, e.name):
                    ns[k] += e.end - e.start
                    runs[k] += 1
        for k in programs:
            if ns[k] > program_ns[k]:
                program_ns[k], program_runs[k] = ns[k], runs[k]
    for evs in devices.values():
        evs = clip(evs, lo, hi)
        busy = union(evs)
        busy_ns += sum(b - a for a, b in busy)
        for e in evs:
            ops[e.name] = ops.get(e.name, 0.0) + (e.end - e.start)
        for a, b in gaps(busy, lo, hi):
            for label, length in timeline.split(a, b):
                idle[label] = idle.get(label, 0.0) + length
    window = hi - lo
    busy_s = busy_ns / n / 1e9
    return {
        "devices": n,
        "window_s": window / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window / 1e9) if window > 0 else 0.0,
        "program_s": {k: v / 1e9 for k, v in program_ns.items()},
        "program_runs": program_runs,
        "device_ops": [[k[:NAME_CHARS], v / n / 1e9] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n / 1e9] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }
