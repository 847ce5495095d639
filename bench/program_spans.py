"""The serving program's own spans in a profiler trace, beside device 0's
busy time.

While a profiler session records, every span of ``repro.serving.trace``
is a ``jax.profiler.TraceAnnotation`` (``sched.tick``, ``engine.decode``,
``paging.fetch``, ...): it lands in the ``.xplane.pb`` on the thread that
ran it, on the device trace's clock, with its arguments as the event's
stats.  :func:`load` reads those spans and the union of device 0's
operation intervals, both within the ``bench.window`` span (the whole
trace where there is none).  A span counts when it starts inside the
window, the rule :func:`bench.xplane.reduce_trace` applies to programs.
A program without these spans gives none, and the readers that use them
read None.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

from bench import xplane

PREFIXES = ("sched.", "engine.", "paging.")
TICK = "sched.tick"

Interval = Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int                  # ns, on the trace's clock
    end: int
    thread: Tuple[int, int]     # (plane, line): one line per host thread
    args: Dict[str, object]

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e-6

    def holds(self, other: "Span") -> bool:
        return (self.thread == other.thread and self.start <= other.start
                and other.end <= self.end)


@dataclasses.dataclass
class ProgramTrace:
    window: Interval
    spans: Dict[str, List[Span]]    # by name, in start order
    # device 0's busy intervals inside the window (sorted, disjoint);
    # None for a trace with no device plane (a CPU run)
    busy: Optional[List[Interval]]

    def named(self, name: str) -> List[Span]:
        return self.spans.get(name, [])

    @property
    def ticks(self) -> int:
        return len(self.named(TICK))


@functools.lru_cache(maxsize=4)
def load(path: str) -> ProgramTrace:
    """The program's spans and device 0's busy time in the trace at
    ``path``, parsed once per path."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    found: List[Span] = []
    window: Optional[Interval] = None
    ops = None
    for p, plane in enumerate(pd.planes):
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if ops is None and line is not None:
                ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                       for e in line.events]
            continue
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                name = ev.name
                start = int(ev.start_ns)
                end = int(ev.start_ns + ev.duration_ns)
                if name == xplane.WINDOW_SPAN and window is None:
                    window = (start, end)
                elif name.startswith(PREFIXES):
                    found.append(Span(name, start, end, (p, n),
                                      dict(ev.stats)))
    if window is None:
        ends = [(s.start, s.end) for s in found] + (ops or [])
        window = ((min(s for s, _e in ends), max(e for _s, e in ends))
                  if ends else (0, 0))
    lo, hi = window
    spans: Dict[str, List[Span]] = {}
    for s in sorted(found, key=lambda s: s.start):
        if lo <= s.start < hi:
            spans.setdefault(s.name, []).append(s)
    busy = None
    if ops is not None:
        busy = xplane._union([c for c in (xplane._clip(s, e, lo, hi)
                                          for s, e in ops) if c[1] > c[0]])
    return ProgramTrace(window=window, spans=spans, busy=busy)


def trace_path() -> str:
    """The trace the traced run of this process just wrote."""
    from bench.run import TRACE_DIR
    return xplane.find_trace(str(TRACE_DIR))


def of(w) -> Optional[ProgramTrace]:
    """The program trace of the traced window ``w``; None for a window
    that was not traced."""
    if not getattr(w, "trace", None):
        return None
    try:
        return load(trace_path())
    except FileNotFoundError:
        return None


def per_tick_ms(w, name: str) -> Optional[float]:
    """Summed duration of the spans called ``name`` per tick, in ms; None
    where the trace holds no tick or no such span."""
    t = of(w)
    if t is None or not t.ticks or not t.named(name):
        return None
    return sum(s.ms for s in t.named(name)) / t.ticks


def idle_ns(spans: List[Interval], busy: List[Interval]) -> int:
    """Time inside the union of ``spans`` in which the device ran
    nothing; ``busy`` is sorted and disjoint."""
    inside = xplane._union(spans)
    overlap = i = j = 0
    while i < len(inside) and j < len(busy):
        lo = max(inside[i][0], busy[j][0])
        hi = min(inside[i][1], busy[j][1])
        if hi > lo:
            overlap += hi - lo
        if inside[i][1] < busy[j][1]:
            i += 1
        else:
            j += 1
    return sum(e - s for s, e in inside) - overlap
