"""Device 0 idle inside the engine's prefill and decode spans, per tick:
the device waiting on the engine's host work (building inputs,
dispatch, sampling).  Idle is the complement of the union of device
operations, within the union of ``engine.prefill`` and
``engine.decode`` spans, clipped to the traced window."""

from bench import program_spans
from bench.xplane import _clip


def read(w):
    t = program_spans.of(w)
    if t is None or t.busy is None or not t.ticks:
        return None
    spans = t.named("engine.prefill") + t.named("engine.decode")
    if not spans:
        return None
    inside = [_clip(s.start, s.end, *t.window) for s in spans]
    return program_spans.idle_ns(inside, t.busy) * 1e-6 / t.ticks
