"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-program device time, the costliest device operations and the longest
idle gaps, labelled by the benchmark's host span around each gap.

Device planes are the ``/device:TPU:<n>`` planes: their ``XLA Ops`` line
holds one event per executed operation, their ``XLA Modules`` line one per
executed program (named like ``jit__decode_impl(42)``).  Host spans are
the ``jax.profiler.TraceAnnotation`` events whose names start with
``bench.``; the ``bench.window`` span marks the measured window.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def program_name(event_name: str) -> str:
    return _PROGRAM_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _clip(lo, hi, w_lo, w_hi):
    return max(lo, w_lo), min(hi, w_hi)


def reduce_trace(path: str, top: int = 10) -> Dict:
    """Everything the per-layer readers take from one trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, int, int]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines or "XLA Modules" in lines:
                devices.append(lines)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)))
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not devices:
        raise ValueError(f"{path}: no device plane with XLA events")
    w_lo, w_hi = window[0] if window else (None, None)
    per_dev_busy, op_time, programs = [], collections.Counter(), \
        collections.defaultdict(list)
    gaps: List[Tuple[int, int]] = []
    for i, lines in enumerate(devices):
        ops_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        events = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                  for e in ops_line.events]
        if w_lo is None:
            w_lo = min(s for s, _e, _n in events)
            w_hi = max(e for _s, e, _n in events)
        inside = []
        for s, e, name in events:
            s, e = _clip(s, e, w_lo, w_hi)
            if e > s:
                inside.append((s, e))
                op_time[op_name(name)] += e - s
        busy = _union(inside)
        per_dev_busy.append(sum(e - s for s, e in busy))
        if i == 0:
            prev = w_lo
            for s, e in busy:
                if s > prev:
                    gaps.append((prev, s))
                prev = e
            if w_hi > prev:
                gaps.append((prev, w_hi))
        mod_line = lines.get("XLA Modules")
        if mod_line is not None and i == 0:
            for e in mod_line.events:
                s = int(e.start_ns)
                if w_lo <= s < w_hi:
                    programs[program_name(e.name)].append(
                        int(e.duration_ns) * 1e-9)
    window_s = (w_hi - w_lo) * 1e-9
    busy_s = sum(per_dev_busy) / len(per_dev_busy) * 1e-9
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    labelled = [[label_gap(g, spans), (g[1] - g[0]) * 1e-9]
                for g in gaps[:top]]
    return dict(
        busy_s=busy_s, window_s=window_s, n_devices=len(devices),
        programs=dict(programs),
        device_ops=[[n, t * 1e-9] for n, t in op_time.most_common(top)],
        idle_gaps=labelled)


def label_gap(gap: Tuple[int, int], spans: List[Tuple[str, int, int]]
              ) -> str:
    """The innermost benchmark span around the gap's midpoint."""
    mid = (gap[0] + gap[1]) // 2
    best: Optional[Tuple[str, int, int]] = None
    for name, s, e in spans:
        if name == WINDOW_SPAN or not s <= mid < e:
            continue
        if best is None or e - s < best[2] - best[1]:
            best = (name, s, e)
    return best[0][len(SPAN_PREFIX):] if best else "outside benchmark spans"
