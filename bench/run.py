#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload qwen3-0.6b.xr --seed 7 --seconds 30 \
        --trace 0

A cell (``BENCHMARK.json`` ``workloads``) is a configuration served under
a traffic mix.  One process builds the serving objects from the seed,
warms up every program the window can reach (set-up), drives
``Scheduler.tick()`` for ``--seconds`` under the mix's load, then checks a
sample of what it served against the plain reference.  ``--trace 1``
records the window with the profiler and reports the per-layer metrics
instead of the end-to-end ones.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1`` a
``breakdown``), ``window_compiles`` (compiles inside the window: 0 unless
something the window ran was not warmed up), and the compared numbers
with their limits last, under ``compared``; the same numbers close
stderr.  No TPU, or fewer chips than
the cell asks for, is an error: the run exits nonzero and prints no
result.

Options for finding a cell's settings, not used by its runs:
``--rate`` overrides the open-loop rate, and ``--control int8[,bfloat16]``
puts each named lower precision of the reference in the program's place
at the served positions: the last line then judges the controls, by the
same decision, and the served tokens' own numbers close stderr above
them.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def _setup_paths() -> None:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def _use_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout, so
    only the first run of a cell compiles; it overrides any directory
    the environment names, so two checkouts share nothing.  Every program
    is kept, also those that compile in under a second (most prefill
    programs do), and the directory is not capped in size."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def devices_for(chips: int, allow_cpu: bool = False):
    """The first ``chips`` accelerator devices; no TPU is an error."""
    import jax
    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX sees {devs[0].platform} devices "
                         f"only; this benchmark does not fall back")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _pct(xs, q):
    import numpy as np
    return float(np.percentile(xs, q)) if xs else float("nan")


def measure(reg, workload: str, seed: int, seconds: float, trace: bool,
            devices, rate_hz=None, controls=()):
    """One run: set-up, window, metrics, comparison.  Returns the result
    object of the last line."""
    from bench import correct
    from bench.harness import Cell, clock

    cell_doc = reg.workload(workload)
    conf = reg.config(cell_doc["config"])
    mix = reg.mix(cell_doc["traffic"])
    family = reg.family(conf["model"]["family"])
    d = family.dims(cell_doc["config"], conf)
    dev = devices[0]
    peaks = reg.peaks(dev.device_kind)
    _log(f"device: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}")
    cell = Cell(family, d, conf, mix, peaks, seed, rate_hz=rate_hz)
    t_build = clock()
    cell.build()
    t_warm = clock()
    cell.warm()
    setup_s = clock() - T_START
    _log(f"set-up: {setup_s:.3f} s (start {t_build - T_START:.3f} s, build "
         f"{t_warm - t_build:.3f} s, warm-up {clock() - t_warm:.3f} s), "
         f"{cell.compiles.n} compiles ({cell.compiles.seconds:.3f} s "
         f"compiling, {cell.compiles.cache_hits} persistent-cache hits)")
    trace_dir = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
        # a trace grows by up to 8 MB per second of serving: a mix may
        # trace a shorter window than it measures
        seconds = min(seconds, mix.get("trace_seconds", seconds))
    w = cell.run_window(seconds, trace_dir=trace_dir)
    # a closed loop's first wave of prefills runs before the window opens
    w.setup_s = w.opened_at - T_START
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devices)
    in_window = [r for r in w.requests if 0.0 <= r.due < w.window_s]
    done = [r for r in in_window if r.finished is not None
            and r.finished <= w.window_s]
    failed = sum(1 for r in w.requests if r.req.rejected or r.req.truncated)
    _log(f"window: {w.window_s:.3f} s, {w.ticks} ticks, "
         f"{w.compiles} compiles inside ({w.compile_s:.3f} s), "
         f"peak HBM {peak} B")
    if w.lateness_s:
        _log(f"load generator late by p50 {_pct(w.lateness_s, 50) * 1e3:.1f}"
             f" ms, max {max(w.lateness_s) * 1e3:.1f} ms")
    ttft = [(r.tokens[0] - r.sent) * 1e3 for r in w.requests
            if r.tokens and 0.0 <= r.tokens[0] <= w.window_s]
    _log(f"requests: {len(in_window)} due or sent in the window, "
         f"{len(done)} finished in it, {failed} failed; "
         f"{w.counters['preemptions']} preemptions, "
         f"{w.counters['restores']} restores; TTFT from send p50 "
         f"{_pct(ttft, 50):.1f} ms p90 {_pct(ttft, 90):.1f} ms "
         f"(n={len(ttft)})")
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in reg.metrics_for(workload, kind):
        v = reg.reader(m["name"])(w)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices), memory_peak_bytes=int(peak))
    result = dict(correct=False, attempted=len(in_window), failed=failed,
                  metrics=metrics, device=device)
    if trace:
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
        result["breakdown"] = dict(device_ops=w.trace["device_ops"],
                                   idle_gaps=w.trace["idle_gaps"])
    cell.free()
    t = clock()
    chk = correct.check(family, reg.reference(conf["reference"]), d, conf,
                        seed, w.requests, controls=controls)
    _log(f"reference: {clock() - t:.3f} s over {chk['sequences']} requests, "
         f"{chk['tokens']} served tokens ({chk['preempted']} preempted)")
    limit = conf["correct"][correct.NUMBER]
    judged = {n: chk.get(n) for n in controls} if controls else {
        "program": chk["program"]}
    _log(f"served tokens: {chk['program']}, correct "
         f"{correct.decide(chk['program'], conf)}")
    result["window_compiles"] = w.compiles
    result["correct"] = all(correct.decide(v, conf) for v in judged.values())
    result["compared"] = {
        (correct.NUMBER if n == "program" else f"{n}.{correct.NUMBER}"):
        {"value": None if v is None else v[correct.NUMBER], "limit": limit}
        for n, v in judged.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    _setup_paths()
    from bench.registry import Registry
    reg = Registry()
    chips = reg.workload(args.workload)["chips"]
    controls = tuple(c for c in args.control.split(",") if c)
    from bench.correct import CONTROLS
    if any(c not in CONTROLS for c in controls):
        ap.error(f"--control takes {', '.join(CONTROLS)}")
    _use_cache()
    devices = devices_for(chips)
    result = measure(reg, args.workload, args.seed, args.seconds,
                     bool(args.trace), devices, rate_hz=args.rate,
                     controls=controls)
    for name, c in result["compared"].items():
        _log(f"compared {name}: {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
