"""Chrome-trace span instrumentation (repro.serving.trace) and its
wiring through the serving tick pipeline.

Three layers:

* the Tracer primitive itself — event-format validity (every ``B`` has
  an ``E``, per-track timestamps monotonic, JSON round-trips through
  ``validate``), the shared no-op span, and the zero-allocation
  guarantee of the disabled fast path the hot tick takes on every
  untraced run;
* the instrumented pipeline — a traced paged serve whose per-tick
  ``sched.*`` spans, per-page ``paging.fetch`` spans and
  preempt/restore instants must RECONCILE with the metrics the same
  run records (summed ``exposed:*``/``hidden:*`` span durations equal
  ``paging.exposed_s``/``hidden_s`` within 10%, preempt instants equal
  ``scheduler.preemptions``), and the same spans read back from a
  profiler session with no tracer attached;
* the v6 metrics schema — every summary now carries a ``trace``
  section and ``validate`` rejects v5 payloads without one — and the
  StragglerMonitor, whose step timing rides the same span primitive.
"""

import gc
import json
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core.placement import packed_sizes, plan_for_budget
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.parallel.sharding import freeze_for_serving
from repro.runtime.monitor import StragglerMonitor
from repro.serving import (Request, Scheduler, ServingEngine, Stopwatch,
                           Tracer, validate)
from repro.serving.trace import (_NULL_SPAN, doc_tracks, instant_count, span,
                                 span_durations, validate as validate_trace)

CFG = ModelConfig(name="tinyT", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, remat=False)


@pytest.fixture(scope="module")
def packed():
    return freeze_for_serving(tfm.init_params(CFG, jax.random.PRNGKey(0)),
                              bits=8)


def _half_paged_plan(packed):
    sizes = packed_sizes(packed)
    plan = plan_for_budget(sizes, sum(sizes.values()) // 2)
    assert plan.paged_bytes(sizes) > 0
    return plan


# ---------------------------------------------------------------------------
# the Tracer primitive
# ---------------------------------------------------------------------------

def test_span_nesting_instants_counters_roundtrip():
    tr = Tracer()
    with tr.span("tick", track="main", tick=0):
        with tr.span("admit", track="main"):
            tr.instant("reject", track="main", uid=3)
        tr.counter("pool_bytes", track="io", bytes=4096)
    tr.complete("page", 1e-3, track="io", page=7)
    doc = json.loads(tr.to_json())          # round-trip through JSON
    validate_trace(doc)
    assert doc["displayTimeUnit"] == "ms"
    assert tr.event_count == 7              # 2x(B+E) + i + C + X, no M
    assert doc_tracks(doc) == ["main", "io"]
    assert instant_count(doc, "reject") == 1
    (dur,) = span_durations(doc, "page", track="io")
    assert dur == pytest.approx(1e-3)
    # nesting: the inner admit span lies within the outer tick span
    tick, = span_durations(doc, "tick")
    admit, = span_durations(doc, "admit")
    assert admit <= tick


def test_span_args_and_timestamps_are_relative_microseconds():
    tr = Tracer()
    with tr.span("a", track="t", uid=1):
        pass
    doc = tr.to_dict()
    ev = [e for e in doc["traceEvents"] if e["ph"] == "B"][0]
    assert ev["args"] == {"uid": 1}
    assert 0.0 <= ev["ts"] < 1e6            # relative to tracer birth


def test_unclosed_begin_rejected():
    tr = Tracer()
    span = tr.span("open", track="main")
    span.__enter__()
    with pytest.raises(ValueError, match="unclosed"):
        validate_trace(tr.to_dict())
    span.__exit__(None, None, None)
    validate_trace(tr.to_dict())            # closed: valid again


def test_validate_rejects_malformed_docs():
    with pytest.raises(ValueError):
        validate_trace({})                  # no traceEvents
    base = dict(pid=0, tid=0, ts=0.0, name="x")
    with pytest.raises(ValueError, match="ph"):
        validate_trace({"traceEvents": [dict(base, ph="Q")]})
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [dict(base, ph="X", dur=-1.0)]})
    with pytest.raises(ValueError, match="backwards"):
        validate_trace({"traceEvents": [
            dict(base, ph="B", ts=5.0), dict(base, ph="E", ts=6.0),
            dict(base, ph="B", ts=1.0), dict(base, ph="E", ts=2.0)]})


def test_cross_thread_tracks_get_distinct_tids():
    tr = Tracer()

    def worker():
        tr.complete("fetch", 1e-4, track="io")

    t = threading.Thread(target=worker)
    with tr.span("tick", track="main"):
        t.start()
        t.join()
    doc = tr.to_dict()
    validate_trace(doc)
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] != "M"}
    assert len(tids) == 2                   # one lane per track, not thread


def test_disabled_tracer_is_noop_and_shared_span():
    tr = Tracer(enabled=False)
    s1 = tr.span("a", track="x", big_arg=list(range(100)))
    s2 = tr.span("b")
    assert s1 is s2                         # the module-wide null span
    with s1:
        pass
    tr.instant("i")
    tr.counter("c", v=1)
    tr.complete("x", 1.0)
    assert tr.event_count == 0
    assert tr.track_names == []
    validate_trace(tr.to_dict())            # empty doc is a valid doc


def test_disabled_tracer_zero_allocation_steady_state():
    """The untraced hot path must not allocate per call: 15k
    span/instant/counter calls leave the interpreter's allocated-block
    count within pymalloc free-list noise (any per-call retention would
    show up as >= 15000 blocks)."""
    tr = Tracer(enabled=False)

    def one_pass(n):
        for _ in range(n):
            with tr.span("tick", track="t"):
                pass
            tr.instant("i", track="t")
            tr.counter("c", track="t", v=1)

    one_pass(100)                           # warm up caches
    gc.collect()
    before = sys.getallocatedblocks()
    one_pass(5000)
    assert sys.getallocatedblocks() - before < 16
    assert tr.event_count == 0


def test_span_without_session_or_tracer_is_shared_null_span():
    """With no profiler session and no tracer, the span helper every hot
    path calls returns the shared null span and allocates nothing that
    stays: 5000 calls leave the allocated-block count within free-list
    noise."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert span("sched.tick", tick=3) is _NULL_SPAN
    assert span("paging.fetch", None, "io", page=1) is _NULL_SPAN

    def one_pass(n):
        for i in range(n):
            with span("sched.tick", tick=i):
                pass

    one_pass(100)                           # warm up caches
    gc.collect()
    before = sys.getallocatedblocks()
    one_pass(5000)
    assert sys.getallocatedblocks() - before < 16


def test_stopwatch_brackets_and_injectable_clock():
    ticks = iter([1.0, 3.5])
    sw = Stopwatch(clock=lambda: next(ticks))
    with sw:
        pass
    assert sw.elapsed_s == pytest.approx(2.5)
    sw2 = Stopwatch()
    sw2.start()
    assert sw2.stop() >= 0.0


# ---------------------------------------------------------------------------
# the instrumented pipeline: spans reconcile with metrics
# ---------------------------------------------------------------------------

def _traced_serve(packed, rng, *, preempt=False):
    tr = Tracer()
    eng = ServingEngine(CFG, packed, batch_slots=1 if preempt else 2,
                        max_len=64, plan=_half_paged_plan(packed))
    eng.attach_paging()
    s = Scheduler(eng, prefill_chunk=8, async_io=True,
                  preemptive=preempt, tracer=tr, trace_track="m")
    if preempt:
        s.add_stream("urgent", priority=2)
        long_req = Request(uid=0, prompt=rng.integers(0, 256, 6)
                           .astype(np.int32), max_new_tokens=10)
        s.submit(long_req)
        for _ in range(4):
            s.tick()
        s.submit(Request(uid=1, prompt=rng.integers(0, 256, 5)
                         .astype(np.int32), max_new_tokens=3),
                 stream="urgent")
    else:
        for uid in range(3):
            s.submit(Request(uid=uid, prompt=rng.integers(0, 256, 6 + uid)
                             .astype(np.int32), max_new_tokens=5))
    s.run_until_done()
    doc = tr.to_dict()
    validate_trace(doc)
    eng.pager.close()
    return tr, doc, s, eng


def test_traced_run_phases_and_io_spans(packed, rng):
    tr, doc, s, eng = _traced_serve(packed, rng)
    # one tick, fence, admit, plan and compute span per tick, on the
    # tenant's track; begin skips ticks with no successor pass to kick
    for name in ("sched.tick", "sched.fence", "sched.admit", "sched.plan",
                 "sched.compute"):
        assert len(span_durations(doc, name, track="m")) == s.ticks, name
    assert (s.ticks - 1 <= len(span_durations(doc, "sched.begin",
                                              track="m"))
            <= s.ticks)
    # the engine's spans nest in compute on the same track
    assert span_durations(doc, "engine.decode", track="m")
    assert span_durations(doc, "engine.sample", track="m")
    # every host->device page fetch is a span on the store's fetch track
    # (demand misses ride through the same fetch path, so swaps count
    # them), each holding its CRC check and device_put
    track = eng.pager.fetch_track
    pages = span_durations(doc, "paging.fetch", track=track)
    assert len(pages) == eng.swap_count
    assert all(d >= 0.0 for d in pages)
    assert len(span_durations(doc, "paging.crc", track=track)) == len(pages)
    assert len(span_durations(doc, "paging.put", track=track)) >= len(pages)
    # the async pipeline kicked passes -> begin_pass instants
    assert instant_count(doc, "begin_pass", track="m") > 0
    # compute dominates the tick (sanity that spans carry real time)
    assert sum(span_durations(doc, "sched.compute", track="m")) > 0.0


def test_profiler_sees_the_program_spans(packed, rng, tmp_path):
    """With no Tracer attached, a profiler session alone records the
    program's spans: per tick one ``sched.tick`` holding ``sched.fence``
    and ``sched.admit``; one ``engine.decode`` per decode step, each
    holding one ``engine.sample``; one ``paging.fetch`` per swapped page
    on the page worker's thread, each holding its ``paging.crc`` (with the
    bytes it checked and the chunks it sent to the pool) and
    ``paging.put``, and each naming the pass that asked for it."""
    from bench import program_spans, xplane

    eng = ServingEngine(CFG, packed, batch_slots=2, max_len=64,
                        plan=_half_paged_plan(packed))
    eng.attach_paging()
    s = Scheduler(eng, prefill_chunk=8, async_io=True)
    decodes = []
    decode_rows = eng._decode_rows

    def counted(*a):
        decodes.append(1)
        return decode_rows(*a)

    eng._decode_rows = counted
    reqs = [Request(uid=uid, prompt=rng.integers(0, 256, 6 + uid)
                    .astype(np.int32), max_new_tokens=5) for uid in range(3)]
    for r in reqs:
        s.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        s.run_until_done()
    eng.pager.close()
    t = program_spans.load(xplane.find_trace(str(tmp_path)))
    assert t.busy is None                   # no device plane on the CPU
    ticks = t.named("sched.tick")
    assert len(ticks) == t.ticks == s.ticks
    assert [sp.args["tick"] for sp in ticks] == list(range(s.ticks))
    main = ticks[0].thread

    def inside_ticks(name):
        return [sum(1 for sp in t.named(name) if tk.holds(sp))
                for tk in ticks]

    assert inside_ticks("sched.fence") == [1] * s.ticks
    assert inside_ticks("sched.admit") == [1] * s.ticks
    assert inside_ticks("sched.compute") == [1] * s.ticks
    assert max(inside_ticks("engine.decode")) == 1
    assert len(t.named("engine.decode")) == len(decodes) > 0
    for d in t.named("engine.decode"):
        assert sum(1 for sp in t.named("engine.sample") if d.holds(sp)) == 1
    # the rest sample each request's first token after its prefill
    assert len(t.named("engine.sample")) == len(decodes) + len(reqs)
    fetches = t.named("paging.fetch")
    assert len(fetches) == eng.swap_count > 0
    for f in fetches:
        assert f.thread != main
        crcs = [sp for sp in t.named("paging.crc") if f.holds(sp)]
        assert len(crcs) == 1
        assert crcs[0].thread == f.thread
        assert crcs[0].args["nbytes"] == f.args["wire_nbytes"]
        assert crcs[0].args["chunks"] >= 0
        assert sum(1 for sp in t.named("paging.put") if f.holds(sp)) >= 1
    named = {sp.args["pass_id"] for sp in t.named("sched.begin")}
    named |= {sp.args["pass_id"] for sp in t.named("paging.wait")
              if sp.args["demand"]}
    assert {f.args["pass_id"] for f in fetches} <= named
    assert all(f.args["pass_id"] > 0 for f in fetches)


def test_trace_reconciles_with_paging_metrics(packed, rng):
    """The acceptance bar: summed stall-span durations equal the
    exposed/hidden stall the SAME run's metrics recorded, within 10%."""
    tr, doc, s, eng = _traced_serve(packed, rng)
    summary = validate(s.metrics.summary(paging=eng.paging_summary(),
                                         trace=s.trace_summary()))
    pg = summary["paging"]
    span_exposed = sum(span_durations(doc, "exposed:weights",
                                      track="m:stall"))
    span_hidden = sum(span_durations(doc, "hidden:weights",
                                     track="m:stall"))
    assert span_exposed == pytest.approx(pg["exposed_s"], rel=0.10)
    assert span_hidden == pytest.approx(pg["hidden_s"], rel=0.10)


def test_predicted_overlay_track_and_drift_ratio(packed, rng):
    """The predicted-vs-measured stall ratio is kept in the trace summary;
    the trace itself holds measured spans only."""
    tr, doc, s, eng = _traced_serve(packed, rng)
    assert not [t for t in doc_tracks(doc) if t.endswith("(predicted)")]
    ts = s.trace_summary()
    assert ts["events"] == tr.event_count > 0
    assert ts["tracks"] == tr.track_names
    assert ts["predicted_vs_measured_stall_ratio"] > 0.0


def test_preempt_restore_instants_match_scheduler_counters(packed, rng):
    tr, doc, s, eng = _traced_serve(packed, rng, preempt=True)
    assert s.metrics.preemptions >= 1
    assert instant_count(doc, "preempt", track="m") == s.metrics.preemptions
    assert instant_count(doc, "restore", track="m") == s.metrics.restores
    assert instant_count(doc, "admit", track="m") >= 2  # both requests


def test_untraced_scheduler_stays_untraced(packed, rng):
    eng = ServingEngine(CFG, packed, batch_slots=2, max_len=64,
                        plan=_half_paged_plan(packed))
    eng.attach_paging()
    s = Scheduler(eng, prefill_chunk=8)
    assert s.tracer is None and eng.tracer is None
    assert eng.pager.tracer is None
    s.submit(Request(uid=0, prompt=rng.integers(0, 256, 6)
                     .astype(np.int32), max_new_tokens=3))
    s.run_until_done()
    ts = s.trace_summary()
    assert ts["events"] == 0 and ts["tracks"] == []
    # the predicted-vs-measured drift is tracked tracer-independently,
    # so even an untraced paged run reports a meaningful ratio
    assert ts["predicted_vs_measured_stall_ratio"] > 0.0
    eng.pager.close()


# ---------------------------------------------------------------------------
# metrics schema v6 + StragglerMonitor on the span primitive
# ---------------------------------------------------------------------------

def test_metrics_v6_carries_trace_section_and_rejects_v5(packed, rng):
    _tr, _doc, s, eng = _traced_serve(packed, rng)
    doc = validate(s.metrics.summary(trace=s.trace_summary()))
    assert doc["trace"]["events"] > 0
    bare = validate(s.metrics.summary())    # no trace kwarg: zero section
    assert bare["trace"] == dict(events=0, tracks=[],
                                 predicted_vs_measured_stall_ratio=1.0)
    stale = s.metrics.summary()
    del stale["trace"]                      # a v5 payload
    with pytest.raises(ValueError):
        validate(stale)


def test_straggler_monitor_rides_the_tracer():
    t = [0.0]

    def clock():
        return t[0]

    mon = StragglerMonitor(warmup=2, threshold=2.0,
                           tracer=Tracer(clock=clock))
    durs = [0.1, 0.1, 0.1, 0.1, 0.5, 0.1]   # step 4 is the straggler
    for d in durs:
        mon.step_start()
        t[0] += d
        assert mon.step_end() == (d == 0.5)
    assert mon.flagged == [4]
    doc = mon.tracer.to_dict()
    validate_trace(doc)
    steps = span_durations(doc, "step", track="train")
    assert len(steps) == len(durs)
    assert steps == pytest.approx(durs)
    assert instant_count(doc, "straggler", track="train") == 1
