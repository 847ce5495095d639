"""The readers of the program's own spans (``bench/program_spans.py``):
idle time inside spans on synthetic intervals, silence on a trace of a
program without the spans (``bench/testdata/olmo-batch.xplane.pb``), and
the five readers on a trace with them, recorded on one TPU v5 lite
(``bench/testdata/olmo-batch-spans.xplane.pb``: a traced window of the
``olmo-1b-paged.batch`` cell, about three ticks long)."""

import types
from pathlib import Path

import pytest

from bench import program_spans
from bench.registry import Registry

TESTDATA = Path(__file__).resolve().parents[2] / "bench" / "testdata"
NO_SPANS = TESTDATA / "olmo-batch.xplane.pb"
SPANS = TESTDATA / "olmo-batch-spans.xplane.pb"
READERS = ("sched.tick_ms", "engine.sample_ms_per_tick",
           "engine.idle_ms_per_tick", "paging.crc_ms_per_tick",
           "paging.put_ms_per_tick")
TRACED = types.SimpleNamespace(trace={"busy_s": 1.0})


def test_bench_idle_inside_spans():
    busy = [(10, 20), (30, 40), (50, 60)]
    assert program_spans.idle_ns([], busy) == 0
    assert program_spans.idle_ns([(0, 100)], []) == 100
    assert program_spans.idle_ns([(0, 100)], busy) == 70
    # overlapping spans count once; a span inside a busy stretch is busy
    assert program_spans.idle_ns([(12, 18), (15, 35), (33, 34)], busy) == 10
    assert program_spans.idle_ns([(55, 58)], busy) == 0
    # spans that straddle busy edges, and one past the last busy stretch
    assert program_spans.idle_ns([(5, 12), (38, 52), (58, 70)], busy) \
        == 5 + 10 + 10


def _readers(monkeypatch, path):
    monkeypatch.setattr(program_spans, "trace_path", lambda: str(path))
    reg = Registry()
    return {n: reg.reader(n) for n in READERS}


def test_bench_program_span_readers_silent_without_the_spans(monkeypatch):
    """A program that opens none of the spans (the trace predates them)
    and a window that was not traced read None, and raise nothing."""
    for name, read in _readers(monkeypatch, NO_SPANS).items():
        assert read(TRACED) is None, name
        assert read(types.SimpleNamespace(trace=None)) is None, name
    monkeypatch.setattr(program_spans, "trace_path",
                        lambda: program_spans.xplane.find_trace(
                            str(TESTDATA / "absent")))
    for name in READERS:
        assert Registry().reader(name)(TRACED) is None, name


def test_bench_program_span_readers_on_recorded_trace(monkeypatch):
    read = {n: r(TRACED) for n, r in _readers(monkeypatch, SPANS).items()}
    for name, v in read.items():
        assert v is not None and v >= 0.0, name
    t = program_spans.load(str(SPANS))
    assert t.busy and t.ticks >= 2
    ticks = t.named("sched.tick")
    assert all(t.window[0] <= s.start < t.window[1] for s in ticks)
    assert 0.0 < read["sched.tick_ms"] <= max(s.ms for s in ticks)
    # a paged tick spends most of its wall waiting on its pages
    fetches = t.named("paging.fetch")
    assert fetches
    per_tick_fetch = sum(f.ms for f in fetches) / t.ticks
    assert (read["paging.crc_ms_per_tick"] + read["paging.put_ms_per_tick"]
            <= per_tick_fetch)
    for f in fetches:
        crc = sum(s.ms for s in t.named("paging.crc") if f.holds(s))
        put = sum(s.ms for s in t.named("paging.put") if f.holds(s))
        assert crc > 0.0 and put > 0.0
        assert crc + put <= f.ms
    assert read["engine.idle_ms_per_tick"] <= sum(
        s.ms for s in t.named("engine.prefill") + t.named("engine.decode")
    ) / t.ticks
    assert read["engine.sample_ms_per_tick"] == pytest.approx(
        sum(s.ms for s in t.named("engine.sample")) / t.ticks)
