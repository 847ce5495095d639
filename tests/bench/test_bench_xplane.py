"""The reduction from a profiler trace to device busy time, per-program
device time and labelled idle gaps, on a small trace recorded on one
TPU v5 lite (``bench/testdata/olmo-batch.xplane.pb``: a traced window of
the ``olmo-1b-paged.batch`` cell, three ticks long)."""

from pathlib import Path

import pytest

from bench import xplane

TRACE = (Path(__file__).resolve().parents[2] / "bench" / "testdata"
         / "olmo-batch.xplane.pb")


def test_bench_union_and_gap_labels():
    assert xplane._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    spans = [("bench.window", 0, 100), ("bench.sched.tick", 10, 60),
             ("bench.engine.decode", 20, 30), ("bench.wait_arrival", 60, 90)]
    assert xplane.label_gap((22, 28), spans) == "engine.decode"
    assert xplane.label_gap((40, 50), spans) == "sched.tick"
    assert xplane.label_gap((70, 80), spans) == "wait_arrival"
    assert xplane.label_gap((95, 99), spans) == "outside benchmark spans"


def test_bench_names():
    assert xplane.program_name("jit__decode_impl(12)") == "jit__decode_impl"
    assert xplane.op_name("%fusion.3 = f32[2]{0} fusion(%p)") == "fusion.3"


def test_bench_reduce_recorded_trace():
    r = xplane.reduce_trace(str(TRACE))
    assert r["n_devices"] == 1
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert r["window_s"] == pytest.approx(1.93, abs=0.01)
    decode = r["programs"]["jit__decode_impl"]
    assert len(decode) == 3
    assert decode and all(0.0 < t < r["window_s"] for t in decode)
    assert sum(decode) <= r["busy_s"] + 1e-9
    assert len(r["device_ops"]) <= 10
    assert all(t > 0 for _n, t in r["device_ops"])
    gaps = r["idle_gaps"]
    assert gaps and len(gaps) <= 10
    assert [g for _n, g in gaps] == sorted((g for _n, g in gaps),
                                           reverse=True)
    assert sum(g for _n, g in gaps) <= r["window_s"] - r["busy_s"] + 1e-6
    assert all(not n.startswith("bench.") for n, _g in gaps)
    # a paged tick leaves the device idle while the host fences its pages
    assert gaps[0][0] == "paging.fence" and gaps[0][1] > 0.5


def test_bench_trace_readers_on_recorded_trace():
    """The device-trace readers on the recorded trace: shares of a peak
    stay within 0-100 %, and a reader with nothing to read is silent."""
    import types

    from bench.model import dims
    from bench.registry import Registry

    reg = Registry()
    d = dims("olmo-1b-paged", reg.config("olmo-1b-paged"))
    r = xplane.reduce_trace(str(TRACE))
    w = types.SimpleNamespace(
        dims=d, peaks=reg.peaks("TPU v5 lite"), trace=r,
        decode_calls=[[200 + i] * 16 for i in range(3)], prefill_calls=[],
        fences=[(0.0, 0), (0.5, 537919488), (1.0, 2 * 537919488)])
    assert 20 < reg.reader("engine.decode_ms")(w) < 40
    roof = reg.reader("decode_roofline")(w)
    assert 0 < roof < 100
    assert 0 < reg.reader("decode.mfu")(w) < 100
    assert 50 < reg.reader("device.idle_share")(w) < 100
    assert reg.reader("paging.wire_mb_per_tick")(w) == 537.919488
    assert reg.reader("paging.exposed_ms_per_tick")(w) == 500.0
    assert reg.reader("engine.prefill_ms_per_ktok")(w) is None
    empty = types.SimpleNamespace(dims=d, peaks=w.peaks, trace=None,
                                  decode_calls=[], prefill_calls=[],
                                  fences=[])
    for name in ("engine.decode_ms", "decode_roofline", "decode.mfu",
                 "device.idle_share", "paging.wire_mb_per_tick"):
        assert reg.reader(name)(empty) is None
