"""The traffic generator: same seed, same schedule; sizes inside clips;
every seed the same set of sizes in another order."""

import json
from collections import Counter
from pathlib import Path

import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[2] / "bench" / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _sig(items):
    return [(i.uid, i.stream, i.due_s and round(i.due_s, 9), i.new_tokens,
             tuple(i.prompt.tolist())) for i in items]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_bench_open_loop_same_seed_same_schedule(seed):
    mix = _mix("xr")
    a = traffic.open_loop(mix, seed, 12.0, 151936)
    b = traffic.open_loop(mix, seed, 12.0, 151936)
    assert _sig(a) == _sig(b)
    assert all(0.0 <= i.due_s < 12.0 for i in a)
    assert [i.due_s for i in a] == sorted(i.due_s for i in a)


def test_bench_open_loop_lengths_inside_clips_and_same_set():
    mix = _mix("xr")
    streams = {s["name"]: s for s in mix["streams"]}
    sets = []
    for seed in (1, 2, 3):
        items = traffic.open_loop(mix, seed, 20.0, 1000)
        for i in items:
            p, n = streams[i.stream]["prompt"], streams[i.stream]["new_tokens"]
            lo, hi = (p["lo"], p["hi"])
            assert lo <= len(i.prompt) <= hi
            if n["dist"] == "fixed":
                assert i.new_tokens == n["value"]
            else:
                assert n["lo"] <= i.new_tokens <= n["hi"]
            assert i.prompt.min() >= 0 and i.prompt.max() < 1000
        sets.append(Counter((i.stream, len(i.prompt), i.new_tokens)
                            for i in items if i.stream != "assistant"))
        sets[-1]["assistants"] = sum(1 for i in items
                                     if i.stream == "assistant")
    # periodic streams: identical arrivals, sizes permuted; Poisson: the
    # same number of arrivals for every seed
    assert sets[0]["assistants"] == sets[1]["assistants"] == sets[2]["assistants"]
    n = 20 * mix["rate_hz"]
    counts = Counter(i.stream for i in traffic.open_loop(mix, 4, 20.0, 1000))
    assert counts["hand_tracking"] == n and counts["gaze"] == 2 * n


def test_bench_rate_scales_arrivals():
    mix = _mix("xr")
    lo = traffic.open_loop(mix, 3, 10.0, 1000, rate_hz=5)
    hi = traffic.open_loop(mix, 3, 10.0, 1000, rate_hz=20)
    assert len(hi) == pytest.approx(4 * len(lo), rel=0.05)


@pytest.mark.parametrize("seed", [0, 2**31 + 9])
def test_bench_closed_loop_same_seed_and_clips(seed):
    mix = _mix("batch")
    a = traffic.closed_loop(mix, seed, 50304)
    b = traffic.closed_loop(mix, seed, 50304)
    assert [_sig(q) for q in a] == [_sig(q) for q in b]
    assert len(a) == mix["clients"]
    assert all(len(q) == mix["requests_per_client"] for q in a)
    (s,) = mix["streams"]
    flat = [i for q in a for i in q]
    assert all(s["prompt"]["lo"] <= len(i.prompt) <= s["prompt"]["hi"]
               for i in flat)
    assert all(s["new_tokens"]["lo"] <= i.new_tokens <= s["new_tokens"]["hi"]
               for i in flat)
    other = traffic.closed_loop(mix, seed + 1, 50304)
    size = lambda qs: sorted((len(i.prompt), i.new_tokens) for q in qs
                             for i in q)
    assert sorted(len(i.prompt) for q in a for i in q) == \
        sorted(len(i.prompt) for q in other for i in q)
    assert size(a) != size(other) or _sig(a[0]) != _sig(other[0])


def test_bench_quantile_sizes_follow_the_distribution():
    d = {"dist": "lognormal", "median": 128, "sigma": 0.8, "lo": 16,
         "hi": 512}
    xs = traffic.quantile_sizes(d, 1001)
    assert sorted(xs)[500] == 128
    assert min(xs) >= 16 and max(xs) <= 512
    u = traffic.quantile_sizes({"dist": "uniform", "lo": 4, "hi": 8}, 50)
    assert Counter(u) == {4: 10, 5: 10, 6: 10, 7: 10, 8: 10}
