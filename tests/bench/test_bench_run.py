"""A whole run of the harness on the CPU at a tiny size, past its look for
a chip: set-up, window, metrics and the comparison with the reference.
The comparison passes on the program as it is, preempted and restored
requests included, and fails with the timed path broken underneath (a
served token altered where it is produced; a decode step that returns its
cache unchanged; a restore that writes the wrong cache rows) and for the
controls."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import bench_tiny
from bench import run

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 77


def _measure(tmp_path, workload, seconds=1.5, **kw):
    reg = bench_tiny.registry(tmp_path)
    devices = run.devices_for(1, allow_cpu=True)
    return run.measure(reg, workload, SEED, seconds, False, devices, **kw)


def _assert_line(res):
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    json.dumps(res)


def test_bench_run_is_correct_and_reports_every_metric(tmp_path):
    res = _measure(tmp_path, "tiny.xr")
    _assert_line(res)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["window_compiles"] == 0
    for name in ("tok_s", "itl_p95_ms", "ttft_p90_ms", "xr_p95_ms",
                 "setup_s"):
        assert res["metrics"][name]["value"] > 0, name
    c = res["compared"]["mean_logit_gap"]
    assert c["value"] <= c["limit"]


def test_bench_control_is_not_correct(tmp_path):
    """The controls — the reference put in the program's place at the
    served positions, with int8 activations in its weight matmuls or in
    bfloat16 — go through the run's own decision and read not correct.
    This only shows the plumbing at a tiny size on the CPU, where the
    program computes in float32 and its limit is 1e-5; the separation at
    the cells' sizes and limits is read on the chip."""
    res = _measure(tmp_path, "tiny.batch", controls=("int8", "bfloat16"))
    _assert_line(res)
    assert res["correct"] is False
    for name in ("int8", "bfloat16"):
        c = res["compared"][f"{name}.mean_logit_gap"]
        assert c["value"] > c["limit"], name


def _sampled(monkeypatch):
    """Keep what the comparison saw, for the assertions."""
    from bench import correct

    seen = {}
    real = correct.check

    def check(*a, **kw):
        seen.update(real(*a, **kw))
        return dict(seen)

    monkeypatch.setattr(correct, "check", check)
    return seen


def test_bench_preempted_and_restored_requests_are_compared(
        tmp_path, monkeypatch):
    seen = _sampled(monkeypatch)
    res = _measure(tmp_path, "tiny.preempt", seconds=4.0)
    assert seen["preempted"] > 0
    assert res["correct"] is True


def test_bench_restore_of_wrong_kv_rows_is_not_correct(tmp_path,
                                                        monkeypatch):
    """A restore that writes each checkpointed value row one position off
    its key (keys and values moved together would attend the same set):
    the restored requests decode from the wrong cache."""
    from repro.serving.engine import ServingEngine

    real = ServingEngine.restore

    def shifted(self, ckpt, slot):
        if ckpt.kv is not None:
            ckpt.kv = dict(ckpt.kv, v=np.roll(ckpt.kv["v"], 1, axis=-2))
        return real(self, ckpt, slot)

    monkeypatch.setattr(ServingEngine, "restore", shifted)
    seen = _sampled(monkeypatch)
    res = _measure(tmp_path, "tiny.preempt", seconds=4.0)
    assert seen["preempted"] > 0
    assert res["correct"] is False


def test_bench_altered_token_is_not_correct(tmp_path, monkeypatch):
    from repro.serving import engine as eng_mod

    real = eng_mod.sample_token_batch

    def altered(logits, key, temps):
        toks = real(logits, key, temps)
        return (toks + 1) % logits.shape[-1]

    monkeypatch.setattr(eng_mod, "sample_token_batch", altered)
    res = _measure(tmp_path, "tiny.batch")
    assert res["correct"] is False
    assert res["compared"]["mean_logit_gap"]["value"] > \
        res["compared"]["mean_logit_gap"]["limit"]


def test_bench_step_returning_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from repro.serving.engine import ServingEngine
    from repro.models import transformer as tfm

    def stale(self, params, tokens, cache, pos_vec):
        logits, _new = tfm.step(params, tokens, cache, pos_vec, self.cfg,
                                engine=self.plan)
        return logits, cache

    monkeypatch.setattr(ServingEngine, "_decode_impl", stale)
    res = _measure(tmp_path, "tiny.batch")
    assert res["correct"] is False


def test_bench_paged_run_is_correct(tmp_path):
    res = _measure(tmp_path, "tiny-paged.batch")
    _assert_line(res)
    assert res["correct"] is True
    assert res["metrics"]["tok_s"]["value"] > 0


def test_bench_no_tpu_is_an_error():
    with pytest.raises(SystemExit, match="no TPU"):
        run.devices_for(1)


def test_bench_checkout_with_only_the_benchmark_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-0.6b.xr",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_bench_traced_counts_cover_only_the_window(tmp_path, monkeypatch):
    """With ``--trace 1`` the calls counted for the device-trace metrics
    are those made in the window: a closed loop's first wave of prefills,
    before the window opens, is not among them."""
    from bench import xplane
    from bench.harness import Cell
    from bench.model import dims

    monkeypatch.setattr(xplane, "find_trace", lambda d: d)
    monkeypatch.setattr(xplane, "reduce_trace", lambda p: {})
    reg = bench_tiny.registry(tmp_path)
    conf = reg.config("tiny")
    cell = Cell(dims("tiny", conf), conf, reg.mix("batch-tiny"),
                reg.peaks("cpu"), SEED)
    cell.build()
    cell.warm()
    w = cell.run_window(1.5, trace_dir=str(tmp_path / "trace"))
    cell.free()
    first_wave = [r for r in w.requests if r.sent < 0]
    in_window = [r for r in w.requests if r.sent >= 0 and r.tokens
                 and r.tokens[0] <= w.window_s]
    assert first_wave and in_window
    assert sum(t for t, _k in w.prefill_calls) == sum(
        len(r.item.prompt) for r in in_window)
