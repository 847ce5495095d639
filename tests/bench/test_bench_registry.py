"""Cells are data: a configuration, a traffic mix or a per-layer metric is
added by adding files and BENCHMARK.json entries, and the harness finds it
by name."""

import json

import pytest

from bench.registry import BENCH, ROOT, Registry

import bench_tiny


def test_bench_dummy_parts_from_a_temporary_directory_are_listed(tmp_path):
    reg = bench_tiny.registry(tmp_path)
    (tmp_path / "metrics" / "dummy.rate.py").write_text(
        "def read(w):\n    return 42.0\n")
    assert "tiny" in reg.configs() and "qwen3-0.6b" in reg.configs()
    assert "xr-tiny" in reg.mixes() and "xr" in reg.mixes()
    assert "dummy.rate" in reg.metric_readers()
    assert reg.reader("dummy.rate")(None) == 42.0
    assert reg.config("tiny")["hidden_size"] == 64
    assert reg.mix("xr-tiny")["loop"] == "open"
    assert reg.workload("tiny.xr")["config"] == "tiny"


def test_bench_every_named_part_exists():
    reg = Registry()
    doc = reg.doc
    for c in doc["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["name"] in reg.configs()
    for w in doc["workloads"]:
        conf = reg.config(w["config"])
        reg.family(conf["model"]["family"])
        assert callable(reg.reference(conf["reference"]).score)
        reg.mix(w["traffic"])
        assert reg.metrics_for(w["name"], "per_layer")
        names = [m["name"] for m in reg.metrics_for(w["name"], "end_to_end")]
        assert "setup_s" in names and len(names) >= 2
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert callable(reg.reader(m["name"]))
    moves = {m["name"] for m in doc["end_to_end"]}
    for m in doc["per_layer"]:
        assert m["moves"] in moves
        for w in m["workloads"]:
            assert m["moves"] in [x["name"] for x in
                                  reg.metrics_for(w, "end_to_end")]


def test_bench_unknown_device_kind_is_an_error():
    reg = Registry()
    assert reg.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in the peaks table"):
        reg.peaks("TPU v99")
    with pytest.raises(KeyError):
        reg.peaks("cpu")


def test_bench_metrics_for_respects_workload_lists():
    reg = Registry()
    xr = {m["name"] for m in reg.metrics_for("qwen3-0.6b.xr", "end_to_end")}
    batch = {m["name"] for m in reg.metrics_for("olmo-1b-paged.batch",
                                                "end_to_end")}
    assert "xr_p95_ms" in xr and "xr_p95_ms" not in batch
    pl = {m["name"] for m in reg.metrics_for("olmo-1b-paged.batch",
                                             "per_layer")}
    assert "paging.wire_mb_per_tick" in pl
    assert "sched.xr_deadline_met" not in pl
