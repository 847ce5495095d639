"""A tiny configuration and mixes, registered from a temporary directory,
for running the benchmark's code on the CPU."""

import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {
    "source": "tiny test configuration",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 2048, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True,
    "model": {"family": "dense", "norm": "rmsnorm", "qk_norm": True,
              "mlp": "swiglu"},
    "reference": "dense_decoder",
    "serving": {"weight_bits": 8, "activation_dtype": "float32",
                "kv_dtype": "float32", "resident_share": 1.0,
                "slots": 4, "max_len": 128},
    "correct": {"sample_tokens": 64, "max_sequences": 8,
                "mean_logit_gap": 1e-5},
}

XR = {
    "loop": "open", "rate_hz": 8,
    "scheduler": {"prefill_chunk": 16, "token_budget": 24,
                  "preemptive": True, "admission": None},
    "streams": [
        {"name": "hand_tracking", "priority": 2, "deadline_ms": 15.0,
         "xr": True, "arrival": {"kind": "periodic", "per_rate": 1.0},
         "prompt": {"dist": "uniform", "lo": 4, "hi": 8},
         "new_tokens": {"dist": "fixed", "value": 2}},
        {"name": "assistant", "priority": 0, "deadline_ms": None,
         "xr": False, "arrival": {"kind": "poisson", "per_rate": 0.5},
         "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.8,
                    "lo": 4, "hi": 40},
         "new_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                        "lo": 2, "hi": 12}}],
}

# trackers that preempt assistants: every slot holds an assistant when a
# tracker comes due, so each tracker takes a slot from one, which is
# restored once a slot frees.  Assistants ask for 320 a second x 88 tokens
# = 28,160 tokens/s; four slots serve four tokens a tick, so a backlog of
# assistants holds the slots whenever a tick takes over 0.14 ms.  It
# still rests on the host's speed, with a wide margin: a tick of this tiny
# model takes about 3 ms on a CPU
PREEMPT = {
    "loop": "open", "rate_hz": 20,
    "scheduler": {"prefill_chunk": 16, "token_budget": 24,
                  "preemptive": True, "admission": None},
    "streams": [
        {"name": "hand_tracking", "priority": 2, "deadline_ms": 15.0,
         "xr": True, "arrival": {"kind": "periodic", "per_rate": 0.5},
         "prompt": {"dist": "uniform", "lo": 4, "hi": 8},
         "new_tokens": {"dist": "fixed", "value": 2}},
        {"name": "assistant", "priority": 0, "deadline_ms": None,
         "xr": False, "arrival": {"kind": "periodic", "per_rate": 16.0},
         "prompt": {"dist": "uniform", "lo": 8, "hi": 24},
         "new_tokens": {"dist": "uniform", "lo": 80, "hi": 96}}],
}

BATCH = {
    "loop": "closed", "clients": 4, "requests_per_client": 8,
    "scheduler": {"prefill_chunk": 64, "token_budget": None,
                  "preemptive": False, "admission": None},
    "streams": [
        {"name": "batch", "priority": 0, "deadline_ms": None, "xr": False,
         "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                    "lo": 4, "hi": 48},
         "new_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                        "lo": 2, "hi": 12}}],
}


def registry(tmp: Path, **conf_changes):
    """A registry over ``bench/`` and ``tmp``, with cells ``tiny.xr``,
    ``tiny.preempt``, ``tiny.batch`` and ``tiny-paged.batch``."""
    from bench.registry import BENCH, Registry

    for sub in ("configs", "traffic", "metrics"):
        (tmp / sub).mkdir(parents=True, exist_ok=True)
    tiny = dict(TINY, **conf_changes)
    (tmp / "configs" / "tiny.json").write_text(json.dumps(tiny))
    paged = json.loads(json.dumps(tiny))
    paged["model"] = dict(paged["model"], norm="nonparam_ln", qk_norm=False)
    paged["num_key_value_heads"] = 4
    paged["serving"]["resident_share"] = 0.5
    (tmp / "configs" / "tiny-paged.json").write_text(json.dumps(paged))
    (tmp / "traffic" / "xr-tiny.json").write_text(json.dumps(XR))
    (tmp / "traffic" / "batch-tiny.json").write_text(json.dumps(BATCH))
    (tmp / "traffic" / "preempt-tiny.json").write_text(json.dumps(PREEMPT))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["workloads"] = [
        {"name": "tiny.xr", "config": "tiny", "traffic": "xr-tiny",
         "chips": 1, "why": "test"},
        {"name": "tiny.preempt", "config": "tiny",
         "traffic": "preempt-tiny", "chips": 1, "why": "test"},
        {"name": "tiny.batch", "config": "tiny", "traffic": "batch-tiny",
         "chips": 1, "why": "test"},
        {"name": "tiny-paged.batch", "config": "tiny-paged",
         "traffic": "batch-tiny", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(doc))
    # placeholder peaks so CPU runs can compute shares; never a device number
    (tmp / "peaks.json").write_text(json.dumps({"devices": {"cpu": {
        "bf16_flops": 1e12, "int8_ops": 1e12, "hbm_bytes_per_s": 1e11,
        "hbm_bytes": 1e10}}}))
    return Registry(dirs=(BENCH, tmp), benchmark=tmp / "BENCHMARK.json")
