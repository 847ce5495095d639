"""Operation and byte counts from shapes, checked by hand at a tiny size
and at the cells' published sizes."""

import json
from pathlib import Path

import pytest

from bench import counts
from bench.model import dims

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"


def _tiny():
    return dims("t", {
        "hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
        "vocab_size": 10, "rope_theta": 1e4, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True,
        "model": {"family": "dense", "norm": "rmsnorm", "qk_norm": True,
                  "mlp": "swiglu"},
        "serving": {"weight_bits": 8, "slots": 2, "max_len": 16}})


def test_bench_counts_by_hand():
    d = _tiny()
    # per layer: wq 8x8, wk 4x8, wv 4x8, wo 8x8, gate/up 16x8, down 8x16
    per_layer = 64 + 32 + 32 + 64 + 128 + 128 + 128
    assert counts.matmul_params(d) == 2 * per_layer + 10 * 8
    rows = 8 + 4 + 4 + 8 + 16 + 16 + 8
    layer_bytes = per_layer + 4 * rows + 2 * 8 * 4 + 2 * 4 * 4
    assert counts.weight_bytes(d) == 2 * layer_bytes + 10 * 8 * 4
    assert counts.kv_row_bytes(d) == 2 * 2 * 4 * 4
    assert counts.attn_flops(d, 5) == 2 * 4 * 8 * 5
    flops, nbytes = counts.decode_call(d, [3, 5])
    assert flops == (2 * 2 * counts.matmul_params(d)
                     + counts.attn_flops(d, 8))
    assert nbytes == counts.weight_bytes(d) + 2 * 8 * 4 + 8 * 64
    # a 3-token prompt chunk at positions 0..2 attends 1 + 2 + 3 keys
    assert counts.prefill_flops(d, 3, 6) == (
        2 * counts.matmul_params(d) * 3 + counts.attn_flops(d, 6))


@pytest.mark.parametrize("name,params,weight_mb", [
    ("qwen3-0.6b", 595_984_384, 1_064),
    ("olmo-1b-paged", 1_176_764_416, 1_488),
])
def test_bench_counts_at_published_sizes(name, params, weight_mb):
    d = dims(name, json.loads((CONFIGS / f"{name}.json").read_text()))
    assert counts.matmul_params(d) == params
    assert round(counts.weight_bytes(d) / 1e6) == weight_mb
    # decode of 16 slots at 300 live positions each is bound by bytes
    flops, nbytes = counts.decode_call(d, [300] * 16)
    assert nbytes / 819e9 > flops / 197e12
