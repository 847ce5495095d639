"""The benchmark's plain reference against the program's full forward, at a
tiny size on the CPU: once with qk-norm and grouped queries (Qwen3's
block), once with non-parametric LayerNorm and full multi-head attention
(OLMo's block).  Both read the same seeded int8 weights."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench.registry import Registry

dense = Registry().family("dense")
dims, make_weights, model_config = (dense.dims, dense.make_weights,
                                    dense.model_config)
dense_decoder = Registry().reference("dense_decoder")

BLOCKS = {
    "qk_norm_gqa": dict(model={"family": "dense", "norm": "rmsnorm",
                               "qk_norm": True, "mlp": "swiglu"},
                        heads=(4, 2)),
    "nonparam_ln_mha": dict(model={"family": "dense", "norm": "nonparam_ln",
                                   "qk_norm": False, "mlp": "swiglu"},
                            heads=(4, 4)),
}


def _conf(block):
    b = BLOCKS[block]
    return {
        "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
        "num_attention_heads": b["heads"][0],
        "num_key_value_heads": b["heads"][1], "head_dim": 16,
        "vocab_size": 200, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True, "model": b["model"],
        "serving": {"weight_bits": 8, "slots": 2, "max_len": 128,
                    "resident_share": 1.0},
    }


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_bench_reference_matches_program_forward(block):
    from repro.models import transformer as tfm

    d = dims(block, _conf(block))
    w = make_weights(d, 2**31 + 3)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, d.vocab, (2, 128)).astype(np.int32)
    logits = np.asarray(tfm.forward(w, jnp.asarray(tokens),
                                    model_config(d)))
    targets = rng.integers(0, d.vocab, (2, 128)).astype(np.int32)
    best, arg, at = dense_decoder.score(d, w, tokens, targets)
    np.testing.assert_allclose(np.asarray(best), logits.max(-1),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(arg), logits.argmax(-1))
    want = np.take_along_axis(logits, targets[..., None], -1)[..., 0]
    np.testing.assert_allclose(np.asarray(at), want, rtol=1e-4, atol=1e-5)


def test_bench_control_departs_from_reference():
    """The control (bfloat16) is measurably off the float32 reference."""
    d = dims("qk_norm_gqa", _conf("qk_norm_gqa"))
    w = make_weights(d, 5)
    tokens = np.random.default_rng(1).integers(0, d.vocab, (2, 128)).astype(
        np.int32)
    best, _a, _t = dense_decoder.score(d, w, tokens, tokens)
    cbest, _a, _t = dense_decoder.score(d, w, tokens, tokens,
                                        dtype="bfloat16")
    err = np.abs(np.asarray(cbest) - np.asarray(best)).max()
    assert 1e-4 < err < 0.5


def test_bench_weights_are_one_program_for_every_seed():
    d = dims("qk_norm_gqa", _conf("qk_norm_gqa"))
    a = make_weights(d, 1)
    b = make_weights(d, 2**40 + 1)
    c = make_weights(d, 1)
    leaves = lambda t: jax.tree_util.tree_leaves(t)
    assert all(np.array_equal(x, y) for x, y in zip(leaves(a), leaves(c)))
    assert not np.array_equal(a["embed"], b["embed"])
    wq = a["layers"]["attn"]["wq"]
    assert wq["packed"].dtype == jnp.uint8
    assert wq["packed"].shape == (d.n_layers, d.q_dim, d.d_model)
    assert wq["scale"].shape == (d.n_layers, d.q_dim)
    levels = np.asarray(wq["packed"]).astype(int) - 128
    assert levels.min() >= -127 and levels.max() <= 127
