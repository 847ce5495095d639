"""The dense family: decoders whose layers are all alike — grouped-query
attention and a SwiGLU MLP — with a tied LM head (Qwen3, OLMo).  It
defines the functions of ``bench.registry.FAMILY_API``, through which
alone the harness reaches the model.

``bench/configs/<config>.json`` holds the published ``config.json`` keys
of a model (Hugging Face names) plus three groups of the benchmark's own:
``model`` (the block's mechanisms), ``serving`` (how the cell serves it:
weight bits, dtypes, resident share, slots, ``max_len``) and ``correct``
(the comparison's sample size and limit).

Weights are random int8 made on the device from the seed, in one jitted
call.  The tree has the layout the serving engine takes (what
``parallel.sharding.freeze_for_serving`` gives for a dense decoder): every
matmul weight as ``{"packed": uint8 (L, N, K), "scale": f32 (L, N)}`` —
symmetric per-output-row int8 levels stored offset by 128 — the embedding
(tied LM head) in float32, and norm scales ``s`` applied as ``1 + s``.
The seed is an argument of the one compiled program, so every seed uses the
same program.

Counts are the operations and bytes that the algorithm needs, counted from
shapes — the work, not what the program happens to do: a decode step
reads the packed weights as stored (int8 levels plus one float32 scale per
output row), the tied embedding once as the LM head, and the keys and
values of the live positions only; it computes for the real tokens only.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Iterable, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str              # rmsnorm | nonparam_ln
    norm_eps: float
    qk_norm: bool
    rope_theta: float
    tied: bool
    weight_bits: int
    slots: int
    max_len: int

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


NORM_EPS = {"rmsnorm": "rms_norm_eps"}


def dims(name: str, conf: Dict) -> Dims:
    m, s = conf["model"], conf["serving"]
    if m["mlp"] != "swiglu":
        raise ValueError(f"{name}: the dense family serves swiglu decoders")
    heads = conf["num_attention_heads"]
    d = conf["hidden_size"]
    norm = m["norm"]
    # non-parametric LayerNorm (OLMo) uses the LayerNorm default 1e-5
    eps = conf[NORM_EPS[norm]] if norm in NORM_EPS else 1e-5
    return Dims(
        name=name, n_layers=conf["num_hidden_layers"], d_model=d,
        n_heads=heads,
        n_kv_heads=conf.get("num_key_value_heads", heads),
        head_dim=conf.get("head_dim", d // heads),
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        norm=norm, norm_eps=float(eps), qk_norm=bool(m["qk_norm"]),
        rope_theta=float(conf["rope_theta"]),
        tied=bool(conf["tie_word_embeddings"]),
        weight_bits=int(s["weight_bits"]), slots=int(s["slots"]),
        max_len=int(s["max_len"]))


# (name, rows, cols) of each packed matrix of a layer, as the program
# stores it: y = x @ W^T with W (rows, cols)
def layer_matrices(d: Dims):
    return (("attn", "wq", d.q_dim, d.d_model),
            ("attn", "wk", d.kv_dim, d.d_model),
            ("attn", "wv", d.kv_dim, d.d_model),
            ("attn", "wo", d.d_model, d.q_dim),
            ("mlp", "w_gate", d.d_ff, d.d_model),
            ("mlp", "w_up", d.d_ff, d.d_model),
            ("mlp", "w_down", d.d_model, d.d_ff))


def model_config(d: Dims):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=d.name, family="dense", n_layers=d.n_layers,
        d_model=d.d_model, n_heads=d.n_heads, n_kv_heads=d.n_kv_heads,
        head_dim=d.head_dim, d_ff=d.d_ff, vocab_size=d.vocab,
        qk_norm=d.qk_norm, qkv_bias=False, rope_theta=d.rope_theta,
        mlp_act="swiglu", norm_type=d.norm, tie_embeddings=True,
        weight_bits=d.weight_bits)


# -- weights -------------------------------------------------------------------
NORM_STD = 0.1     # norm scales s ~ N(0, 0.1): the 1 + s form is exercised
EMBED_STD = 0.02


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as the two uint32 words of a threefry key."""
    s = int(seed) % (1 << 64)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def _int8_rows(key, rows: int, cols: int):
    w = jax.random.normal(key, (rows, cols), jnp.float32) * cols ** -0.5
    scale = jnp.max(jnp.abs(w), axis=1) / 127.0
    levels = jnp.clip(jnp.round(w / scale[:, None]), -127, 127)
    return (levels.astype(jnp.int32) + 128).astype(jnp.uint8), scale


@functools.lru_cache(maxsize=None)
def _generator(d: Dims):
    mats = layer_matrices(d)

    def layer(key):
        ks = jax.random.split(key, len(mats) + 4)
        out: Dict[str, Any] = {"attn": {}, "mlp": {}}
        for i, (grp, name, rows, cols) in enumerate(mats):
            packed, scale = _int8_rows(ks[i], rows, cols)
            out[grp][name] = {"packed": packed, "scale": scale}
        if d.norm == "rmsnorm":
            n = len(mats)
            out["attn_norm"] = {"scale": NORM_STD * jax.random.normal(
                ks[n], (d.d_model,), jnp.float32)}
            out["mlp_norm"] = {"scale": NORM_STD * jax.random.normal(
                ks[n + 1], (d.d_model,), jnp.float32)}
        else:
            out["attn_norm"], out["mlp_norm"] = {}, {}
        if d.qk_norm:
            n = len(mats)
            out["attn"]["q_norm"] = NORM_STD * jax.random.normal(
                ks[n + 2], (d.head_dim,), jnp.float32)
            out["attn"]["k_norm"] = NORM_STD * jax.random.normal(
                ks[n + 3], (d.head_dim,), jnp.float32)
        return out

    @jax.jit
    def make(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        k_embed, k_norm, k_layers = jax.random.split(key, 3)
        layers = jax.lax.map(layer, jax.random.split(k_layers, d.n_layers))
        tree = {
            "embed": EMBED_STD * jax.random.normal(
                k_embed, (d.vocab, d.d_model), jnp.float32),
            "final_norm": ({"scale": NORM_STD * jax.random.normal(
                k_norm, (d.d_model,), jnp.float32)}
                if d.norm == "rmsnorm" else {}),
            "layers": layers,
        }
        return tree

    return make


def make_weights(d: Dims, seed: int) -> Dict[str, Any]:
    if d.weight_bits != 8 or not d.tied:
        raise ValueError(f"{d.name}: the generator makes tied int8 weights")
    return _generator(d)(jnp.asarray(seed_words(seed)))


# -- counts --------------------------------------------------------------------
F32 = 4


def matmul_params(d: Dims) -> int:
    """Multiply-accumulates per token in the matmuls, LM head included."""
    per_layer = sum(r * c for _g, _n, r, c in layer_matrices(d))
    return d.n_layers * per_layer + d.vocab * d.d_model


def weight_bytes(d: Dims) -> int:
    """Bytes of the weights one step reads: packed matrices with their
    scales, norm scales, and the float32 tied embedding as the LM head."""
    per_layer = sum(r * c * d.weight_bits // 8 + r * F32
                    for _g, _n, r, c in layer_matrices(d))
    if d.norm == "rmsnorm":
        per_layer += 2 * d.d_model * F32
    if d.qk_norm:
        per_layer += 2 * d.head_dim * F32
    return d.n_layers * per_layer + d.vocab * d.d_model * F32


def kv_row_bytes(d: Dims) -> int:
    """Bytes of one position's keys and values over all layers (float32)."""
    return d.n_layers * 2 * d.kv_dim * F32


def attn_flops(d: Dims, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` positions."""
    return d.n_layers * 4 * d.q_dim * keys


def token_flops(d: Dims, keys: int) -> int:
    return 2 * matmul_params(d) + attn_flops(d, keys)


def decode_call(d: Dims, keys: Iterable[int]) -> tuple:
    """(flops, bytes) of one batched decode step whose real rows attend
    ``keys`` positions each (the new token included)."""
    keys = list(keys)
    flops = sum(token_flops(d, k) for k in keys)
    nbytes = (weight_bytes(d) + len(keys) * d.d_model * F32
              + sum(k for k in keys) * kv_row_bytes(d))
    return flops, nbytes


def prefill_flops(d: Dims, tokens: int, key_sum: int) -> int:
    """``tokens`` real prompt tokens whose queries attend ``key_sum``
    positions in all."""
    return 2 * matmul_params(d) * tokens + attn_flops(d, key_sum)


# -- warm-up -------------------------------------------------------------------
def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def prefill_shapes(mix: Dict, d: Dims) -> List[tuple]:
    """Every (bucket, kv_span) prefill program the mix can reach: chunks
    of n tokens run in the bucket next_pow2(n) and attend the cache up to
    next_pow2(insert position + bucket), capped at ``max_len``."""
    sc = mix["scheduler"]
    chunk = next_pow2(sc["prefill_chunk"])
    pmin, pmax = traffic.length_range(mix, "prompt")
    budgeted = sc.get("token_budget") is not None
    shapes = set()
    for n in range(1, min(chunk, pmax) + 1):
        b = next_pow2(n)
        if budgeted:
            positions = range(0, pmax - n + 1)
        else:
            positions = range(0, pmax - n + 1, chunk)
        for pos in positions:
            if pos == 0 and not budgeted and n < min(pmin, chunk):
                continue
            shapes.add((b, min(d.max_len, next_pow2(pos + b))))
    return sorted(shapes)


def warm_prefill(eng, mix: Dict, d: Dims) -> None:
    """Run each prefill program of ``prefill_shapes`` once on the engine's
    cache, as the engine calls it for a dense decoder: all slots' rows in
    one call, tokens padded to the bucket."""
    params = eng.fence_tick_params()
    k = eng.slots
    for bucket, span in prefill_shapes(mix, d):
        fn = eng._prefill_for_bucket(bucket, True, span)
        logits, cache = fn(params, jnp.zeros((k, bucket), jnp.int32),
                           eng.cache, jnp.arange(k, dtype=jnp.int32),
                           jnp.zeros((k,), jnp.int32))
        del cache      # free the copy before the next program runs
        # the engine reads each finished row's last logits eagerly
        int(jnp.argmax(logits[k - 1, bucket - 1]))
        del logits
