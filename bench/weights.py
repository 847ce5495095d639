"""Random int8 weights made on the device from the seed, in one jitted call.

The tree has the layout the serving engine takes (what
``parallel.sharding.freeze_for_serving`` gives for a dense decoder): every
matmul weight as ``{"packed": uint8 (L, N, K), "scale": f32 (L, N)}`` —
symmetric per-output-row int8 levels stored offset by 128 — the embedding
(tied LM head) in float32, and norm scales ``s`` applied as ``1 + s``.
The seed is an argument of the one compiled program, so every seed uses the
same program.  The reference calls the same function again after the
program's state is freed: it takes nothing the program made.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Dims, layer_matrices

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
