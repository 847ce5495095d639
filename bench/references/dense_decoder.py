"""Plain reference of a dense decoder (Qwen3, OLMo): the full causal forward
pass in straightforward ``jax.numpy``, written from the published model
descriptions and independent of the program's model code.

- Weights: the int8 levels and per-row scales that ``bench/weights.py``
  makes from the seed, dequantized as ``(level - 128) * scale``; norm
  scales ``s`` apply as ``1 + s``.
- Block: pre-norm residual.  ``h = x + Wo·attn(norm(x))``, then
  ``out = h + Wdown·(silu(Wgate·norm(h)) * Wup·norm(h))``.
- Norm: RMSNorm (Qwen3, eps from the config) or LayerNorm without affine
  parameters (OLMo, eps 1e-5).  Qwen3 also RMS-normalizes each head's
  queries and keys before the rotary embedding.
- Attention: grouped queries (query head ``h`` reads kv head
  ``h // (Hq / Hkv)``), rotary embedding on split halves with the
  config's ``rope_theta``, scale ``1 / sqrt(head_dim)``, causal.
- Head: logits = final_norm(x) · embed^T (tied embeddings).

``dtype="float32"`` is the reference: float32 throughout with every
matmul at ``precision="highest"``.  Two lower precisions serve as
controls: ``"bfloat16"`` holds activations, weights, keys and values in
bfloat16 with bfloat16 matmuls (norms and softmax still reduce in
float32); ``"int8"`` keeps the reference but feeds every weight matmul
int8 activations (symmetric, one scale per row) against the int8 weight
levels with int32 accumulation — the W8A8 step a later change to the
program would be tempted by, since its weights are int8 already.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.model import Dims

HEAD_ROWS = 128     # positions per block of the LM head


def _mm(a, b, dt):
    prec = (jax.lax.Precision.HIGHEST if dt == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jnp.matmul(a.astype(dt), b.astype(dt), precision=prec)


def _dequant(p, dt):
    w = (p["packed"].astype(jnp.int32) - 128).astype(jnp.float32)
    return (w * p["scale"][:, None]).astype(dt)


def _norm(x, scale, d: Dims):
    x32 = x.astype(jnp.float32)
    if d.norm == "rmsnorm":
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                + d.norm_eps)
    else:
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + d.norm_eps)
    if scale is not None:
        y = y * (1.0 + scale)
    return y.astype(x.dtype)


def _rope(x, theta):
    """x (B, H, S, hd): rotate the two halves by position * frequency."""
    hd, s = x.shape[-1], x.shape[-2]
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., : hd // 2], x32[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _wmm(x, p, dt, int8: bool):
    """x @ W^T for a packed int8 weight ``p``."""
    if not int8:
        return _mm(x, _dequant(p, dt).T, dt)
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    xs = jnp.where(amax > 0, amax / 127.0, 1.0)
    xq = jnp.clip(jnp.round(x32 / xs), -127, 127).astype(jnp.int8)
    wq = (p["packed"].astype(jnp.int32) - 128).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * xs * p["scale"]).astype(dt)


def _layer(x, p, d: Dims, dt, int8: bool = False):
    b, s, _ = x.shape
    h = _norm(x, p["attn_norm"].get("scale"), d)
    q = _wmm(h, p["attn"]["wq"], dt, int8)
    k = _wmm(h, p["attn"]["wk"], dt, int8)
    v = _wmm(h, p["attn"]["wv"], dt, int8)
    q = q.reshape(b, s, d.n_heads, d.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, d.n_kv_heads, d.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, d.n_kv_heads, d.head_dim).transpose(0, 2, 1, 3)
    if d.qk_norm:
        q = _norm(q, p["attn"]["q_norm"], d)
        k = _norm(k, p["attn"]["k_norm"], d)
    q, k = _rope(q, d.rope_theta), _rope(k, d.rope_theta)
    g = d.n_heads // d.n_kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = _mm(q, k.transpose(0, 1, 3, 2), dt).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d.head_dim))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    o = _mm(probs, v, dt).transpose(0, 2, 1, 3).reshape(b, s, d.q_dim)
    x = x + _wmm(o, p["attn"]["wo"], dt, int8)
    h = _norm(x, p["mlp_norm"].get("scale"), d)
    gate = _wmm(h, p["mlp"]["w_gate"], dt, int8)
    up = _wmm(h, p["mlp"]["w_up"], dt, int8)
    act = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32))
    return x + _wmm(act.astype(dt), p["mlp"]["w_down"], dt, int8)


@functools.lru_cache(maxsize=None)
def _scorer(d: Dims, dtype: str):
    int8 = dtype == "int8"
    dt = jnp.dtype("float32" if int8 else dtype)

    @jax.jit
    def score(weights, tokens, targets):
        """tokens, targets (B, S) -> (best logit, argmax token, logit of
        each ``targets`` entry), each (B, S), at every position."""
        x = jnp.take(weights["embed"], tokens, axis=0).astype(dt)

        def body(x, p):
            return _layer(x, p, d, dt, int8), None

        x, _ = jax.lax.scan(body, x, weights["layers"])
        x = _norm(x, weights["final_norm"].get("scale"), d)
        embed = weights["embed"].astype(dt)
        b, s, _ = x.shape
        nblk = s // HEAD_ROWS
        xb = x.reshape(b, nblk, HEAD_ROWS, -1).transpose(1, 0, 2, 3)
        tb = targets.reshape(b, nblk, HEAD_ROWS).transpose(1, 0, 2)

        def head(args):
            xs, ts = args
            logits = _mm(xs, embed.T, dt).astype(jnp.float32)
            at = jnp.take_along_axis(logits, ts[..., None], -1)[..., 0]
            return logits.max(-1), logits.argmax(-1).astype(jnp.int32), at

        best, arg, at = jax.lax.map(head, (xb, tb))
        back = lambda a: a.transpose(1, 0, 2).reshape(b, s)
        return back(best), back(arg), back(at)

    return score


def score(d: Dims, weights: Dict, tokens, targets,
          dtype: str = "float32") -> Tuple[jax.Array, ...]:
    """(best, argmax, target logit) at every position of ``tokens`` (B, S);
    S must be a multiple of 128."""
    with jax.default_matmul_precision(
            "default" if dtype == "bfloat16" else "highest"):
        return _scorer(d, dtype)(weights, tokens, targets)
