"""Operations and bytes that the algorithm needs, counted from shapes.

These are the work, not what the program happens to do: a decode step
reads the packed weights as stored (int8 levels plus one float32 scale per
output row), the tied embedding once as the LM head, and the keys and
values of the live positions only; it computes for the real tokens only.
"""

from __future__ import annotations

from typing import Iterable

from bench.model import Dims, layer_matrices

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
