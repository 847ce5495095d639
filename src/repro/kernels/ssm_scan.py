"""Fused selective-scan (Mamba-1) Pallas TPU kernel.

EXPERIMENTS.md §Perf cell B identifies mamba's 16x state expansion as the
dominant memory term of the SSM/hybrid cells: the pure-JAX chunked scan
materializes the (B, T, d_inner, N) discretized tensors in HBM on every
associative-scan pass (log2(chunk) passes, x3 with remat+backward).

This kernel keeps the expansion entirely in VMEM:

  grid = (batch, d_inner blocks, sequence chunks)   [chunks innermost]
  scratch: h (di_blk, N) f32 — carried across the chunk axis
  per chunk: read x/dt (chunk, di_blk) + B/C (chunk, N) from HBM,
             discretize + associative-scan + output IN VMEM,
             write y (chunk, di_blk) back.

HBM traffic per token: x, dt, y (3·di) + B, C (2·N) bytes — the N-fold
expansion never leaves VMEM, exactly the At-Memory discipline the paper
applies to weights, applied here to the SSM state stream.

Inside a chunk the recurrence h_t = a_t * h_{t-1} + b_t runs once per state
index n as a Hillis-Steele scan down the chunk's rows (log2(chunk) steps of
a sublane roll plus a masked combine) — the shape Mosaic lowers, where the
strided slices of ``lax.associative_scan`` do not.  The working set is a
few chunk x di_blk f32 planes (256x128 = 128 KiB each), one state index
at a time.  A enters transposed to (N, Di) and D as a (1, Di) row, so
d_inner is the lane axis of every operand.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _linear_scan(a: jax.Array, b: jax.Array):
    """Inclusive scan of h_t = a_t * h_{t-1} + b_t down axis 0 (h_{-1} = 0):
    returns (prod_{s<=t} a_s, h_t) — ``associative_scan``'s result for the
    same combine, by doubling row shifts."""
    t = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    k = 1
    while k < t:
        live = row >= k
        b = jnp.where(live, a * pltpu.roll(b, k, 0) + b, b)
        a = jnp.where(live, a * pltpu.roll(a, k, 0), a)
        k *= 2
    return a, b


def _scan_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, d_ref, o_ref, h_ref):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)           # (T, dib)
    dt = dt_ref[0].astype(jnp.float32)         # (T, dib)
    At = at_ref[...].astype(jnp.float32)       # (N, dib)
    B = b_ref[0].astype(jnp.float32)           # (T, N)
    C = c_ref[0].astype(jnp.float32)           # (T, N)
    u = dt * x
    y = x * d_ref[...].astype(jnp.float32)     # the D skip term
    for n in range(At.shape[0]):
        aa, bb = _linear_scan(jnp.exp(dt * At[n:n + 1, :]),
                              u * B[:, n:n + 1])
        h = aa * h_ref[n:n + 1, :] + bb        # (T, dib)
        h_ref[n:n + 1, :] = h[-1:, :]
        y = y + h * C[:, n:n + 1]
    o_ref[0] = y.astype(o_ref.dtype)


def selective_scan_fused(x: jax.Array, dt: jax.Array, A: jax.Array,
                         B: jax.Array, C: jax.Array, D: jax.Array, *,
                         chunk: int = 256, di_block: int = 128,
                         interpret: bool = False) -> jax.Array:
    """x, dt: (Bz, S, Di); A: (Di, N); B, C: (Bz, S, N); D: (Di,) -> y.

    Zero initial state (the train/prefill case); S padded to chunk, Di to
    di_block.
    """
    bsz, s, di = x.shape
    n = A.shape[1]
    chunk = min(chunk, s)
    di_block = min(di_block, di)
    spad = (-s) % chunk
    dpad = (-di) % di_block
    if spad or dpad:
        x = jnp.pad(x, ((0, 0), (0, spad), (0, dpad)))
        dt = jnp.pad(dt, ((0, 0), (0, spad), (0, dpad)))
        B = jnp.pad(B, ((0, 0), (0, spad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, spad), (0, 0)))
    if dpad:
        A = jnp.pad(A, ((0, dpad), (0, 0)))
        D = jnp.pad(D, ((0, dpad),))
    n_chunks = (s + spad) // chunk
    n_di = (di + dpad) // di_block

    out = pl.pallas_call(
        _scan_kernel,
        grid=(bsz, n_di, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, di_block), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, di_block), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((n, di_block), lambda b, d, c: (0, d)),
            pl.BlockSpec((1, chunk, n), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, di_block), lambda b, d, c: (0, d)),
        ],
        out_specs=pl.BlockSpec((1, chunk, di_block), lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((bsz, s + spad, di + dpad), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, di_block), jnp.float32)],
        interpret=interpret,
    )(x, dt, A.T, B, C, D[None, :])
    return out[:, :s, :di]


def hbm_bytes_per_token(di: int, n: int, itemsize: int = 2) -> Tuple[int, int]:
    """(fused, unfused) HBM bytes per token per layer — the §Perf estimate.

    Unfused (pure-JAX chunked scan): the (di, N) expansion crosses HBM
    ~2x per associative-scan pass (log2(chunk)=8 passes) plus x/dt/B/C/y.
    Fused: x, dt, y (3·di) + B, C (2·N) only.
    """
    fused = (3 * di + 2 * n) * itemsize
    passes = 8
    unfused = (3 * di + 2 * n) * itemsize + 2 * passes * di * n * 4
    return fused, unfused
