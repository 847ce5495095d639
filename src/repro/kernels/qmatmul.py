"""Fused dequant matmul — the At-MRAM weight path as a Pallas TPU kernel.

The Siracusa mechanism (paper Fig. 4): packed sub-byte weights are streamed
from the MRAM over a dedicated port, expanded bit-serially *at* the PEs, and
never staged at full width in any intermediate memory.  The TPU-native
analogue implemented here:

  * weights live **packed** (2/4/8-bit fields in a uint8 carrier) in HBM;
  * the Pallas grid pipeline double-buffers packed blocks HBM->VMEM
    (= the 2-bank interleaved MRAM prefetch hiding the 9-cycle latency);
  * unpack + dequant happen **inside the kernel**, adjacent to the MXU
    (= the At-Memory expansion at the PE inputs);
  * per-output-channel scales are applied once per output block on the final
    reduction step (= the NORMQUANT per-channel projection).

Two datapaths, mirroring N-EUREKA's two consumers:
  - float path  (LM serving):   x bf16/f32  @ W_packed -> f32
  - integer path (N-EUREKA pw): x uint8     @ W_packed -> int32 -> requant uint8

Sub-byte fields are never re-interleaved into a (bn, bk) weight tile (a
minor-dim reshape Mosaic cannot lower).  Field ``j`` of packed byte ``b``
holds reduction element ``b*f + j``, so the wrapper de-interleaves the
*activations* instead — ``x[:, j::f]`` becomes plane ``j`` of an
``(f, M, K/f)`` array — and the kernel sums ``f`` dots of plane ``j``
against field ``j`` of the packed block.  Scale and bias vectors travel as
``(1, N)`` rows so every block is (8, 128)-tileable.

Block shapes are MXU-aligned (multiples of 128 where the problem allows) and
the K (reduction) grid axis is innermost so output blocks stay resident in
VMEM across the reduction — output-stationary, like N-EUREKA's accumulators.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def unpack_fields(wp: jax.Array, bits: int):
    """uint8 carrier block (..., kf) -> ``8 // bits`` signed int32 planes.

    Plane ``j`` holds the levels of reduction elements ``b*f + j`` for each
    carrier byte ``b`` (the little-endian field order of
    :func:`repro.core.packing.pack`), each the carrier's shape."""
    w = wp.astype(jnp.int32)
    if bits == 8:
        return [w - 128]
    mask = (1 << bits) - 1
    off = 1 << (bits - 1)
    return [((w >> (j * bits)) & mask) - off for j in range(8 // bits)]


def deinterleave(x: jax.Array, f: int) -> jax.Array:
    """(M, K) -> (f, M, K/f) with plane j = x[:, j::f] (K a multiple of f)."""
    m, k = x.shape
    return x.reshape(m, k // f, f).transpose(2, 0, 1)


def _dot_nt(a: jax.Array, b: jax.Array) -> jax.Array:
    """a (m, k) . b (n, k)^T -> (m, n) f32."""
    return jax.lax.dot_general(a.astype(jnp.float32), b.astype(jnp.float32),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


# Largest reduction one integer dot may cover: Mosaic has no int32 matmul,
# so uint8 x int8-level products run on the float MXU path, where both
# operands are exact and a partial sum stays exact below 2**24
# (512 * 255 * 128 < 2**24).
INT_DOT_MAX_K = 512


def int_dot_nt(a: jax.Array, b: jax.Array) -> jax.Array:
    """Exact integer a (m, k) . b (n, k)^T -> (m, n) int32, for uint8
    activations against signed 8-bit levels and k <= INT_DOT_MAX_K."""
    assert a.shape[-1] <= INT_DOT_MAX_K, a.shape
    return _dot_nt(a.astype(jnp.int32), b).astype(jnp.int32)


def _qmatmul_f32_kernel(x_ref, wp_ref, scale_ref, o_ref, *, bits: int,
                        nk: int):
    """out[m, n] = sum_k x[m, k] * unpack(wp)[n, k] * scale[n]  (f32 acc)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = o_ref[...]
    for j, w in enumerate(unpack_fields(wp_ref[...], bits)):
        acc += _dot_nt(x_ref[j], w)
    o_ref[...] = acc

    @pl.when(k == nk - 1)
    def _scale():
        o_ref[...] = o_ref[...] * scale_ref[...]


def _qmatmul_f32_blockscale_kernel(x_ref, wp_ref, scale_ref, o_ref, *,
                                   bits: int):
    """out[m, n] = sum_k x[m, k] * unpack(wp)[n, k] * scale[k // block, n].

    The per-(channel, block) scales of the page wire encoding
    (core.quantize.quantize_blockwise) are applied to the unpacked levels
    *inside* the reduction — the fused "run straight off the wire form"
    path, so an encoded page never needs decoding into the per-channel
    device format before compute.  Unlike the per-channel kernel there is
    no final scale step: each k-block is already fully scaled when it
    enters the MXU.

    Carrier byte ``b`` of a block holds elements ``b*f .. b*f+f-1``, all in
    scale group ``b // (block/f)``, so every field plane takes the same
    per-byte scale: the plane is transposed to (bk/f, bn), split along
    sublanes into (groups, block/f, bn), scaled by the (groups, 1, bn)
    scale rows, and fed to the MXU as the right-hand operand.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    s = scale_ref[...]                                   # (groups, bn)
    groups, bn = s.shape
    acc = o_ref[...]
    for j, w in enumerate(unpack_fields(wp_ref[...], bits)):
        wt = w.astype(jnp.float32).T                     # (bk/f, bn)
        kf = wt.shape[0]
        wt = (wt.reshape(groups, kf // groups, bn) * s[:, None, :]
              ).reshape(kf, bn)
        acc += jax.lax.dot_general(
            x_ref[j].astype(jnp.float32), wt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    o_ref[...] = acc


def _qmatmul_int8_kernel(x_ref, wp_ref, mult_ref, bias_ref, o_ref, acc_ref,
                         *, bits: int, nk: int):
    """Integer path with fused requant: uint8 act x packed W -> uint8.

    acc int32 lives in VMEM scratch (the SCM accumulators); the NORMQUANT
    projection (per-channel float rescale + bias + clip) runs on the final
    reduction step.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc = acc_ref[...]
    for j, w in enumerate(unpack_fields(wp_ref[...], bits)):
        acc += int_dot_nt(x_ref[j], w)
    acc_ref[...] = acc

    @pl.when(k == nk - 1)
    def _requant():
        o_ref[...] = requant_u8(acc_ref[...], mult_ref[...], bias_ref[...])


def requant_u8(acc: jax.Array, mult: jax.Array, bias: jax.Array) -> jax.Array:
    """NORMQUANT projection: int32 acc -> uint8 (float-rescale formulation).

    The clipped value is cast through int32: Mosaic has no float -> uint8
    conversion, and [0, 255] survives both casts exactly."""
    y = jnp.round(acc.astype(jnp.float32) * mult) + bias.astype(jnp.float32)
    return jnp.clip(y, 0.0, 255.0).astype(jnp.int32).astype(jnp.uint8)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _row_block(m: int, bm: int) -> int:
    """Row block: ``bm``, shrunk to the 8-row tile above ``m`` so a decode
    batch of a few rows is not padded out to a full prefill block."""
    return min(bm, -(-m // 8) * 8)


def _prep(x: jax.Array, packed: jax.Array, *, bits: int, bm: int, bn: int,
          bk: int):
    """Pad x / packed to whole blocks and de-interleave x by field."""
    f = 8 // bits
    assert bk % f == 0
    bm = _row_block(x.shape[0], bm)
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(packed, 0, bn), 1, bk // f)
    mp, kp = xp.shape
    return deinterleave(xp, f), wp, bm, (mp // bm, wp.shape[0] // bn, kp // bk)


def _row(v: jax.Array, dtype, n: int) -> jax.Array:
    """Per-channel vector (N,) -> (1, N_padded) row."""
    return _pad_to(v.astype(dtype), 0, n)[None, :]


def qmatmul_f32(x: jax.Array, packed: jax.Array, scale: jax.Array, *,
                bits: int, k_orig: int,
                bm: int = 128, bn: int = 128, bk: int = 512,
                interpret: bool = False) -> jax.Array:
    """x (M, K) float @ packed (N, K/f) uint8 with per-N scale -> (M, N) f32.

    Blocks are padded to (bm, bn, bk); bk must be a multiple of the packing
    factor so packed blocks stay byte-aligned (= MRAM-row aligned).
    """
    f = 8 // bits
    m, k = x.shape
    n = packed.shape[0]
    assert packed.shape[1] * f >= k_orig and k == k_orig
    xd, wp, bm, grid = _prep(x, packed, bits=bits, bm=bm, bn=bn, bk=bk)
    sp = _row(scale, jnp.float32, bn)

    out = pl.pallas_call(
        functools.partial(_qmatmul_f32_kernel, bits=bits, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((f, bm, bk // f), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((bn, bk // f), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * bm, wp.shape[0]),
                                       jnp.float32),
        interpret=interpret,
    )(xd, wp, sp)
    return out[:m, :n]


def qmatmul_f32_blockscale(x: jax.Array, packed: jax.Array,
                           scales: jax.Array, *, bits: int, k_orig: int,
                           block: int = 32, bm: int = 128, bn: int = 128,
                           bk: int = 512, interpret: bool = False
                           ) -> jax.Array:
    """x (M, K) float @ packed (N, K/f) uint8 with per-(N, K/block) scales.

    The wire-encoded page form (packed intN levels + per-block scales)
    consumed directly — the At-MRAM expansion happens adjacent to the MXU
    with the *block* scale granularity of the page codec, so a cold page
    handed to compute run-quantized skips the host-side decode entirely.
    ``block`` must divide ``bk`` (so scale groups align with reduction
    blocks) and be a multiple of the packing factor; K tails shorter than
    a block are safe because the padded x columns are zero.  The scales
    enter the kernel transposed, (K/block, N), so a block of them is a
    lane-dense (bk/block, bn) tile.
    """
    f = 8 // bits
    assert bk % block == 0 and block % f == 0
    m, k = x.shape
    n = packed.shape[0]
    assert packed.shape[1] * f >= k_orig and k == k_orig
    assert scales.shape == (n, -(-k_orig // block))
    xd, wp, bm, grid = _prep(x, packed, bits=bits, bm=bm, bn=bn, bk=bk)
    kp = grid[2] * bk
    st = _pad_to(scales.astype(jnp.float32), 0, bn).T
    st = jnp.pad(st, ((0, kp // block - st.shape[0]), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_qmatmul_f32_blockscale_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((f, bm, bk // f), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((bn, bk // f), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bk // block, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * bm, wp.shape[0]),
                                       jnp.float32),
        interpret=interpret,
    )(xd, wp, st)
    return out[:m, :n]


def qmatmul_int8(x_q: jax.Array, packed: jax.Array, mult: jax.Array,
                 bias: jax.Array, *, bits: int, k_orig: int,
                 bm: int = 128, bn: int = 128, bk: int = 512,
                 interpret: bool = False) -> jax.Array:
    """uint8 activations (M, K) @ packed weights -> requantized uint8 (M, N).

    ``mult`` is the folded float per-channel rescale (w_scale*in_scale/out_scale),
    ``bias`` the folded int32 per-channel bias (see core.quantize.fold_requant).
    ``bk`` is capped at :data:`INT_DOT_MAX_K` so each block's dot is exact.
    """
    f = 8 // bits
    bk = min(bk, INT_DOT_MAX_K)
    m, k = x_q.shape
    n = packed.shape[0]
    xd, wp, bm, grid = _prep(x_q, packed, bits=bits, bm=bm, bn=bn, bk=bk)

    out = pl.pallas_call(
        functools.partial(_qmatmul_int8_kernel, bits=bits, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((f, bm, bk // f), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((bn, bk // f), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * bm, wp.shape[0]),
                                       jnp.uint8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(xd, wp, _row(mult, jnp.float32, bn), _row(bias, jnp.int32, bn))
    return out[:m, :n]
