"""N-EUREKA convolution engine as Pallas TPU kernels.

Implements exactly the three operators the silicon supports (paper §II-C):
3x3 dense, 3x3 depthwise and 1x1 dense convolutions with 8-bit (uint8)
activations, 2-8-bit weights and the per-channel NORMQUANT requantization.
Layout is HWC, like the accelerator's L1 activation layout; weights are
packed along the reduction axis (the MRAM stream order).

Hardware adaptation notes (see DESIGN.md §2):
  * N-EUREKA is output-stationary with 6x6 PEs over 8x8 input tiles and
    28-channel input chunks (bandwidth-limited).  The TPU mapping keeps the
    output-stationary reduction (accumulators in VMEM scratch across the
    input-channel grid axis) but uses MXU-aligned channel blocks; the
    padded feature map stays whole in VMEM (the INPUTBUFFER analogue).
  * Bit-serial weight arithmetic becomes sub-byte *packed streaming*: HBM
    traffic scales with the weight bit-width exactly as MRAM cycles do.
  * Strides 1 and 2 are supported (MobileNet-V2 needs stride 2).  The
    wrapper splits the padded map into its ``stride**2`` spatial phases
    (``x[pi::s, pj::s]``), so every 3x3 tap is a contiguous window of one
    phase and the kernel never issues a strided load.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.qmatmul import (INT_DOT_MAX_K, _pad_to, _row, int_dot_nt,
                                   qmatmul_int8, requant_u8, unpack_fields)

# a whole 112x112 feature map plus its int32 accumulator exceeds the
# default scoped VMEM; v5e has 128 MiB of it
_VMEM_LIMIT = 96 * 1024 * 1024


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _channel_block(c: int, blk: int, mult: int) -> int:
    """Channel block: ``blk``, or all of ``c`` (rounded up to ``mult``)
    when that is smaller, so a narrow layer is one full-width block."""
    return min(blk, _round_up(c, mult))


def _phases(x: jax.Array, stride: int, ho: int, wo: int) -> jax.Array:
    """'same'-padded (H, W, C) map -> (stride**2, Hq, Wq, C) phases.

    Phase ``pi*stride + pj`` is ``xp[pi::stride, pj::stride]``, so tap
    (i, j) of output pixel (r, c) sits at phase ``(i%s)*s + j%s``, row
    ``r + i//s``, column ``c + j//s``."""
    h, w_, _ = x.shape
    s = stride
    hq, wq = ho + 2 // s, wo + 2 // s
    xp = jnp.pad(x, ((1, s * hq - h - 1), (1, s * wq - w_ - 1), (0, 0)))
    return jnp.stack([xp[pi::s, pj::s] for pi in range(s) for pj in range(s)])


def _tap(i: int, j: int, s: int):
    """(phase, row offset, column offset) of 3x3 tap (i, j) at stride s."""
    return (i % s) * s + j % s, i // s, j // s


# ---------------------------------------------------------------------------
# 3x3 dense:  out[h, w, co] = sum_{i,j,ci} x[s*h+i, s*w+j, ci] * W[co, i, j, ci]
# Grid: (cout blocks, cin blocks); the phase-split input stays whole in VMEM
# (the INPUTBUFFER analogue); cin is the innermost (reduction) axis.  Each
# tap is one MXU dot per packed field plane — no im2col concatenation.
# ---------------------------------------------------------------------------

def _strip_rows(ho: int) -> int:
    """Output rows per strip: the largest divisor of ``ho`` up to 8, so a
    strip's working set is a few vregs deep instead of the whole map."""
    return max(r for r in range(1, min(ho, 8) + 1) if ho % r == 0)


def _dense3x3_kernel(x_ref, wp_ref, mult_ref, bias_ref, o_ref, acc_ref, *,
                     bits: int, n_ci: int, stride: int, ho: int, wo: int):
    ci = pl.program_id(1)
    f = 8 // bits
    rows = _strip_rows(ho)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    taps = [(_tap(i, j, stride), unpack_fields(wp_ref[i * 3 + j], bits))
            for i in range(3) for j in range(3)]     # planes: (bco, bci/f)

    def strip(r, carry):
        r0 = r * rows
        acc = None
        for (ph, di, dj), planes in taps:
            for fj, w in enumerate(planes):
                patch = x_ref[ph * f + fj, pl.ds(r0 + di, rows),
                              dj:dj + wo, :]
                term = int_dot_nt(patch.reshape(rows * wo, -1), w)
                acc = term if acc is None else acc + term
        span = pl.ds(r0 * wo, rows * wo)
        acc_ref[span, :] = acc_ref[span, :] + acc
        return carry

    jax.lax.fori_loop(0, ho // rows, strip, 0)

    @pl.when(ci == n_ci - 1)
    def _requant():
        o_ref[...] = requant_u8(acc_ref[...], mult_ref[...], bias_ref[...])


def conv3x3_dense(x: jax.Array, packed: jax.Array, mult: jax.Array,
                  bias: jax.Array, *, bits: int, cin: int, stride: int = 1,
                  bco: int = 128, bci: int = 512,
                  interpret: bool = False) -> jax.Array:
    """x (H, W, Cin) uint8, packed (Cout, 3, 3, Cin/f) -> (Ho, Wo, Cout) uint8.

    'same' padding for stride 1; for stride 2 output is ceil(H/2) (pad=1).
    Input channels are de-interleaved by packed field, like the matmul
    activations (:func:`repro.kernels.qmatmul.deinterleave`).
    """
    f = 8 // bits
    h, w_, c = x.shape
    cout = packed.shape[0]
    assert c == cin
    ho = -(-h // stride)
    wo = -(-w_ // stride)
    bci = _channel_block(c, min(bci, INT_DOT_MAX_K), 8 * f)
    bco = _channel_block(cout, bco, 8)
    cp = _round_up(c, bci)
    cop = _round_up(cout, bco)

    ph = _phases(_pad_to(x, 2, bci), stride, ho, wo)       # (P, Hq, Wq, cp)
    n_ph, hq, wq, _ = ph.shape
    # field planes: channel b*f + j -> plane j, index b
    xd = ph.reshape(n_ph, hq, wq, cp // f, f).transpose(0, 4, 1, 2, 3)
    xd = xd.reshape(n_ph * f, hq, wq, cp // f)
    # weights: (Cout, 3, 3, Cin/f) -> (9 taps, Cout, Cin/f), blocks padded
    wp = _pad_to(_pad_to(packed, 0, bco), 3, bci // f)
    wp = wp.reshape(cop, 9, cp // f).transpose(1, 0, 2)

    n_ci = cp // bci
    out = pl.pallas_call(
        functools.partial(_dense3x3_kernel, bits=bits, n_ci=n_ci,
                          stride=stride, ho=ho, wo=wo),
        grid=(cop // bco, n_ci),
        in_specs=[
            pl.BlockSpec((n_ph * f, hq, wq, bci // f),
                         lambda co, ci: (0, 0, 0, ci)),
            pl.BlockSpec((9, bco, bci // f), lambda co, ci: (0, co, ci)),
            pl.BlockSpec((1, bco), lambda co, ci: (0, co)),
            pl.BlockSpec((1, bco), lambda co, ci: (0, co)),
        ],
        out_specs=pl.BlockSpec((ho * wo, bco), lambda co, ci: (0, co)),
        out_shape=jax.ShapeDtypeStruct((ho * wo, cop), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((ho * wo, bco), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(xd, wp, _row(mult, jnp.float32, bco), _row(bias, jnp.int32, bco))
    return out[:, :cout].reshape(ho, wo, cout)


# ---------------------------------------------------------------------------
# 3x3 depthwise: out[h, w, c] = sum_{i,j} x[s*h+i, s*w+j, c] * W[c, i, j]
# Bit-serial in silicon with parallel accumulator update; on TPU a VPU
# (elementwise) kernel over lane-dense channel blocks.
# ---------------------------------------------------------------------------

def _dw3x3_kernel(x_ref, wp_ref, mult_ref, bias_ref, o_ref, *,
                  bits: int, stride: int, ho: int, wo: int):
    f = 8 // bits
    rows = _strip_rows(ho)
    planes = unpack_fields(wp_ref[...], bits)          # f x (ceil(9/f), bc)
    taps = [(_tap(i, j, stride),
             planes[t % f][t // f:t // f + 1, :])      # level row (1, bc)
            for t, (i, j) in enumerate((i, j) for i in range(3)
                                       for j in range(3))]

    def strip(r, carry):
        r0 = r * rows
        acc = None
        for (ph, di, dj), w in taps:
            patch = x_ref[ph, pl.ds(r0 + di, rows), dj:dj + wo, :]
            term = patch.astype(jnp.int32) * w
            acc = term if acc is None else acc + term
        o_ref[pl.ds(r0, rows), :, :] = requant_u8(acc, mult_ref[...],
                                                  bias_ref[...])
        return carry

    jax.lax.fori_loop(0, ho // rows, strip, 0)


def conv3x3_dw(x: jax.Array, packed: jax.Array, mult: jax.Array,
               bias: jax.Array, *, bits: int, stride: int = 1, bc: int = 128,
               interpret: bool = False) -> jax.Array:
    """Depthwise 3x3; packed (C, ceil(9/f)) uint8 along the 9-tap axis.

    The taps travel transposed, (ceil(9/f), C), so each tap's levels are a
    lane-dense row over the channel block."""
    h, w_, c = x.shape
    ho = -(-h // stride)
    wo = -(-w_ // stride)
    bc = _channel_block(c, bc, 8)
    cp = _round_up(c, bc)
    ph = _phases(_pad_to(x, 2, bc), stride, ho, wo)    # (P, Hq, Wq, cp)
    n_ph, hq, wq, _ = ph.shape
    wt = _pad_to(packed, 0, bc).T                       # (ceil(9/f), cp)
    kf = wt.shape[0]

    out = pl.pallas_call(
        functools.partial(_dw3x3_kernel, bits=bits, stride=stride, ho=ho,
                          wo=wo),
        grid=(cp // bc,),
        in_specs=[
            pl.BlockSpec((n_ph, hq, wq, bc), lambda cb: (0, 0, 0, cb)),
            pl.BlockSpec((kf, bc), lambda cb: (0, cb)),
            pl.BlockSpec((1, bc), lambda cb: (0, cb)),
            pl.BlockSpec((1, bc), lambda cb: (0, cb)),
        ],
        out_specs=pl.BlockSpec((ho, wo, bc), lambda cb: (0, 0, cb)),
        out_shape=jax.ShapeDtypeStruct((ho, wo, cp), jnp.uint8),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(ph, wt, _row(mult, jnp.float32, bc), _row(bias, jnp.int32, bc))
    return out[:, :, :c]


# ---------------------------------------------------------------------------
# 1x1 dense (pointwise): a channel matmul — runs on the integer qmatmul
# kernel (the silicon reuses the same PEs in bit-parallel mode).  The whole
# (padded) input-channel axis is one reduction block.
# ---------------------------------------------------------------------------

def conv1x1(x: jax.Array, packed: jax.Array, mult: jax.Array, bias: jax.Array,
            *, bits: int, cin: int, stride: int = 1,
            bm: int = 256, bn: int = 128,
            interpret: bool = False) -> jax.Array:
    h, w_, c = x.shape
    if stride != 1:
        x = x[::stride, ::stride, :]
        h, w_ = x.shape[0], x.shape[1]
    cout = packed.shape[0]
    xf = x.reshape(h * w_, c)
    out = qmatmul_int8(xf, packed, mult, bias, bits=bits, k_orig=cin,
                       bm=bm, bn=_channel_block(cout, bn, 8),
                       bk=min(_round_up(c, 8 * (8 // bits)), INT_DOT_MAX_K),
                       interpret=interpret)
    return out.reshape(h, w_, cout)
