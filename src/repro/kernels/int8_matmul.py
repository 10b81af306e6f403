"""Pallas TPU kernel: int8 x int8 -> int32 matmul with a FUSED bit-shift
requantization epilogue — the paper's requant unit realized in VMEM.

This is the hardware-adaptation centerpiece (DESIGN.md §2): on the paper's
ASIC, the requant unit sits between the MAC array and SRAM so the un-requantized
int32 tensor never reaches memory.  On TPU the analogue is fusing the shift /
round / clip (and the Fig. 1(b) ReLU sign-check, and the Eq. 3 bias align)
into the matmul kernel's epilogue while the accumulator tile is still in
VMEM — the int32 tensor never reaches HBM, quartering the output writeback
bytes and removing a separate elementwise kernel launch.

Tiling: grid (M/bm, N/bn, K/bk) with K innermost ("arbitrary" semantics);
the int32 accumulator tile lives in a VMEM scratch buffer across K steps.
MXU alignment: bm/bn/bk multiples of 128 when shapes allow (int8 MXU packs
32x128x128); the ops.py wrapper pads otherwise.

W8A8 serving (DESIGN §13): every qlinear module of the engine forward
routes here through ``ops.int8_matmul`` when ``cfg.matmul_kernel='int8'``
— all shift amounts come from the calibrated ``LinearQuantSpec`` and are
compile-time constants, so one specialization per module shape serves
the whole run (and shards unchanged under §8 shard_map).  On interpret-
mode backends the wrapper runs ``integer_ops.int_linear`` instead (bit-
exact, no Python-loop overhead); kernel tests force the body with
``force_kernel=True``.  Zero-padded tiles are proven leak-free through
both bias-align shift signs in ``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["int8_matmul_kernel", "make_int8_matmul"]


def _shift_requant_i32(acc: jax.Array, shift: int, lo: int, hi: int) -> jax.Array:
    """Static-shift requant: round-half-away right shift + clip, int math only."""
    if shift > 0:
        half = 1 << (shift - 1)
        acc = jnp.where(acc >= 0, (acc + half) >> shift,
                        -(((-acc) + half) >> shift))
    elif shift < 0:
        # negative shift = LEFT shift: saturate BEFORE shifting.  int32 <<
        # wraps silently, so an accumulator past 2^31 / 2^|shift| would
        # sign-flip straight through the clip below; clamping to the
        # largest magnitude that shifts exactly keeps the result on the
        # saturating side (the clamped value already maps >= hi / <= lo
        # for any sub-int32 output window).
        bound = (2**31 - 1) >> (-shift)
        acc = jnp.clip(acc, -bound, bound) << (-shift)
    return jnp.clip(acc, lo, hi)


def int8_matmul_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *,
                       nk: int, shift: int, bias_shift: int,
                       relu: bool, lo: int, hi: int, out_dtype):
    """Grid = (i: M tiles, j: N tiles, k: K tiles), K innermost.

    b_ref holds the int8 bias codes; the Eq. 3 left-shift alignment
    ``b << ((N_x + N_w) - N_b)`` happens here in int32, once per (i, j) tile.
    """
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        # explicit: an ambient "highest" precision would ask Mosaic for
        # an fp32 contraction of int8 operands, which it refuses
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        if b_ref is not None:
            b = b_ref[...].astype(jnp.int32)
            if bias_shift >= 0:
                b = b << bias_shift
            else:
                # negative bias_shift: the bias grid is FINER than the
                # accumulator grid, so drop low bits with a rounding
                # right-shift by |bias_shift| (Eq. 3, "sacrificing smaller
                # values").
                b = _shift_requant_i32(b, -bias_shift, -(2**31), 2**31 - 1)
            acc = acc + b
        if relu:
            acc = jnp.maximum(acc, 0)  # Fig. 1(b): sign check pre-requant
        o_ref[...] = _shift_requant_i32(acc, shift, lo, hi).astype(out_dtype)


def make_int8_matmul(m: int, k: int, n: int, *, bm: int, bk: int, bn: int,
                     shift: int, bias_shift: int = 0, relu: bool = False,
                     lo: int = -128, hi: int = 127, has_bias: bool = False,
                     out_dtype=jnp.int8, interpret: bool = False):
    """Build the pallas_call for an (m, k) x (k, n) int8 matmul.

    All quantization constants are *static* (they are deploy-time shift
    amounts, per the paper's artifact split), so the epilogue compiles to
    immediate shifts — no scalar memory traffic.
    """
    nk = k // bk
    kernel = functools.partial(
        int8_matmul_kernel, nk=nk, shift=shift, bias_shift=bias_shift,
        relu=relu, lo=lo, hi=hi, out_dtype=out_dtype)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        wrapped = kernel
    else:
        def wrapped(x_ref, w_ref, o_ref, acc_ref):
            return kernel(x_ref, w_ref, None, o_ref, acc_ref)

    return pl.pallas_call(
        wrapped,
        grid=(m // bm, n // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="int8_matmul",
    )
