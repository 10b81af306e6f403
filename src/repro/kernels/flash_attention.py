"""Pallas TPU flash attention with FUSED int8-KV bit-shift dequantization.

The paper's thesis (DESIGN.md §1) is that every avoidable memory touch of a
full-precision tensor costs energy and information: its ASIC fuses the
requant unit between the MAC array and SRAM so un-requantized tensors never
reach memory.  ``int8_matmul.py`` realizes that for projections; this module
realizes it for attention — the dominant cost at long sequence and during
decode (DESIGN.md §2).

Dataflow (the whole point):

    HBM:   int8 KV codes ──DMA──▶ VMEM tile ──cast·2^-N (in-register)──▶ MXU
                                      │
           (the bf16 KV tensor never exists in HBM; previously the cache was
            dequantized to a full bf16 copy *before* attention, tripling KV
            read/write bytes, and the (B,H,qc,kc) score tiles round-tripped
            through HBM between the softmax and the PV matmul)

Dequantization of a power-of-two-grid code is ``x * 2^-N`` (Eq. 1 inverse)
with static ``N``:

  * K codes: the scalar folds into the softmax scale — the kernel computes
    ``(q @ K_codes^T) * (sm_scale * 2^-N_k)``; the cast int8→bf16 is exact
    (|code| <= 128 < 2^8) and happens on the VMEM tile.
  * V codes: the scalar folds into the final normalization —
    ``out = acc * 2^-N_v / l`` — exact because ``l`` depends only on ``p``.

Two grid variants:

  * **prefill**: grid (B, H, Sq/bq, Skv/bk), causal, online softmax with
    fp32 running (m, l, acc) in VMEM scratch, GQA via the K/V index map
    (``h // groups`` — no repeated KV is ever materialized).  KV tiles
    above the causal diagonal (and fully-padded tiles) are skipped.
  * **decode**: q_len == 1, grid (B, KVH, S/bk), the (scalar, traced)
    absolute position arrives via scalar prefetch; all ``groups`` query
    heads of one KV head ride in the sublane dimension of a single q tile,
    so a KV tile is DMA'd exactly once per group (GQA-aware).  KV tiles
    entirely in the future (``kv_start > pos``) are skipped.

Tiling follows the ``int8_matmul`` conventions: lane dim 128, fp32 scratch
persists across the innermost ("arbitrary") KV grid dimension, block shapes
are static and chosen by the ``ops.py`` wrapper which also pads inputs.
Head axes are flattened into the lane axis — q/o ``(B, S, H*D)``, K/V
``(B, S, KVH*D)`` or pool ``(NB, bs, KVH*D)``, all free reshapes of the
model's layouts — so a block that selects one head is ``(1, rows, D)``:
its last two dims are (8, 128)-tileable, which a size-1 head dim in the
second-minor place is not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["make_flash_prefill", "make_flash_decode",
           "make_paged_flash_decode", "mxu_precision", "DEFAULT_MASK_VALUE"]

# Finite stand-in for -inf: exp(MASK - m) underflows to exactly 0.0 in f32
# whenever any in-tile entry is live, and never produces inf - inf = NaN.
DEFAULT_MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)

# m/l running statistics keep a full 128-lane register row (TPU lane width);
# only column 0 is semantically live, the rest is broadcast.
_STATS_LANES = 128


def mxu_precision(dtype):
    """Explicit dot precision for a kernel's operands.  Narrow (bf16,
    int8) operands multiply exactly into the f32/i32 accumulator, so
    DEFAULT loses nothing, and it must be explicit: under an enclosing
    ``jax.default_matmul_precision("highest")`` Mosaic would be asked for
    an fp32 contraction of narrow operands, which it refuses.  f32
    operands keep the ambient precision (None)."""
    return jax.lax.Precision.DEFAULT if jnp.dtype(dtype).itemsize < 4 else None


def _flash_prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                          *, groups: int, score_scale: float, v_scale: float,
                          causal: bool, q_offset: int, sq: int, skv: int,
                          bq: int, bk: int, nk: int, out_dtype):
    """Grid (b, h, qi, ki), ki innermost.  Block shapes (head in lanes):
    q (1,bq,dk) · k (1,bk,dk) · v (1,bk,dv) · o (1,bq,dv)."""
    del groups, sq  # encoded in the index maps / wrapper slicing
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # Tile-level skipping: causal tiles strictly above the diagonal and
    # fully-padded tiles contribute nothing — no DMA'd compute is wasted.
    kv_start = ki * bk
    run = kv_start < skv
    if causal:
        run = jnp.logical_and(run, kv_start <= q_offset + (qi + 1) * bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                                   # (bq, dk)
        # int8 KV codes cast in-register; exact (|code| < 2^8 << bf16 mantissa)
        k = k_ref[0].astype(q.dtype)                   # (bk, dk)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=mxu_precision(q.dtype),
            preferred_element_type=jnp.float32) * score_scale

        kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kv_pos < skv                            # padding mask
        if causal:
            q_pos = (q_offset + qi * bq
                     + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            mask = jnp.logical_and(mask, kv_pos <= q_pos)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_curr = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)               # old-stats correction
        p = jnp.exp(s - m_next)                        # masked entries -> 0.0
        l_scr[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)

        v = v_ref[0].astype(q.dtype)                   # (bk, dv)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(q.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=mxu_precision(q.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _store():
        l = l_scr[:, :1]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)      # fully-masked rows
        o_ref[0] = (acc_scr[...] * l_inv * v_scale).astype(out_dtype)


def make_flash_prefill(b: int, h: int, kvh: int, sq_p: int, skv_p: int,
                       dk_p: int, dv_p: int, *, bq: int, bk: int,
                       causal: bool, q_offset: int, sq: int, skv: int,
                       score_scale: float, v_scale: float, k_dtype,
                       out_dtype, interpret: bool = False):
    """Build the prefill pallas_call.

    Operands are the model's native (B, S, H, D) with heads flattened into
    lanes: q (B, Sq_p, H*dk_p), k (B, Skv_p, KVH*dk_p), v (B, Skv_p,
    KVH*dv_p); output (B, Sq_p, H*dv_p).  The K/V index map contracts the
    GQA grouping (``h // groups``) so grouped heads read the same KV tile
    and nothing is repeated in HBM.  ``sq``/``skv`` are the true
    (unpadded) lengths; ``*_p`` the padded operand shapes.

    ``h``/``kvh`` are PER-SHARD counts: under the shard_map wiring
    (DESIGN §8) each device builds this call for its local slice of the
    head axis, so whole GQA groups must land on one shard — the wrapper
    partitions KV heads, never splits a group.
    """
    del k_dtype
    assert kvh >= 1 and h % kvh == 0, (
        f"(per-shard) query heads ({h}) must be a positive multiple of "
        f"(per-shard) KV heads ({kvh}): the shard_map wrapper may only "
        f"partition whole GQA groups across the tensor axis")
    groups = h // kvh
    nk = skv_p // bk
    kernel = functools.partial(
        _flash_prefill_kernel, groups=groups, score_scale=score_scale,
        v_scale=v_scale, causal=causal, q_offset=q_offset, sq=sq, skv=skv,
        bq=bq, bk=bk, nk=nk, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(b, h, sq_p // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dk_p), lambda b_, h_, qi, ki: (b_, qi, h_)),
            pl.BlockSpec((1, bk, dk_p),
                         lambda b_, h_, qi, ki: (b_, ki, h_ // groups)),
            pl.BlockSpec((1, bk, dv_p),
                         lambda b_, h_, qi, ki: (b_, ki, h_ // groups)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv_p),
                               lambda b_, h_, qi, ki: (b_, qi, h_)),
        out_shape=jax.ShapeDtypeStruct((b, sq_p, h * dv_p), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),   # running max m
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),   # running sum l
            pltpu.VMEM((bq, dv_p), jnp.float32),           # output acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_prefill",
    )


def _flash_decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, score_scale: float,
                         v_scale: float, bk: int, nk: int, out_dtype):
    """Grid (b, kv_head, ki).  One q tile carries all ``groups`` query heads
    of this KV head in its sublane dim — the KV tile is loaded once and
    shared (GQA-aware).  ``pos`` (absolute position of the new token) is a
    traced scalar delivered by scalar prefetch; KV tiles with
    ``kv_start > pos`` are skipped, so decode cost tracks the LIVE sequence
    length, not the allocated cache length."""
    ki = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(ki * bk <= pos)
    def _compute():
        q = q_ref[0, 0]                                # (gp, dk)
        k = k_ref[0].astype(q.dtype)                   # (bk, dk)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=mxu_precision(q.dtype),
            preferred_element_type=jnp.float32) * score_scale

        gp = q.shape[0]
        kv_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (gp, bk), 1)
        s = jnp.where(kv_pos <= pos, s, DEFAULT_MASK_VALUE)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_curr = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_scr[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)

        v = v_ref[0].astype(q.dtype)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(q.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=mxu_precision(q.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _store():
        l = l_scr[:, :1]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[0, 0] = (acc_scr[...] * l_inv * v_scale).astype(out_dtype)


def make_flash_decode(b: int, kvh: int, gp: int, s_max: int, dk_p: int,
                      dv_p: int, *, bk: int, score_scale: float,
                      v_scale: float, out_dtype, interpret: bool = False):
    """Build the decode pallas_call.

    Operands: pos (1,) int32 scalar-prefetch · q (B, KVH, gp, dk) ·
    k/v (B, S_max, KVH*d) — the cache's native layout with heads in
    lanes, indexed in place (no transpose, no dequantized copy).  ``gp``
    is the GQA group count padded to the sublane minimum.  ``kvh`` is the
    PER-SHARD KV head count under the shard_map wiring (DESIGN §8); the
    group structure is shard-invariant, so ``gp`` needs no per-shard
    adjustment.
    """
    assert kvh >= 1 and gp >= 1, (
        f"(per-shard) decode needs at least one KV head and one group "
        f"(got kvh={kvh}, gp={gp}) — the shard_map wrapper must not "
        f"over-partition the head axis")
    nk = s_max // bk
    kernel = functools.partial(
        _flash_decode_kernel, score_scale=score_scale, v_scale=v_scale,
        bk=bk, nk=nk, out_dtype=out_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kvh, nk),
        in_specs=[
            pl.BlockSpec((1, 1, gp, dk_p),
                         lambda b_, h_, ki, pos_ref: (b_, h_, 0, 0)),
            pl.BlockSpec((1, bk, dk_p),
                         lambda b_, h_, ki, pos_ref: (b_, ki, h_)),
            pl.BlockSpec((1, bk, dv_p),
                         lambda b_, h_, ki, pos_ref: (b_, ki, h_)),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, dv_p),
                               lambda b_, h_, ki, pos_ref: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, _STATS_LANES), jnp.float32),
            pltpu.VMEM((gp, _STATS_LANES), jnp.float32),
            pltpu.VMEM((gp, dv_p), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, dv_p), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )


def _paged_flash_decode_kernel(pos_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                               m_scr, l_scr, acc_scr, *, score_scale: float,
                               v_scale: float, bs: int, nb: int, out_dtype):
    """Grid (slot, kv_head, ti).  Identical online softmax to the dense
    decode kernel, except (a) the KV tile for grid step ``ti`` is whatever
    POOL BLOCK the slot's block table names (the index map reads
    ``bt_ref[b, ti]`` — the gather happens in the DMA engine, no gathered
    copy ever exists in HBM), and (b) the mask position is PER-SLOT
    (``pos_ref[b]``), which is what makes continuous batching work: every
    slot in the fixed-width batch decodes at its own sequence length."""
    del bt_ref  # consumed by the index maps
    b = pl.program_id(0)
    ti = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(ti * bs <= pos)
    def _compute():
        q = q_ref[0, 0]                                # (gp, dk)
        k = k_ref[0].astype(q.dtype)                   # (bs, dk) pool block
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=mxu_precision(q.dtype),
            preferred_element_type=jnp.float32) * score_scale

        gp = q.shape[0]
        kv_pos = ti * bs + jax.lax.broadcasted_iota(jnp.int32, (gp, bs), 1)
        s = jnp.where(kv_pos <= pos, s, DEFAULT_MASK_VALUE)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_curr = jnp.max(s, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_scr[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)

        v = v_ref[0].astype(q.dtype)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(q.dtype), v, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=mxu_precision(q.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(ti == nb - 1)
    def _store():
        l = l_scr[:, :1]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[0, 0] = (acc_scr[...] * l_inv * v_scale).astype(out_dtype)


def make_paged_flash_decode(b: int, kvh: int, gp: int, nbmax: int, bs: int,
                            dk_p: int, dv_p: int, *, score_scale: float,
                            v_scale: float, out_dtype,
                            interpret: bool = False):
    """Build the PAGED decode pallas_call (serving engine, DESIGN §9).

    Operands: pos (B,) int32 + block_tables (B, nbmax) int32 (both
    scalar-prefetch) · q (B, KVH, gp, dk) · k/v POOL (NB, bs, KVH*d) — the
    block pool's native layout with heads in lanes.  ``nbmax`` is the
    per-sequence block-table width (grid's KV extent), ``bs`` the pool
    block size; the K/V index maps translate grid step ``ti`` to pool
    block ``bt[b, ti]``, so the
    kernel walks each slot's logical sequence through physically scattered
    blocks with zero gather/copy.  Unallocated table tail entries point at
    the pool's trash block; their tiles are masked by ``pos`` exactly like
    the dense kernel masks the cache tail.  ``kvh`` is the PER-SHARD KV
    head count under shard_map (pool head-sharded, tables/positions
    replicated across the tensor axis — DESIGN §9)."""
    assert kvh >= 1 and gp >= 1, (
        f"(per-shard) paged decode needs at least one KV head and one "
        f"group (got kvh={kvh}, gp={gp})")
    kernel = functools.partial(
        _paged_flash_decode_kernel, score_scale=score_scale, v_scale=v_scale,
        bs=bs, nb=nbmax, out_dtype=out_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, nbmax),
        in_specs=[
            pl.BlockSpec((1, 1, gp, dk_p),
                         lambda b_, h_, ti, pos_ref, bt_ref: (b_, h_, 0, 0)),
            pl.BlockSpec((1, bs, dk_p),
                         lambda b_, h_, ti, pos_ref, bt_ref:
                         (bt_ref[b_, ti], 0, h_)),
            pl.BlockSpec((1, bs, dv_p),
                         lambda b_, h_, ti, pos_ref, bt_ref:
                         (bt_ref[b_, ti], 0, h_)),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, dv_p),
                               lambda b_, h_, ti, pos_ref, bt_ref:
                               (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, _STATS_LANES), jnp.float32),
            pltpu.VMEM((gp, _STATS_LANES), jnp.float32),
            pltpu.VMEM((gp, dv_p), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, dv_p), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_flash_decode",
    )
