"""Unified RAGGED paged flash attention: one pallas_call per mixed step.

The serving engine's step traffic is inherently mixed — some slots are
mid-prefill (a chunk of C tokens), some are decoding (1 token), some are
verifying a speculative tail (1 + K tokens).  Dispatching each class at
its own padded shape costs three executables per step, pow2 bucket
padding, and serialized phases (DESIGN §9/§11).  This kernel serves the
whole step in ONE dispatch over a FLATTENED token stream:

    q:   (T_pad, H*Dk)       — all live tokens of every class, packed,
                               heads flattened into the lane axis
    out: (T_pad, H*Dv)
    k/v: (NB, bs, KVH*D)     — the block pool, KV heads in the lane axis
    per-sequence descriptors, scalar-prefetched like the paged decode
    kernel's (positions, block table):
      q_start (S,)      first stream row of sequence s
      q_len   (S,)      its token count this step (0 = padding slot)
      kv_len  (S,)      its TOTAL visible KV rows after this step
      block_tables (S, NBmax)  logical block -> pool block

The head axes live in the lane dimension so every block's last two dims
are TPU-tileable: a q/o block is ``(T_pad, G*D)`` (the ``G = H/KVH``
query heads of one KV head, contiguous in the lane axis) and a K/V block
is ``(1, bs, D)`` — one KV head of one pool block.  Both reshapes from
the model's ``(T, H, D)`` / ``(NB, bs, KVH, D)`` layouts are free.

Grid (KVH, S, NBmax): the KV-head axis is parallel; the sequence and
block-table axes are sequential ("arbitrary") because every (s, ti)
step revisits the same output block — Pallas keeps it resident in VMEM
for the whole sweep, and each sequence read-modify-writes only its own
disjoint row window, so the packed stream is assembled in place.  One
grid step serves the whole GQA group of its KV head, so each pool block
is DMA'd once per KV head, never once per query head.  The K/V index
maps are the paged-decode gather (``bt_ref[s, ti]`` — the block walk
happens in the DMA engine), clamped to the sequence's last live block:
table-tail steps repeat the previous block index, so Pallas issues no
DMA for them.  int8 Eq.-1 codes dequantize in-register exactly as in
``flash_attention.py``: K's power-of-two scale folds into the softmax
scale, V's into the final normalization.

Causal masking is derived PER ROW from the descriptors instead of from
the operand shape: stream row ``q_start[s] + i`` is the token at
absolute position ``kv_len[s] - q_len[s] + i``, so

    mask[i, j] = (0 <= i < q_len[s]) and (kv_pos[j] <= position(i))

covers all three traffic classes with one formula — a decode row
(q_len=1) sees its whole context, a prefill chunk gets the staircase,
a speculative tail gets the staircase rooted at the committed context.

The q window per sequence is a STATIC ``tw`` rows wide (max per-sequence
q_len plus one sublane tile), starting at ``q_start`` rounded down to
the sublane size (Mosaic needs provably 8-aligned dynamic row offsets)
and clamped to the stream end; rows of the window outside
``[q_start, q_start + q_len)`` are fully masked and their output write
is suppressed (read-modify-write keeps neighbouring sequences' rows).
Stream rows not covered by ANY descriptor are never written — the ops
wrapper zeroes them after the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (_STATS_LANES, DEFAULT_MASK_VALUE,
                                          mxu_precision)

__all__ = ["make_ragged_paged_flash"]

_SUBLANES = 8
_VMEM_LIMIT_MAX = 100 << 20     # of the 128 MiB VMEM per v5e/v6e core


def _ragged_paged_flash_kernel(qs_ref, ql_ref, kl_ref, bt_ref,
                               q_ref, k_ref, v_ref, o_ref,
                               m_scr, l_scr, acc_scr, *, groups: int,
                               dk: int, dv: int, score_scale: float,
                               v_scale: float, bs: int, nbmax: int, tw: int,
                               t_pad: int, out_dtype):
    """Grid (kv_head, seq, ti).  Blocks: q (T_pad, G*dk) / o (T_pad, G*dv)
    — the whole packed stream for one GQA group, revisited across
    (seq, ti); k/v (1, bs, d) — this KV head of the pool block named by
    ``bt_ref[s, ti]``.  Scratch carries one (m, l, acc) set per group."""
    del bt_ref  # consumed by the index maps
    s_ = pl.program_id(1)
    ti = pl.program_id(2)
    qs = qs_ref[s_]
    ql = ql_ref[s_]
    kl = kl_ref[s_]
    # static-height window at an 8-aligned start, clamped so it never
    # runs past the stream; ``off`` is where the sequence's row 0 lands
    start = pl.multiple_of(
        jnp.minimum(qs // _SUBLANES * _SUBLANES, t_pad - tw), _SUBLANES)
    off = qs - start

    @pl.when(ti == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    # skip table-tail blocks (ti*bs >= kv_len) and padding slots
    @pl.when(jnp.logical_and(ti * bs < kl, ql > 0))
    def _compute():
        k = k_ref[0].astype(q_ref.dtype)                # (bs, dk) pool block
        v = v_ref[0].astype(q_ref.dtype)                # (bs, dv)
        # descriptor-derived causal mask: window row i is the sequence's
        # local token ``i - off`` at absolute position kv_len - q_len + local
        local = jax.lax.broadcasted_iota(jnp.int32, (tw, bs), 0) - off
        kv_pos = ti * bs + jax.lax.broadcasted_iota(jnp.int32, (tw, bs), 1)
        live = jnp.logical_and(jnp.logical_and(local >= 0, local < ql),
                               kv_pos <= kl - ql + local)
        for g in range(groups):                         # static, G = H/KVH
            q = q_ref[pl.ds(start, tw), pl.ds(g * dk, dk)]   # (tw, dk)
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                precision=mxu_precision(q.dtype),
                preferred_element_type=jnp.float32) * score_scale
            s = jnp.where(live, s, DEFAULT_MASK_VALUE)

            m_prev = m_scr[g, :, :1]
            l_prev = l_scr[g, :, :1]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)
            l_scr[g] = jnp.broadcast_to(
                l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                (tw, _STATS_LANES))
            m_scr[g] = jnp.broadcast_to(m_next, (tw, _STATS_LANES))
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=mxu_precision(v.dtype),
                preferred_element_type=jnp.float32)

    @pl.when(ti == nbmax - 1)
    def _store():
        local = jax.lax.broadcasted_iota(jnp.int32, (tw, 1), 0) - off
        valid = jnp.logical_and(local >= 0, local < ql)
        for g in range(groups):
            l = l_scr[g, :, :1]
            l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
            vals = (acc_scr[g] * l_inv * v_scale).astype(out_dtype)
            # masked read-modify-write: window rows of OTHER sequences (the
            # windows of adjacent short sequences overlap) keep their values
            rows = (pl.ds(start, tw), pl.ds(g * dv, dv))
            o_ref[rows] = jnp.where(valid, vals, o_ref[rows])


def make_ragged_paged_flash(s: int, h: int, kvh: int, nbmax: int, bs: int,
                            t_pad: int, tq: int, dk: int, dv: int, *,
                            score_scale: float, v_scale: float, out_dtype,
                            interpret: bool = False):
    """Build the unified ragged pallas_call.

    Operands: q_start/q_len/kv_len (S,) + block_tables (S, NBmax), all
    int32 scalar-prefetch · q (T_pad, H*dk) · k/v POOL (NB, bs, KVH*d).
    Output (T_pad, H*dv) — packed like q; rows covered by no descriptor
    are left unwritten (the wrapper zeroes them).

    Contract (callers build descriptors host-side): ``q_start`` is
    nondecreasing with ``q_start + q_len <= t_pad`` per sequence, row
    windows ``[q_start, q_start + q_len)`` are pairwise disjoint, every
    ``q_len <= tq``, padding slots carry ``q_len == kv_len == 0`` with
    trash-block tables.  ``h``/``kvh`` are PER-SHARD counts under the §8
    shard_map wiring — whole GQA groups per shard, same as the other
    flash kernels.  ``dk``/``dv`` must be lane multiples (128) unless the
    pool holds a single KV head.
    """
    assert kvh >= 1 and h % kvh == 0, (
        f"(per-shard) query heads ({h}) must be a positive multiple of "
        f"(per-shard) KV heads ({kvh})")
    assert 1 <= tq <= t_pad and t_pad % _SUBLANES == 0, (
        f"q window {tq} must fit the sublane-padded stream {t_pad}")
    groups = h // kvh
    # static q-window height: one extra sublane tile absorbs the offset
    # of q_start above its 8-aligned window start
    tw = min(-(-tq // _SUBLANES) * _SUBLANES + _SUBLANES, t_pad)
    kernel = functools.partial(
        _ragged_paged_flash_kernel, groups=groups, dk=dk, dv=dv,
        score_scale=score_scale, v_scale=v_scale, bs=bs, nbmax=nbmax, tw=tw,
        t_pad=t_pad, out_dtype=out_dtype)

    def kv_map(kh, s_, ti, qs, ql, kl, bt):
        # clamp to the last live block: repeated indices skip the DMA
        last = jnp.maximum(kl[s_] - 1, 0) // bs
        return (bt[s_, jnp.minimum(ti, last)], 0, kh)

    def stream_map(kh, s_, ti, qs, ql, kl, bt):
        return (0, kh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(kvh, s, nbmax),
        in_specs=[
            pl.BlockSpec((t_pad, groups * dk), stream_map),
            pl.BlockSpec((1, bs, dk), kv_map),
            pl.BlockSpec((1, bs, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((t_pad, groups * dv), stream_map),
        scratch_shapes=[
            pltpu.VMEM((groups, tw, _STATS_LANES), jnp.float32),  # max m
            pltpu.VMEM((groups, tw, _STATS_LANES), jnp.float32),  # sum l
            pltpu.VMEM((groups, tw, dv), jnp.float32),            # acc
        ],
    )
    # the stream blocks stay resident for the whole sweep (double-
    # buffered); the scratch grows with the q window.  Wide chunks
    # (tq in the thousands) outgrow the default scoped VMEM limit.
    item = jnp.dtype(out_dtype).itemsize
    vmem = (2 * t_pad * groups * (dk + dv) * item
            + 2 * 2 * bs * (dk + dv) * 2
            + groups * tw * (2 * _STATS_LANES + dv) * 4)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, h * dv), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=min(max(2 * vmem, 32 << 20), _VMEM_LIMIT_MAX)),
        interpret=interpret,
        name="ragged_paged_flash",
    )
