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
    per-sequence descriptors, scalar-prefetched:
      q_start (S,)      first stream row of sequence s
      q_len   (S,)      its token count this step (0 = padding slot)
      kv_len  (S,)      its TOTAL visible KV rows after this step
      block_tables (S, NBmax)  logical block -> pool block

The head axes live in the lane dimension, so a q/o block is
``(T_pad, HP*G*D)`` — the ``G = H/KVH`` query heads of ``HP`` KV heads,
contiguous in the lane axis — and both reshapes from the model's
``(T, H, D)`` / ``(NB, bs, KVH, D)`` layouts are free.

Grid ``(KVH/HP, S, ceil(NBmax/P))``: the head axis is parallel, the
sequence and step axes sequential ("arbitrary") because every (s, j)
step revisits the same output block — Pallas keeps it resident in VMEM
for the whole sweep, and each sequence read-modify-writes only its own
disjoint row window, so the packed stream is assembled in place.

The walk visits only live KV.  The pools stay in HBM (``pl.ANY``); each
grid step copies ``P`` pool blocks of its sequence — the ``HP`` KV
heads' lanes of each — with ``pltpu.make_async_copy`` into one half of
a double-buffered VMEM slab, and starts its successor's copies into the
other half before it computes.  Only blocks below ``ceil(kv_len / bs)``
are copied: a step past its sequence's last live block, and every step
of a padding slot (``kv_len == 0``), issues no copy and no compute, and
pays only the grid loop's own cost — which is why ``P`` blocks share a
step: a sequence takes ``ceil(NBmax/P)`` steps, not ``NBmax``.  ``P``, ``HP``
and the compute width come from static shapes alone (``_walk``): ``P``
covers ``_KV_ROWS_PER_STEP`` pool rows, ``HP`` is the most KV heads
whose q/o blocks and (m, l, acc) scratch for the static q window ``tw``
fit ``_RESIDENT_BYTES`` (every head for a decode window, fewer for a
4096-row chunk), and each step's score tile is at most
``_SCORE_BYTES`` wide.  One grid step serves the whole GQA group of
each of its KV heads, so a pool block is copied once per head step,
never once per query head.  int8 Eq.-1 codes dequantize in-register
exactly as in ``flash_attention.py``: K's power-of-two scale folds into
the softmax scale, V's into the final normalization.

Causal masking is derived PER ROW from the descriptors instead of from
the operand shape: stream row ``q_start[s] + i`` is the token at
absolute position ``kv_len[s] - q_len[s] + i``, so

    mask[i, j] = (0 <= i < q_len[s]) and (kv_pos[j] <= position(i))

covers all three traffic classes with one formula — a decode row
(q_len=1) sees its whole context, a prefill chunk gets the staircase,
a speculative tail gets the staircase rooted at the committed context.
The same mask hides the slab rows past ``kv_len`` in a step's last live
block; where a float pool's slab rows were not copied this step they
are zeroed first, so stale VMEM never reaches the PV product.

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
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (_STATS_LANES, DEFAULT_MASK_VALUE,
                                          mxu_precision)

__all__ = ["make_ragged_paged_flash", "ragged_walk_counts"]

_SUBLANES = 8
_VMEM_LIMIT_MAX = 100 << 20     # of the 128 MiB VMEM per v5e/v6e core
_KV_ROWS_PER_STEP = 1024        # pool rows one grid step copies (P * bs)
_SCORE_BYTES = 4 << 20          # widest f32 (tw, kc) score tile
_RESIDENT_BYTES = 64 << 20      # q/o blocks + (m, l, acc) of one head step


class _Walk(NamedTuple):
    tw: int         # static q-window height
    heads: int      # KV heads per grid step (HP)
    blocks: int     # pool blocks per grid step (P)
    steps: int      # grid steps per sequence, ceil(nbmax / P)
    sub: int        # pool blocks per score tile (divides P)


def _walk(h: int, kvh: int, nbmax: int, bs: int, t_pad: int, tq: int,
          dk: int, dv: int) -> _Walk:
    """The kernel's walk, from static shapes alone."""
    groups = h // kvh
    # one extra sublane tile absorbs the offset of q_start above its
    # 8-aligned window start
    tw = min(-(-tq // _SUBLANES) * _SUBLANES + _SUBLANES, t_pad)

    def resident(hp):   # double-buffered q/o blocks (f32 worst case) + scratch
        return (2 * t_pad * hp * groups * (dk + dv) * 4
                + hp * groups * tw * (2 * _STATS_LANES + dv) * 4)

    heads = max([d for d in range(1, kvh + 1) if kvh % d == 0
                 and resident(d) <= _RESIDENT_BYTES] or [1])
    blocks = max(1, min(nbmax, _KV_ROWS_PER_STEP // bs))
    sub = max([c for c in range(1, blocks + 1) if blocks % c == 0
               and tw * c * bs * 4 <= _SCORE_BYTES] or [1])
    return _Walk(tw, heads, blocks, -(-nbmax // blocks), sub)


def ragged_walk_counts(kv_len, *, h: int, kvh: int, nbmax: int, bs: int,
                       t_pad: int, tq: int, dk: int, dv: int):
    """(grid steps, pool blocks copied) of one ``ragged_paged_flash`` call.

    A pure function of the static shapes the factory sees and the
    ``kv_len`` descriptor (host numpy): every sequence slot takes
    ``ceil(nbmax / P)`` steps per head step, and each head step copies
    the ``ceil(kv_len / bs)`` live blocks of each sequence (the K and V
    rows of its heads), none of a padding slot's.  ``tq`` is the static
    q-window bound the call was built with, ``t_pad`` its stream."""
    w = _walk(h, kvh, nbmax, bs, t_pad, tq, dk, dv)
    kl = np.asarray(kv_len, np.int64)
    head_steps = kvh // w.heads
    return (head_steps * kl.shape[0] * w.steps,
            head_steps * int((-(-kl // bs)).sum()))


def _ragged_paged_flash_kernel(qs_ref, ql_ref, kl_ref, bt_ref,
                               q_ref, k_hbm, v_hbm, o_ref,
                               k_buf, v_buf, sem, m_scr, l_scr, acc_scr, *,
                               walk: _Walk, groups: int, n_seq: int,
                               dk: int, dv: int,
                               score_scale: float, v_scale: float, bs: int,
                               nbmax: int, t_pad: int, out_dtype):
    """Grid (head step, seq, j).  Blocks: q (T_pad, HP*G*dk) / o
    (T_pad, HP*G*dv) — the whole packed stream for HP GQA groups,
    revisited across (seq, j).  k/v stay in HBM; ``k_buf``/``v_buf``
    (2, P*bs, HP*d) hold the two halves of the copy slab, ``sem`` one
    DMA semaphore per (half, K|V, block).  Scratch carries one
    (m, l, acc) set per query head of the step."""
    tw, hp, p_blk, nj, sub = walk
    hg = pl.program_id(0)
    s_ = pl.program_id(1)
    j = pl.program_id(2)
    idx = s_ * nj + j
    slot = idx % 2

    def n_live(seq, step):
        """Pool blocks of ``seq`` that grid step ``step`` copies."""
        nblk = (kl_ref[seq] + bs - 1) // bs
        return jnp.clip(nblk - step * p_blk, 0, p_blk)

    def block_copies(seq, step, half, b):
        blk = bt_ref[seq, jnp.minimum(step * p_blk + b, nbmax - 1)]
        rows = pl.ds(b * bs, bs)
        # the lanes of this head step's HP KV heads (all of them at HP = KVH)
        k_src = k_hbm.at[blk, :, pl.ds(hg * (hp * dk), hp * dk)]
        v_src = v_hbm.at[blk, :, pl.ds(hg * (hp * dv), hp * dv)]
        return (pltpu.make_async_copy(k_src, k_buf.at[half, rows],
                                      sem.at[half, 0, b]),
                pltpu.make_async_copy(v_src, v_buf.at[half, rows],
                                      sem.at[half, 1, b]))

    def each_copy(seq, step, half, act):
        """``act`` on the K and V copies of the blocks a step copies."""
        def body(b, carry):
            for c in block_copies(seq, step, half, b):
                act(c)
            return carry
        jax.lax.fori_loop(0, n_live(seq, step), body, 0)

    def start(seq, step, half):
        each_copy(seq, step, half, lambda c: c.start())

    # the first step of a head step has no predecessor to prefetch for it
    @pl.when(idx == 0)
    def _prologue():
        start(s_, j, slot)

    # start the successor's copies into the other half before computing
    nxt_last = j == nj - 1
    nxt_s = jnp.where(nxt_last, s_ + 1, s_)

    @pl.when(nxt_s < n_seq)
    def _prefetch():
        start(jnp.minimum(nxt_s, n_seq - 1), jnp.where(nxt_last, 0, j + 1),
              1 - slot)

    each_copy(s_, j, slot, lambda c: c.wait())
    n = n_live(s_, j)

    if jnp.issubdtype(v_buf.dtype, jnp.floating):
        # slab rows this step did not copy hold whatever VMEM held: the
        # mask hides them from the scores, zeros keep NaN out of p @ v
        def zero(b, carry):
            v_buf[slot, pl.ds(pl.multiple_of(b * bs, bs), bs)] = jnp.zeros(
                (bs, hp * dv), v_buf.dtype)
            return carry
        jax.lax.fori_loop(jnp.where(n > 0, n, p_blk), p_blk, zero, 0)

    qs = qs_ref[s_]
    ql = ql_ref[s_]
    kl = kl_ref[s_]
    seq_live = kl > 0
    # static-height window at an 8-aligned start, clamped so it never
    # runs past the stream; ``off`` is where the sequence's row 0 lands
    start_row = pl.multiple_of(
        jnp.minimum(qs // _SUBLANES * _SUBLANES, t_pad - tw), _SUBLANES)
    off = qs - start_row

    @pl.when(jnp.logical_and(j == 0, seq_live))
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    kc = sub * bs

    def tile(u, carry):
        """One score tile: ``sub`` pool blocks starting at block u*sub."""
        # descriptor-derived causal mask: window row i is the sequence's
        # local token ``i - off`` at absolute position kv_len - q_len + local
        local = jax.lax.broadcasted_iota(jnp.int32, (tw, kc), 0) - off
        kv_pos = ((j * p_blk + u * sub) * bs
                  + jax.lax.broadcasted_iota(jnp.int32, (tw, kc), 1))
        live = jnp.logical_and(jnp.logical_and(local >= 0, local < ql),
                               kv_pos <= kl - ql + local)
        rows = pl.ds(pl.multiple_of(u * kc, kc), kc)

        def head(kh, carry):
            k = k_buf[slot, rows, pl.ds(pl.multiple_of(kh * dk, dk), dk)]
            v = v_buf[slot, rows, pl.ds(pl.multiple_of(kh * dv, dv), dv)]
            k, v = k.astype(q_ref.dtype), v.astype(q_ref.dtype)
            for g in range(groups):                     # static, G = H/KVH
                hq = kh * groups + g
                q = q_ref[pl.ds(start_row, tw),
                          pl.ds(pl.multiple_of(hq * dk, dk), dk)]
                s = jax.lax.dot_general(
                    q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                    precision=mxu_precision(q.dtype),
                    preferred_element_type=jnp.float32) * score_scale
                s = jnp.where(live, s, DEFAULT_MASK_VALUE)

                m_prev = m_scr[hq, :, :1]
                l_prev = l_scr[hq, :, :1]
                m_next = jnp.maximum(m_prev,
                                     jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.exp(s - m_next)
                l_scr[hq] = jnp.broadcast_to(
                    l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                    (tw, _STATS_LANES))
                m_scr[hq] = jnp.broadcast_to(m_next, (tw, _STATS_LANES))
                acc_scr[hq] = acc_scr[hq] * alpha + jax.lax.dot_general(
                    p.astype(v.dtype), v,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    precision=mxu_precision(v.dtype),
                    preferred_element_type=jnp.float32)
            return carry

        return jax.lax.fori_loop(0, hp, head, carry)

    # only the tiles that hold live blocks: none on a dead step
    jax.lax.fori_loop(0, (n + sub - 1) // sub, tile, 0)

    @pl.when(jnp.logical_and(j == nj - 1, seq_live))
    def _store():
        local = jax.lax.broadcasted_iota(jnp.int32, (tw, 1), 0) - off
        valid = jnp.logical_and(local >= 0, local < ql)

        def store(hq, carry):
            l = l_scr[hq, :, :1]
            l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
            vals = (acc_scr[hq] * l_inv * v_scale).astype(out_dtype)
            # masked read-modify-write: window rows of OTHER sequences (the
            # windows of adjacent short sequences overlap) keep their values
            rows = (pl.ds(start_row, tw),
                    pl.ds(pl.multiple_of(hq * dv, dv), dv))
            o_ref[rows] = jnp.where(valid, vals, o_ref[rows])
            return carry
        jax.lax.fori_loop(0, hp * groups, store, 0)


def make_ragged_paged_flash(s: int, h: int, kvh: int, nbmax: int, bs: int,
                            t_pad: int, tq: int, dk: int, dv: int, *,
                            score_scale: float, v_scale: float, out_dtype,
                            interpret: bool = False):
    """Build the unified ragged call.

    Operands: q_start/q_len/kv_len (S,) + block_tables (S, NBmax), all
    int32 scalar-prefetch · q (T_pad, H*dk) · k/v POOL (NB, bs, KVH*d).
    Output (T_pad, H*dv) — packed like q; rows covered by no descriptor
    are left unwritten (the wrapper zeroes them).  The returned function
    builds the ``pallas_call`` for its pools' dtype (the copy slab's).

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
    walk = _walk(h, kvh, nbmax, bs, t_pad, tq, dk, dv)
    hp = walk.heads
    kernel = functools.partial(
        _ragged_paged_flash_kernel, walk=walk, groups=groups, n_seq=s,
        dk=dk, dv=dv, score_scale=score_scale, v_scale=v_scale,
        bs=bs, nbmax=nbmax, t_pad=t_pad, out_dtype=out_dtype)

    def stream_map(hg, s_, j, qs, ql, kl, bt):
        return (0, hg)

    def call(q_start, q_len, kv_len, block_tables, q, k_pool, v_pool):
        kv_dtype = k_pool.dtype
        rows = walk.blocks * bs
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(kvh // hp, s, walk.steps),
            in_specs=[
                pl.BlockSpec((t_pad, hp * groups * dk), stream_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((t_pad, hp * groups * dv), stream_map),
            scratch_shapes=[
                pltpu.VMEM((2, rows, hp * dk), kv_dtype),      # K slab
                pltpu.VMEM((2, rows, hp * dv), kv_dtype),      # V slab
                pltpu.SemaphoreType.DMA((2, 2, walk.blocks)),
                pltpu.VMEM((hp * groups, walk.tw, _STATS_LANES),
                           jnp.float32),                       # max m
                pltpu.VMEM((hp * groups, walk.tw, _STATS_LANES),
                           jnp.float32),                       # sum l
                pltpu.VMEM((hp * groups, walk.tw, dv), jnp.float32),  # acc
            ],
        )
        # the stream blocks stay resident for the whole sweep (double-
        # buffered), the scratch grows with the q window, the score
        # temporaries with the tile.  Wide chunks (tq in the thousands)
        # outgrow the default scoped VMEM limit.
        item = jnp.dtype(q.dtype).itemsize
        kc = walk.sub * bs
        vmem = (2 * t_pad * hp * groups * (dk + dv) * item
                + 2 * rows * hp * (dk + dv) * jnp.dtype(kv_dtype).itemsize
                + hp * groups * walk.tw * (2 * _STATS_LANES + dv) * 4
                + 4 * walk.tw * kc * 4 + kc * (dk + dv) * item)
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((t_pad, h * dv), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=min(max(2 * vmem, 32 << 20),
                                     _VMEM_LIMIT_MAX)),
            interpret=interpret,
            name="ragged_paged_flash",
        )(q_start, q_len, kv_len, block_tables, q, k_pool, v_pool)

    return call
