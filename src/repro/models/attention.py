"""Attention layers: GQA (+qk_norm) and DeepSeek MLA, with chunked
(flash-style) softmax for long sequences and int8-quantized projections.

Quantization points (DESIGN §3): all projection matmuls run through
``qlinear`` (paper's unified modules); softmax / rope / norms stay in
fp32/bf16 — the paper likewise never quantizes its softmax.

Memory discipline: full-sequence attention materializes (B,H,S,S); at
S=32k that is petabytes.  ``chunked_attention`` scans over KV chunks with
an online softmax so the live tile is (B,H,qc,kc) — the pure-JAX analogue
of a flash kernel, and what makes the prefill_32k dry-run cells fit.

With ``cfg.attn_kernel = 'flash'`` the hot paths route through the fused
Pallas kernels in ``repro.kernels.flash_attention`` (DESIGN §2): int8
KV-cache codes are loaded straight into VMEM and bit-shift dequantized
in-register, so the bf16 cache copy and the HBM score round-trips
disappear.  ``chunked_attention`` stays the reference oracle and the
fallback.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from repro.models.scan_lib import scan as _scan

from repro.configs.base import MLAConfig, ModelConfig
from repro.core.qmodel import QuantContext
from repro.distributed.sharding import constrain, current_mesh
from repro.models.common import apply_rope, linear, rmsnorm

__all__ = ["KVCache", "MLACache", "PagedKVCache", "RaggedBatch", "init_gqa",
           "gqa_attention", "init_mla", "mla_attention", "chunked_attention"]


class KVCache(NamedTuple):
    k: jax.Array        # (B, S_max, KVH, D)
    v: jax.Array        # (B, S_max, KVH, D)


class MLACache(NamedTuple):
    c_kv: jax.Array     # (B, S_max, kv_lora)  — compressed latent
    k_pe: jax.Array     # (B, S_max, rope_dim) — shared rope key


class PagedKVCache(NamedTuple):
    """Serving-engine KV block pool (DESIGN §9): ALL slots' KV lives in one
    pool of fixed-size blocks; per-slot block tables (passed alongside, not
    stored here — they are host-managed ints) map logical block i to a pool
    block.  int8 Eq.-1 codes are written ONCE at their token's step and
    never requantized; block 0 is the trash block inactive slots write to.
    """
    k: jax.Array        # (NB, BS, KVH, D) — int8 codes or model dtype
    v: jax.Array        # (NB, BS, KVH, D)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["dest", "block_tables", "q_start", "q_len",
                                "kv_len"],
                   meta_fields=["tq_max"])
@dataclasses.dataclass(frozen=True)
class RaggedBatch:
    """One MIXED serving step as a flattened token stream (DESIGN §12).

    Prefill chunks, decode rows, and speculative tails of every live slot
    are packed back to back into one (T,) stream; each sequence ``s``
    owns stream rows ``[q_start[s], q_start[s] + q_len[s])`` and sees
    ``kv_len[s]`` total KV rows.  ``dest`` is the host-precomputed
    flattened pool row (``block * block_size + pos % block_size``) each
    token's KV codes scatter to — padding rows point at the trash block.
    All arrays are int32; descriptors follow the contract in
    ``kernels.ragged_flash`` (q_start nondecreasing, windows disjoint,
    padding slots zeroed with trash-block tables).  ``tq_max`` is STATIC
    (part of the pytree structure, so jit specializes on it): the bound
    on any sequence's ``q_len`` that sizes the kernel's per-sequence q
    window — None means the whole stream.
    """
    dest: jax.Array          # (T,)       flattened pool row per token
    block_tables: jax.Array  # (S, NBmax) logical block -> pool block
    q_start: jax.Array       # (S,)
    q_len: jax.Array         # (S,)
    kv_len: jax.Array        # (S,)
    tq_max: Optional[int] = None


# ---------------------------------------------------------------------------
# chunked online-softmax attention (flash-style, pure JAX)
# ---------------------------------------------------------------------------

def _repeat_kv(x: jax.Array, groups: int) -> jax.Array:
    """Expand KV heads for the pure-JAX fallback via broadcast-reshape.

    ``jnp.repeat`` lowers to a gather that materializes a ``groups``x copy
    of the cache in HBM; the broadcast of a size-1 axis is free until the
    reshape, which XLA fuses into the consuming dot.  Head order matches
    ``jnp.repeat(x, groups, axis=2)`` (each KV head's group is contiguous).
    """
    if groups == 1:
        return x
    b, s, h, d = x.shape
    x = jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, groups, d))
    return x.reshape(b, s, h * groups, d)


import functools as _functools


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, kv_chunk: int = 1024,
                      q_offset: int = 0, scale: Optional[float] = None
                      ) -> jax.Array:
    """q: (B,Sq,H,Dk), k: (B,Skv,H,Dk), v: (B,Skv,H,Dv) -> (B,Sq,H,Dv).

    checkpoint'd (flash-attention style): the backward recomputes chunk
    scores/probabilities instead of saving per-chunk masks and p — saving
    them costs O(Sq * Skv / kv_chunk) stacked buffers under the chunk scan
    (observed 5 GB/device of pred masks alone at 4k train).  Decode calls
    (traced q_offset, no grad) skip the checkpoint wrapper.
    """
    if isinstance(q_offset, jax.Array):      # decode path: no backward
        return _chunked_attention(q, k, v, causal=causal, kv_chunk=kv_chunk,
                                  q_offset=q_offset, scale=scale)
    f = jax.checkpoint(_functools.partial(
        _chunked_attention, causal=causal, kv_chunk=kv_chunk,
        q_offset=q_offset, scale=scale))
    return f(q, k, v)


def _chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool, kv_chunk: int = 1024,
                       q_offset: int = 0, scale: Optional[float] = None
                       ) -> jax.Array:
    """Scans KV in chunks carrying (running max, denominator, weighted sum);
    exact softmax, O(Sq * kv_chunk) live memory.  ``q_offset`` is the
    absolute position of q[0] for causal masking (decode: S_past)."""
    b, sq, h, dk = q.shape
    skv = k.shape[1]
    dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    # cap the chunk count at 64: beyond that the scan overhead dominates
    # (and analysis unrolling would blow up the HLO for 512k decode)
    kv_chunk = max(kv_chunk, -(-skv // 64))
    kv_chunk = min(-(-kv_chunk // 128) * 128, skv)
    n_chunks = (skv + kv_chunk - 1) // kv_chunk
    pad = n_chunks * kv_chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # operands stay bf16 (no full-tensor f32 copies — they become stacked
    # f32 buffers under scan); all score/accumulator math is f32 via
    # preferred_element_type on the dots.
    qf = q.transpose(0, 2, 1, 3)             # (B,H,Sq,Dk)
    kc = k.reshape(b, n_chunks, kv_chunk, h, dk)
    vc = v.reshape(b, n_chunks, kv_chunk, h, dv)
    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inputs):
        m, l, acc = carry
        idx, k_blk, v_blk = inputs           # (B,kc,H,Dk) / (B,kc,H,Dv)
        kT = k_blk.transpose(0, 2, 3, 1)     # (B,H,Dk,kc)
        s = jnp.einsum("bhqd,bhdk->bhqk", qf, kT,
                       preferred_element_type=jnp.float32) * scale
        kv_pos = idx * kv_chunk + jnp.arange(kv_chunk)
        mask = kv_pos[None, :] < skv         # padding mask (1,kc)
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> use 0
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        # masked entries have s = -inf => exp yields exactly 0, so no second
        # mask pass is needed (saves a full (B,H,Sq,kc) f32 read+write per
        # chunk — §Perf iteration A)
        p = jnp.exp(s - safe_m[..., None])
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkv->bhqv", p.astype(v_blk.dtype),
            v_blk.transpose(0, 2, 1, 3),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = _scan(
        step, (m0, l0, a0),
        (jnp.arange(n_chunks), kc.transpose(1, 0, 2, 3, 4),
         vc.transpose(1, 0, 2, 3, 4)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Sq,H,Dv)


def _direct_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             q_offset) -> jax.Array:
    """Single-token attention over the full cache, no chunk scan.

    q: (B,1,H,D); k/v: (B,S,KVH,D) — the GQA grouping is contracted
    in-place (no `_repeat_kv` materialization: repeating a seq-sharded
    cache forces an involuntary GSPMD rematerialization, measured 2.1 GB
    f32 per layer).  Scores stay sequence-sharded; softmax/value
    reductions lower to (B,H,1)-sized stat psums (context-parallel
    decode).
    """
    b, s, kvh, dk = k.shape
    h = q.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(q.shape[-1])
    # replicate the (tiny) q over model so the score einsum computes on the
    # sequence-sharded cache IN PLACE; otherwise GSPMD keeps q's head
    # sharding and all-gathers the multi-GB cache instead.
    q = constrain(q, ("batch", None, None, None))
    qg = q.reshape(b, 1, kvh, g, dk)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                    preferred_element_type=jnp.float32) * scale
    sc = constrain(sc, ("batch", None, None, None, "model"))
    kv_pos = jnp.arange(s)
    mask = kv_pos[None, None, None, None, :] <= q_offset
    sc = jnp.where(mask, sc, -jnp.inf)
    # softmax over the sharded axis: max/sum lower to (B,H,1) stat psums
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    # pin the TINY output replicated: otherwise the downstream heads
    # constraint propagates INTO this einsum and reshards the multi-GB v
    # (involuntary GSPMD remat); resharding (B,1,H,D) instead is free.
    out = constrain(out, ("batch", None, None, None, None))
    return out.reshape(b, 1, h, dk).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA (qwen3 / llama / whisper / chameleon / zamba2-shared)
# ---------------------------------------------------------------------------

def init_gqa(init, cfg: ModelConfig, prefix: str = "attn") -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    p = {
        "wq": init.dense((d, cfg.n_heads * hd)),
        "wk": init.dense((d, cfg.n_kv_heads * hd)),
        "wv": init.dense((d, cfg.n_kv_heads * hd)),
        "wo": init.dense((cfg.n_heads * hd, d)),
    }
    if cfg.attn_bias:
        p["bq"] = init.zeros((cfg.n_heads * hd,))
        p["bk"] = init.zeros((cfg.n_kv_heads * hd,))
        p["bv"] = init.zeros((cfg.n_kv_heads * hd,))
    if cfg.qk_norm:
        p["q_norm"] = init.ones((hd,))
        p["k_norm"] = init.ones((hd,))
    return p


def gqa_attention(ctx: QuantContext, p: dict, x: jax.Array, cfg: ModelConfig,
                  *, positions: jax.Array, cache: Optional[KVCache] = None,
                  cache_pos: Optional[jax.Array] = None,
                  causal: bool = True, kv_x: Optional[jax.Array] = None,
                  use_rope: bool = True, kv_chunk: int = 1024,
                  block_tables: Optional[jax.Array] = None,
                  ragged: Optional[RaggedBatch] = None,
                  name: str = "attn") -> tuple[jax.Array, Optional[KVCache]]:
    """GQA with optional qk_norm, KV cache (decode) and cross-attn (kv_x).

    cache semantics: if ``cache`` is given, new K/V are written at
    ``cache_pos`` (scalar step index) and attention runs over the full
    cache (decode); otherwise attention is over the local sequence.

    Paged serving (DESIGN §9): with ``cache`` a :class:`PagedKVCache` the
    new K/V codes are scattered into the block pool through
    ``block_tables`` at per-token absolute positions ``cache_pos`` (shape
    (B, S) — continuous batching decodes every slot at its own position)
    and attention runs over the pool via ``ops.paged_attention``.

    Unified ragged serving (DESIGN §12): with ``cache`` a
    :class:`PagedKVCache` and ``ragged`` a :class:`RaggedBatch`, ``x`` is
    the whole MIXED step as one (1, T, d) stream; codes scatter via the
    precomputed ``ragged.dest`` rows and attention runs in ONE
    ``ops.ragged_attention`` dispatch for every traffic class at once.
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    src = x if kv_x is None else kv_x

    with jax.named_scope("attn_qkv"):
        q = linear(ctx, f"{name}/wq", x, p["wq"], p.get("bq"))
        k = linear(ctx, f"{name}/wk", src, p["wk"], p.get("bk"))
        # wo's input grid is THREADED from wv's output grid (DESIGN §13,
        # lm_calibrate.DATAFLOW_CHAIN): attention rows are softmax-convex
        # combinations of V rows, so the wo input lives inside wv's range.
        v = linear(ctx, f"{name}/wv", src, p["wv"], p.get("bv"))
        q = constrain(q.reshape(b, s, h, hd), ("batch", None, "heads", None))
        k = constrain(k.reshape(b, src.shape[1], kvh, hd),
                      ("batch", None, "heads", None))
        v = constrain(v.reshape(b, src.shape[1], kvh, hd),
                      ("batch", None, "heads", None))

        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            kv_positions = positions if kv_x is None \
                else jnp.arange(src.shape[1])[None]
            k = apply_rope(k, kv_positions, cfg.rope_theta)

    if isinstance(cache, PagedKVCache) and ragged is not None:
        # unified ragged path (DESIGN §12): the batch IS one flattened
        # (1, T) stream mixing prefill chunks, decode rows, and spec
        # tails.  Quantize once, scatter each token's codes to its
        # host-precomputed pool row (padding rows land in the trash
        # block), then attend in ONE ragged dispatch.
        assert b == 1, "ragged serving flattens the batch to (1, T)"
        nb_pool, bs_blk = cache.k.shape[0], cache.k.shape[1]
        kv_frac_bits = None
        with jax.named_scope("kv_write"):
            if cache.k.dtype == jnp.int8:
                from repro.core.qscheme import quant
                kv_frac_bits = cfg.kv_cache_frac_bits
                k_c = quant(k, kv_frac_bits, 8)
                v_c = quant(v, kv_frac_bits, 8)
            else:
                k_c, v_c = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
            k_pool = cache.k.reshape(nb_pool * bs_blk, kvh, hd).at[
                ragged.dest].set(k_c.reshape(-1, kvh, hd)).reshape(
                    cache.k.shape)
            v_pool = cache.v.reshape(nb_pool * bs_blk, kvh, hd).at[
                ragged.dest].set(v_c.reshape(-1, kvh, hd)).reshape(
                    cache.v.shape)
        from repro.kernels import ops as kops
        with jax.named_scope("attention"):
            out = kops.ragged_attention(
                q[0], k_pool, v_pool, ragged.block_tables, ragged.q_start,
                ragged.q_len, ragged.kv_len, kv_frac_bits=kv_frac_bits,
                tq_max=ragged.tq_max, mesh=current_mesh(),
                shard_axis=cfg.attn_shard_axis)[None]
        with jax.named_scope("attn_out"):
            out = constrain(out.reshape(b, s, h * hd),
                            ("batch", None, "heads"))
            out = linear(ctx, f"{name}/wo", out, p["wo"])
        return out, PagedKVCache(k_pool, v_pool)

    if isinstance(cache, PagedKVCache):
        # serving-engine paged path (DESIGN §9): quantize ONCE, scatter the
        # codes into the slot's pool blocks at their absolute positions,
        # then attend over the pool.  ``cache_pos`` is (B, S): each slot in
        # the fixed-width batch is at its OWN live length (decode, S=1) or
        # its chunk's position range (chunked prefill, S=chunk).
        assert block_tables is not None and cache_pos is not None
        nb_pool, bs_blk = cache.k.shape[0], cache.k.shape[1]
        kv_frac_bits = None
        if cache.k.dtype == jnp.int8:
            from repro.core.qscheme import quant
            kv_frac_bits = cfg.kv_cache_frac_bits
            k_c, v_c = quant(k, kv_frac_bits, 8), quant(v, kv_frac_bits, 8)
        else:
            k_c, v_c = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
        blk = jnp.take_along_axis(block_tables, cache_pos // bs_blk, axis=1)
        idx = (blk * bs_blk + cache_pos % bs_blk).reshape(-1)    # (B*S,)
        k_pool = cache.k.reshape(nb_pool * bs_blk, kvh, hd).at[idx].set(
            k_c.reshape(-1, kvh, hd)).reshape(cache.k.shape)
        v_pool = cache.v.reshape(nb_pool * bs_blk, kvh, hd).at[idx].set(
            v_c.reshape(-1, kvh, hd)).reshape(cache.v.shape)
        from repro.kernels import ops as kops
        out = kops.paged_attention(q, k_pool, v_pool, block_tables,
                                   cache_pos, kv_frac_bits=kv_frac_bits,
                                   mesh=current_mesh(),
                                   shard_axis=cfg.attn_shard_axis)
        out = constrain(out.reshape(b, s, h * hd), ("batch", None, "heads"))
        return (linear(ctx, f"{name}/wo", out, p["wo"]),
                PagedKVCache(k_pool, v_pool))

    # 'flash' routes the hot paths through the fused Pallas kernel
    # (DESIGN §2): int8 KV codes are read straight into VMEM and bit-shift
    # dequantized in-register, so the bf16 cache copy below is skipped.
    use_flash = cfg.attn_kernel == "flash"
    kv_frac_bits = None

    new_cache = None
    q_offset = 0
    if cache is not None:
        if cache.k.dtype == jnp.int8:
            # int8 KV cache: write Eq.-1 codes, read back via bit-shift
            # dequant (power-of-two grid, static fractional bits)
            from repro.core.qscheme import dequant, quant
            nkv = cfg.kv_cache_frac_bits
            k_c = quant(k, nkv, 8)
            v_c = quant(v, nkv, 8)
            k_full = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k_c, cache_pos, 1)
            v_full = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v_c, cache_pos, 1)
            new_cache = KVCache(k_full, v_full)
            if use_flash:
                # the whole point: no dequantized HBM copy — the kernel
                # consumes the codes directly
                k, v = k_full, v_full
                kv_frac_bits = nkv
            else:
                k = dequant(k_full, nkv, out_dtype=x.dtype)
                v = dequant(v_full, nkv, out_dtype=x.dtype)
        else:
            k_full = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k, cache_pos, 1)
            v_full = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v, cache_pos, 1)
            new_cache = KVCache(k_full, v_full)
            k, v = k_full, v_full
        q_offset = cache_pos

    groups = h // kvh
    if cache is not None and s == 1:
        if use_flash:
            # fused decode kernel: cache read in place (int8 codes straight
            # to VMEM), grouped heads share one KV tile DMA, traced position
            # arrives via scalar prefetch.  Under a multi-device mesh the
            # call crosses a shard_map boundary with the cache resident
            # HEAD-sharded on cfg.attn_shard_axis (DESIGN §8) — pin the
            # operands there so GSPMD hands them over without a reshard.
            from repro.kernels import ops as kops
            k = constrain(k, ("batch", None, "heads", None))
            v = constrain(v, ("batch", None, "heads", None))
            out = kops.flash_decode(q, k, v, pos=q_offset,
                                    kv_frac_bits=kv_frac_bits,
                                    mesh=current_mesh(),
                                    shard_axis=cfg.attn_shard_axis)
        else:
            # decode: direct attention over the SEQUENCE-sharded cache
            # (flash-decode): scores/values reduce over the seq axis, so the
            # only collectives are (B,H,1)-sized softmax stats — vs
            # re-gathering the whole cache when sharded on (non-dividing) kv
            # heads (§Perf iteration D2: 128 GB/step -> ~0 on qwen3-32b
            # decode_32k).  GQA grouping is contracted in place — no KV
            # repeat materializes.
            out = _direct_decode_attention(q, k, v, q_offset)
    elif use_flash and isinstance(q_offset, int):
        # prefill / train: q-tiled x kv-tiled fused kernel; GQA contracted
        # via the kernel index maps (no _repeat_kv), int8 codes (if any)
        # dequantized in-register.  q_offset is static here by construction.
        from repro.kernels import ops as kops
        k = constrain(k, ("batch", None, "heads", None))
        v = constrain(v, ("batch", None, "heads", None))
        out = kops.flash_attention(q, k, v, causal=causal and kv_x is None,
                                   q_offset=q_offset,
                                   kv_frac_bits=kv_frac_bits,
                                   mesh=current_mesh(),
                                   shard_axis=cfg.attn_shard_axis)
    else:
        if kv_frac_bits is not None:
            # flash requested but unusable (traced multi-token offset):
            # restore the reference dequantize-then-attend dataflow
            from repro.core.qscheme import dequant
            k = dequant(k, kv_frac_bits, out_dtype=x.dtype)
            v = dequant(v, kv_frac_bits, out_dtype=x.dtype)
        k = constrain(_repeat_kv(k, groups), ("batch", None, "heads", None))
        v = constrain(_repeat_kv(v, groups), ("batch", None, "heads", None))
        out = chunked_attention(q, k, v, causal=causal and kv_x is None,
                                kv_chunk=kv_chunk, q_offset=q_offset)
    out = constrain(out.reshape(b, s, h * hd), ("batch", None, "heads"))
    return linear(ctx, f"{name}/wo", out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V3 multi-head latent attention with absorbed decode
# ---------------------------------------------------------------------------

def init_mla(init, cfg: ModelConfig) -> dict:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": init.dense((d, m.q_lora_rank)),
        "q_norm": init.ones((m.q_lora_rank,)),
        "wq_b": init.dense((m.q_lora_rank, h * qk_head)),
        "wkv_a": init.dense((d, m.kv_lora_rank + m.qk_rope_head_dim)),
        "kv_norm": init.ones((m.kv_lora_rank,)),
        "wkv_b": init.dense((m.kv_lora_rank,
                             h * (m.qk_nope_head_dim + m.v_head_dim))),
        "wo": init.dense((h * m.v_head_dim, d)),
    }


def mla_attention(ctx: QuantContext, p: dict, x: jax.Array, cfg: ModelConfig,
                  *, positions: jax.Array, cache: Optional[MLACache] = None,
                  cache_pos: Optional[jax.Array] = None,
                  kv_chunk: int = 1024, name: str = "mla"
                  ) -> tuple[jax.Array, Optional[MLACache]]:
    """MLA forward.  Prefill/train: expanded K/V per token.  Decode: the
    *absorbed* formulation — W_uk folds into q, W_uv into the output, so
    attention runs in the (kv_lora + rope) latent space and the cache stays
    compressed.  That IS MLA's contribution; keeping it preserves the
    memory roofline the architecture was designed for."""
    m: MLAConfig = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    nope, rope_d, vdim = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    cq = rmsnorm(linear(ctx, f"{name}/wq_a", x, p["wq_a"]), p["q_norm"],
                 cfg.norm_eps)
    q = linear(ctx, f"{name}/wq_b", cq, p["wq_b"])
    q = constrain(q.reshape(b, s, h, nope + rope_d),
                  ("batch", None, "heads", None))
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)

    kv_a = linear(ctx, f"{name}/wkv_a", x, p["wkv_a"])
    c_kv, k_pe = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    scale = 1.0 / math.sqrt(nope + rope_d)

    if cache is None:
        # expanded path (train / prefill)
        kv = linear(ctx, f"{name}/wkv_b", c_kv, p["wkv_b"])
        kv = constrain(kv.reshape(b, s, h, nope + vdim),
                       ("batch", None, "heads", None))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, rope_d))],
            axis=-1)
        qq = jnp.concatenate([q_nope, q_pe], axis=-1)
        if cfg.attn_kernel == "flash":
            # fused prefill kernel (groups=1; dk=nope+rope is padded to the
            # lane multiple inside the wrapper); shard_map'd over full heads
            # on a multi-device mesh (kvh == h here)
            from repro.kernels import ops as kops
            out = kops.flash_attention(qq, k, v, causal=True, scale=scale,
                                       mesh=current_mesh(),
                                       shard_axis=cfg.attn_shard_axis)
        else:
            out = chunked_attention(qq, k, v, causal=True, kv_chunk=kv_chunk,
                                    scale=scale)
        out = constrain(out.reshape(b, s, h * vdim), ("batch", None, "heads"))
        return linear(ctx, f"{name}/wo", out, p["wo"]), None

    # absorbed decode path — cache holds (c_kv, k_pe) only
    c_full = jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c_kv, cache_pos, 1)
    pe_full = jax.lax.dynamic_update_slice_in_dim(cache.k_pe, k_pe, cache_pos, 1)
    new_cache = MLACache(c_full, pe_full)

    w_kv_b = p["wkv_b"].reshape(m.kv_lora_rank, h, nope + vdim)
    w_uk = w_kv_b[..., :nope]                     # (lora, H, nope)
    w_uv = w_kv_b[..., nope:]                     # (lora, H, vdim)
    # absorb W_uk into q:  (B,S,H,nope) x (lora,H,nope) -> (B,S,H,lora)
    q_lat = jnp.einsum("bshd,lhd->bshl", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    s_lat = jnp.einsum("bshl,btl->bhst", q_lat,
                       c_full.astype(jnp.float32))
    s_pe = jnp.einsum("bshd,btd->bhst", q_pe.astype(jnp.float32),
                      pe_full.astype(jnp.float32))
    scores = (s_lat + s_pe) * scale
    # positions: (B, S) absolute positions of the query tokens
    t_pos = jnp.arange(c_full.shape[1])
    mask = t_pos[None, None, None, :] <= positions[:, None, :, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bhst,btl->bshl", probs,
                         c_full.astype(jnp.float32))
    out = jnp.einsum("bshl,lhv->bshv", ctx_lat, w_uv.astype(jnp.float32))
    out = out.reshape(b, s, h * vdim).astype(x.dtype)
    return linear(ctx, f"{name}/wo", out, p["wo"]), new_cache
