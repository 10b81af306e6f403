"""jit'd public wrappers around the Pallas kernels.

Responsibilities: flatten batch dims, pad to tile multiples, pick MXU-aligned
block shapes that fit VMEM, dispatch to the Pallas kernel (interpret mode on
CPU), and fall back to the jnp reference for shapes where a kernel launch is
not worthwhile.  The serving hot path (``ragged_attention``,
``int8_matmul``) never falls back on an accelerator.

VMEM budget reasoning (v5e: ~128 MiB VMEM/core, we target < 8 MiB per call
to leave room for double-buffering):
  int8 x tile  bm*bk      (1 B)     128*512  = 64 KiB
  int8 w tile  bk*bn      (1 B)     512*512  = 256 KiB
  int32 acc    bm*bn      (4 B)     128*512  = 256 KiB
so default (bm, bk, bn) = (128, 512, 512) uses < 1 MiB with K-streaming,
and every dim is a multiple of the 128-lane MXU tiling.
"""
from __future__ import annotations

import functools
import math
import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.integer_ops import LinearQuantSpec, int_linear
from repro.kernels import ref
from repro.kernels.flash_attention import (make_flash_decode,
                                           make_flash_prefill,
                                           make_paged_flash_decode)
from repro.kernels.int8_matmul import make_int8_matmul
from repro.kernels.quantize import make_quantize
from repro.kernels.ragged_flash import make_ragged_paged_flash
from repro.kernels.residual_requant import make_residual_requant

__all__ = ["int8_matmul", "quantize_act", "residual_requant",
           "flash_attention", "flash_decode", "paged_attention",
           "ragged_attention", "ragged_call_shape", "attention_kv_bytes",
           "attn_shard_size", "use_interpret", "tpu_kernels",
           "DEFAULT_BLOCKS", "FLASH_BLOCKS"]

DEFAULT_BLOCKS = (128, 512, 512)  # (bm, bk, bn)
FLASH_BLOCKS = (256, 512)         # (bq, bk) — q tile x kv tile


def use_interpret() -> bool:
    return jax.default_backend() == "cpu"


def tpu_kernels(hlo_text: str) -> set[str]:
    """Names of the Pallas kernels a compiled TPU program launches — the
    ``tpu_custom_call`` instructions of its optimized HLO
    (``compiled.as_text()``), named by each kernel's ``pallas_call``
    name (``ragged_paged_flash``, ``int8_matmul``, ...)."""
    return {m.group(1) for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="[^"]*?/(\w+)/pallas_call', line)]
            if m}


def _shard_map(local, mesh: Mesh, in_specs, out_specs):
    """jit(shard_map(...)) over ``mesh``.  check_vma=False: pallas_call
    has no varying-manual-axes rule."""
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    r = x.shape[axis] % mult
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - r)
    return jnp.pad(x, pad)


def _lane_tile(dim: int, pref: int) -> int:
    """Largest lane-multiple tile <= ``pref`` that divides ``dim`` — so a
    weight whose dim is a multiple of 128 but not of ``pref`` (the
    151936-wide LM head) is never padded, i.e. never copied per call.
    Dims below 128 take the whole dim (a full-array block is legal)."""
    if dim <= 128:
        return dim
    t = pref
    while t > 128 and dim % t:
        t //= 2
    return t


def _pick_blocks(m: int, k: int, n: int) -> tuple[int, int, int]:
    bm, bk, bn = DEFAULT_BLOCKS
    return (min(bm, _round_up(m, 8)), _lane_tile(k, bk), _lane_tile(n, bn))


def int8_matmul(x_int: jax.Array, w_int: jax.Array,
                b_int: Optional[jax.Array], spec: LinearQuantSpec,
                *, relu: bool = False, force_kernel: bool = False) -> jax.Array:
    """(..., K) int8 @ (K, N) int8 -> (..., N) int8 with fused requant.

    The batched/ragged entry point for the W8A8 forward (DESIGN §13):
    leading dims — a (B, S) batch or a packed ragged (T,) token stream —
    are flattened into the M axis.  Static shift constants come from
    ``spec`` (deploy artifacts).  On CPU the jnp reference ``int_linear``
    (same bit-exact contract) serves every shape by default:
    interpret-mode Pallas simulates the grid serially and would dominate
    the serving step for zero fidelity gain.
    ``force_kernel=True`` overrides that policy so kernel parity tests
    exercise the fused epilogue itself (in interpret mode on CPU).  On an
    accelerator every shape launches the kernel: rows pad to the sublane
    tile, and dims below a lane take full-dim blocks.
    """
    *batch, k = x_int.shape
    n = w_int.shape[-1]
    m = 1
    for d in batch:
        m *= d
    unsigned = relu and spec.out_unsigned
    lo, hi = ((0, (1 << spec.bits) - 1) if unsigned
              else (-(1 << (spec.bits - 1)), (1 << (spec.bits - 1)) - 1))

    if use_interpret() and not force_kernel:
        return int_linear(x_int, w_int, b_int, spec, apply_relu=relu)

    x2 = x_int.reshape(m, k)
    bm, bk, bn = _pick_blocks(m, k, n)
    x2 = _pad_to(_pad_to(x2, bm, 0), bk, 1)
    w2 = _pad_to(_pad_to(w_int, bk, 0), bn, 1)
    mp, kp = x2.shape
    np_ = w2.shape[1]
    has_bias = b_int is not None
    call = make_int8_matmul(
        mp, kp, np_, bm=bm, bk=bk, bn=bn,
        shift=spec.requant_shift, bias_shift=spec.bias_shift, relu=relu,
        lo=lo, hi=hi, has_bias=has_bias,
        out_dtype=jnp.uint8 if unsigned else jnp.int8,
        interpret=use_interpret())
    if has_bias:
        b2 = _pad_to(b_int.reshape(1, -1), bn, 1)
        out = call(x2, w2, b2)
    else:
        out = call(x2, w2)
    return out[:m, :n].reshape(*batch, n)


# ---------------------------------------------------------------------------
# fused (int8-KV) flash attention — DESIGN.md §2
# multi-device shard_map wiring (KV heads over the tensor axis) — DESIGN.md §8
# ---------------------------------------------------------------------------

def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def attn_shard_size(mesh: Optional[Mesh], shard_axis: str) -> int:
    """Size of the tensor axis the flash kernels shard heads over (1 when
    there is no mesh or the axis is absent — the single-device path)."""
    if mesh is None or shard_axis not in mesh.axis_names:
        return 1
    return mesh.shape[shard_axis]


def _attn_batch_spec(mesh: Mesh, b: int):
    """Batch-dim spec entry: the composite data axes — the SAME selection
    ``sharding.batch_sharding`` uses, so the shard_map boundary matches
    the activations' layout — when they divide B, else replicated."""
    from repro.distributed import sharding as shd
    dp = shd._dp(mesh)
    return dp if (dp and b % shd._axis_size(mesh, dp) == 0) else None


def _check_head_divisibility(kvh: int, tp: int, shard_axis: str):
    if kvh % tp:
        raise NotImplementedError(
            f"flash attention shards KV heads over mesh axis "
            f"'{shard_axis}' (size {tp}), which must divide the operand's "
            f"KV head count ({kvh}); use attn_kernel='chunked' (sequence-"
            f"sharded) for this mesh shape")


# Why jit + a bounded cache: eager shard_map cannot evaluate the closed
# calls inside the wrapper (jax.checkpoint / custom_vjp raise
# NotImplementedError outside jit), so direct eager callers (tests, REPL)
# need the jit; under an outer jitted step it simply inlines.  The cache
# keeps eager re-calls from retracing; bounded so long-lived serving
# processes can't accumulate a closure per distinct (mesh, q_offset, ...).
@functools.lru_cache(maxsize=64)
def _make_sharded_prefill(mesh: Mesh, head_entry, bdim, causal: bool,
                          q_offset: int, kv_frac_bits, scale):
    """shard_map'd prefill: q/k/v enter head-sharded on ``head_entry``
    (whole GQA groups per shard — kvh % tp == 0 is checked by the caller;
    None when the tensor axis is trivial), batch-sharded on the data axes
    when divisible.  Each shard runs the full single-device wrapper on its
    local heads: per-shard block picking, padding, and the static
    power-of-two KV scale folded into that shard's kernel constants.  No
    collectives — softmax is over the (replicated) KV sequence, so shards
    are independent."""
    spec = P(bdim, None, head_entry, None)

    def local(q, k, v):
        return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               kv_frac_bits=kv_frac_bits, scale=scale)

    return _shard_map(local, mesh, (spec, spec, spec), spec)


@functools.lru_cache(maxsize=64)
def _make_sharded_decode(mesh: Mesh, head_entry, bdim, kv_frac_bits,
                         scale):
    """shard_map'd decode: the cache stays resident head-sharded (int8
    codes + their static scale per shard), q is resharded to match (tiny),
    ``pos`` is replicated.  Grouped query heads of a KV head land on the
    same shard, so the kernel's one-DMA-per-group contract holds."""
    spec = P(bdim, None, head_entry, None)

    def local(pos, q, k, v):
        return flash_decode(q, k, v, pos=pos, kv_frac_bits=kv_frac_bits,
                            scale=scale)

    return _shard_map(local, mesh, (P(), spec, spec, spec), spec)


def _resolve_kv_frac_bits(k: jax.Array, kv_frac_bits: Optional[int]) -> int:
    """int8 KV codes are meaningless without their Eq.-1 fractional bit —
    defaulting to 2^0 would be a silent temperature/scale corruption."""
    if k.dtype == jnp.int8:
        if kv_frac_bits is None:
            raise ValueError("int8 KV codes require kv_frac_bits (the "
                             "cache's static Eq.-1 fractional bit)")
        return int(kv_frac_bits)
    return 0


def _dequant_then_repeat(q, k, v, nkv):
    """Reference dataflow the kernel deletes: full dequant copy + group
    repeat, then the pure-JAX chunked attention."""
    from repro.core.qscheme import dequant
    from repro.models.attention import _repeat_kv
    if k.dtype == jnp.int8:
        k = dequant(k, nkv, out_dtype=q.dtype)
        v = dequant(v, nkv, out_dtype=q.dtype)
    groups = q.shape[2] // k.shape[2]
    return _repeat_kv(k, groups), _repeat_kv(v, groups)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_offset: int = 0,
                    kv_frac_bits: Optional[int] = None,
                    scale: Optional[float] = None,
                    mesh: Optional[Mesh] = None,
                    shard_axis: str = "model") -> jax.Array:
    """Fused flash attention: q (B,Sq,H,Dk) x KV (B,Skv,KVH,D) -> (B,Sq,H,Dv).

    K/V may be int8 Eq.-1 codes (then ``kv_frac_bits`` is their static
    fractional bit): the codes are loaded directly into VMEM and dequantized
    in-register — the bf16 KV tensor never materializes in HBM.  GQA is
    contracted via the kernel's index maps, never repeated.  Shapes not
    worth a launch fall back to the pure-JAX ``chunked_attention`` (which
    stays the reference oracle).  ``q_offset`` must be a *static* int here
    (prefill); traced decode positions go through :func:`flash_decode`.

    With a multi-device ``mesh`` the call runs under shard_map: KV heads
    (whole GQA groups) are partitioned across ``shard_axis`` and every
    shard launches the kernel on its local heads (DESIGN §8).  The axis
    size must divide the KV head count.
    """
    b, sq, h, dk = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    if mesh is not None and mesh.size > 1:
        # >1 device: ALWAYS cross a shard_map boundary — GSPMD treats the
        # pallas_call as an opaque custom call and would gather/replicate
        # its operands otherwise (the exact dataflow this kernel deletes).
        tp = attn_shard_size(mesh, shard_axis)
        _check_head_divisibility(kvh, tp, shard_axis)
        call = _make_sharded_prefill(mesh, shard_axis if tp > 1 else None,
                                     _attn_batch_spec(mesh, b),
                                     causal, q_offset, kv_frac_bits, scale)
        return call(q, k, v)
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    nkv = _resolve_kv_frac_bits(k, kv_frac_bits)
    int8_kv = k.dtype == jnp.int8
    if sq < 16 or skv < 128:
        from repro.models.attention import chunked_attention
        kr, vr = _dequant_then_repeat(q, k, v, nkv)
        return chunked_attention(q, kr, vr, causal=causal,
                                 q_offset=q_offset, scale=scale)

    bq, bk = FLASH_BLOCKS
    sq_p = _round_up(sq, 128)
    skv_p = _round_up(skv, 128)
    bq, bk = min(bq, sq_p), min(bk, skv_p)
    sq_p, skv_p = _round_up(sq_p, bq), _round_up(skv_p, bk)
    dk_p, dv_p = _round_up(dk, 128), _round_up(dv, 128)

    def kernel_call(q_, k_, v_):
        # heads flatten into the lane axis (free reshapes; kernel layout)
        qp = _pad_to(_pad_to(q_, bq, 1), dk_p, 3).reshape(b, sq_p, h * dk_p)
        kp = _pad_to(_pad_to(k_, bk, 1), dk_p, 3).reshape(
            b, skv_p, kvh * dk_p)
        vp = _pad_to(_pad_to(v_, bk, 1), dv_p, 3).reshape(
            b, skv_p, kvh * dv_p)
        call = make_flash_prefill(
            b, h, kvh, sq_p, skv_p, dk_p, dv_p, bq=bq, bk=bk, causal=causal,
            q_offset=q_offset, sq=sq, skv=skv,
            score_scale=scale * 2.0 ** (-nkv), v_scale=2.0 ** (-nkv),
            k_dtype=k_.dtype, out_dtype=q_.dtype, interpret=use_interpret())
        out = call(qp, kp, vp).reshape(b, sq_p, h, dv_p)
        return out[:, :sq, :, :dv]

    if int8_kv:
        # inference-only dataflow (codes are non-differentiable anyway)
        return kernel_call(q, k, v)

    # float KV (train / prefill-from-scratch): pallas_call has no VJP rule,
    # so pair the fused forward with a backward that recomputes through the
    # chunked reference — same exact function, flash-attention style.
    def ref_fn(q_, k_, v_):
        from repro.models.attention import chunked_attention
        kr, vr = _dequant_then_repeat(q_, k_, v_, nkv)
        return chunked_attention(q_, kr, vr, causal=causal,
                                 q_offset=q_offset, scale=scale)

    @jax.custom_vjp
    def attn(q_, k_, v_):
        return kernel_call(q_, k_, v_)

    def attn_fwd(q_, k_, v_):
        return kernel_call(q_, k_, v_), (q_, k_, v_)

    def attn_bwd(res, g):
        return jax.vjp(ref_fn, *res)[1](g)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn(q, k, v)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 pos: jax.Array, kv_frac_bits: Optional[int] = None,
                 scale: Optional[float] = None,
                 mesh: Optional[Mesh] = None,
                 shard_axis: str = "model") -> jax.Array:
    """Single-token fused decode: q (B,1,H,Dk) over the full cache
    (B,S_max,KVH,D), masked at traced absolute position ``pos``.

    The cache is read IN PLACE (native layout, int8 codes straight into
    VMEM); grouped query heads share one KV tile DMA.  Falls back to the
    chunked reference when the cache length has no MXU-aligned tile divisor
    OR the head dims are not lane multiples — padding the head dim here
    would copy the ENTIRE cache every decode step, which is exactly the
    dataflow this kernel deletes.

    With a multi-device ``mesh``: shard_map over ``shard_axis`` with the
    cache resident head-sharded — int8 codes AND their static power-of-two
    scale stay with their shard; only the (B,1,H,D) query and the scalar
    position cross the boundary (DESIGN §8).
    """
    b, sq1, h, dk = q.shape
    assert sq1 == 1, "flash_decode is the q_len=1 kernel"
    s_max, kvh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    if mesh is not None and mesh.size > 1:
        tp = attn_shard_size(mesh, shard_axis)
        _check_head_divisibility(kvh, tp, shard_axis)
        call = _make_sharded_decode(mesh, shard_axis if tp > 1 else None,
                                    _attn_batch_spec(mesh, b),
                                    kv_frac_bits, scale)
        return call(jnp.asarray(pos, jnp.int32), q, k, v)
    groups = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    nkv = _resolve_kv_frac_bits(k, kv_frac_bits)

    bk = next((c for c in (512, 256, 128) if s_max % c == 0), None)
    if bk is None or s_max < 128 or dk % 128 or dv % 128:
        from repro.models.attention import chunked_attention
        kr, vr = _dequant_then_repeat(q, k, v, nkv)
        return chunked_attention(q, kr, vr, causal=True, q_offset=pos,
                                 scale=scale)

    gp = max(8, _round_up(groups, 8))
    q4 = _pad_to(q[:, 0].reshape(b, kvh, groups, dk), gp, 2)

    call = make_flash_decode(
        b, kvh, gp, s_max, dk, dv, bk=bk,
        score_scale=scale * 2.0 ** (-nkv), v_scale=2.0 ** (-nkv),
        out_dtype=q.dtype, interpret=use_interpret())
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    out = call(pos_arr, q4, k.reshape(b, s_max, kvh * dk),
               v.reshape(b, s_max, kvh * dv))          # (B, KVH, gp, dv)
    return out[:, :, :groups].reshape(b, 1, h, dv)


# ---------------------------------------------------------------------------
# paged attention over the serving engine's KV block pool — DESIGN.md §9
# ---------------------------------------------------------------------------

def _paged_ref_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                         block_tables: jax.Array, q_positions: jax.Array,
                         nkv: int, scale: float) -> jax.Array:
    """Reference paged attention: gather every table block from the pool,
    dequantize, and attend with per-(slot, query) causal masks.

    This IS the dataflow the paged kernel deletes — a dequantized gathered
    copy of each slot's cache materializes in HBM — kept as the oracle, the
    CPU path, and the fallback for shapes the kernel refuses (non-lane-
    multiple head dims, non-MXU block sizes, multi-token chunks)."""
    from repro.core.qscheme import dequant
    b, c, h, dk = q.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    dv = v_pool.shape[-1]
    g = h // kvh
    s_len = block_tables.shape[1] * bs
    k = k_pool[block_tables].reshape(b, s_len, kvh, dk)
    v = v_pool[block_tables].reshape(b, s_len, kvh, dv)
    if k.dtype == jnp.int8:
        k = dequant(k, nkv, out_dtype=q.dtype)
        v = dequant(v, nkv, out_dtype=q.dtype)
    else:
        k, v = k.astype(q.dtype), v.astype(q.dtype)
    qg = q.reshape(b, c, kvh, g, dk)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(s_len)
    mask = kv_pos[None, None, :] <= q_positions[:, :, None]   # (B, C, S)
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, c, h, dv).astype(q.dtype)


@functools.lru_cache(maxsize=64)
def _make_sharded_paged_decode(mesh: Mesh, head_entry, bdim, kv_frac_bits,
                               scale):
    """shard_map'd paged decode: the BLOCK POOL stays resident head-sharded
    on ``head_entry`` (int8 codes + static po2 scale per shard, exactly
    like the dense cache in DESIGN §8); block tables and per-slot positions
    are slot-metadata — they follow the q/batch partition (``bdim``) and
    are replicated over the tensor axis, so every head shard walks the
    same logical→physical block mapping.  No collectives."""
    qspec = P(bdim, None, head_entry, None)
    pspec = P(None, None, head_entry, None)

    def local(pos, bt, q, kp, vp):
        return paged_attention(q, kp, vp, bt, pos[:, None],
                               kv_frac_bits=kv_frac_bits, scale=scale)

    return _shard_map(local, mesh,
                      (P(bdim), P(bdim, None), qspec, pspec, pspec), qspec)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, q_positions: jax.Array, *,
                    kv_frac_bits: Optional[int] = None,
                    scale: Optional[float] = None,
                    mesh: Optional[Mesh] = None,
                    shard_axis: str = "model") -> jax.Array:
    """Attention over the serving engine's paged KV block pool (DESIGN §9).

    q: (B, C, H, Dk) — C == 1 is the continuous-batching decode hot path,
    C > 1 a chunked-prefill chunk.  k/v_pool: (NB, BS, KVH, D) — ALL
    sequences' blocks in one pool, int8 Eq.-1 codes (``kv_frac_bits``) or
    float.  block_tables: (B, NBmax) int32 mapping each slot's logical
    block ``i`` to its pool block (unallocated tail entries point at the
    trash block and are masked).  q_positions: (B, C) int32 absolute
    positions of the query tokens; attention is causal per slot
    (``kv_pos <= q_positions[b, c]``), which is what lets a fixed-width
    slot batch serve sequences of different live lengths.

    The C == 1 case with MXU-aligned shapes launches the fused paged
    kernel: the block table is consumed by the BlockSpec index maps, so KV
    codes stream block-by-block from the pool straight into VMEM — no
    gathered copy, no dequantized copy, written-once codes are never
    requantized.  Everything else takes the reference gather path.  With a
    multi-device ``mesh`` the kernel path crosses a shard_map boundary:
    pool head-sharded over ``shard_axis``, tables/positions replicated
    across it (batch over the data axes when divisible).
    """
    b, c, h, dk = q.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    dv = v_pool.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    nkv = _resolve_kv_frac_bits(k_pool, kv_frac_bits)
    kernel_ok = (c == 1 and bs % 128 == 0 and dk % 128 == 0
                 and dv % 128 == 0)
    if mesh is not None and mesh.size > 1:
        tp = attn_shard_size(mesh, shard_axis)
        _check_head_divisibility(kvh, tp, shard_axis)
        if not kernel_ok:
            # reference path is plain jnp — GSPMD partitions it directly
            return _paged_ref_attention(q, k_pool, v_pool, block_tables,
                                        q_positions, nkv, scale)
        call = _make_sharded_paged_decode(
            mesh, shard_axis if tp > 1 else None, _attn_batch_spec(mesh, b),
            kv_frac_bits, scale)
        return call(jnp.asarray(q_positions[:, 0], jnp.int32),
                    jnp.asarray(block_tables, jnp.int32), q, k_pool, v_pool)
    if not kernel_ok:
        return _paged_ref_attention(q, k_pool, v_pool, block_tables,
                                    q_positions, nkv, scale)
    groups = h // kvh
    gp = max(8, _round_up(groups, 8))
    q4 = _pad_to(q[:, 0].reshape(b, kvh, groups, dk), gp, 2)
    call = make_paged_flash_decode(
        b, kvh, gp, block_tables.shape[1], bs, dk, dv,
        score_scale=scale * 2.0 ** (-nkv), v_scale=2.0 ** (-nkv),
        out_dtype=q.dtype, interpret=use_interpret())
    pos = jnp.asarray(q_positions[:, 0], jnp.int32)
    nb = k_pool.shape[0]
    out = call(pos, jnp.asarray(block_tables, jnp.int32), q4,
               k_pool.reshape(nb, bs, kvh * dk),
               v_pool.reshape(nb, bs, kvh * dv))
    return out[:, :, :groups].reshape(b, 1, h, dv)


@functools.lru_cache(maxsize=64)
def _make_sharded_ragged(mesh: Mesh, head_entry, kv_frac_bits, scale,
                         tq_max):
    """shard_map'd ragged attention: the pool stays resident head-sharded
    (like paged decode); the packed (T, H, D) stream is head-sharded on
    its head axis and the descriptors are replicated.  The token axis is
    NOT partitioned — a ragged stream has no slot-aligned batch dim for
    the data axes to split, and T_pad is a few dozen rows, so replicating
    it across data-parallel shards is the cheap and correct layout."""
    qspec = P(None, head_entry, None)
    pspec = P(None, None, head_entry, None)

    def local(q, kp, vp, bt, qs, ql, kl):
        return ragged_attention(q, kp, vp, bt, qs, ql, kl,
                                kv_frac_bits=kv_frac_bits, scale=scale,
                                tq_max=tq_max)

    return _shard_map(
        local, mesh,
        (qspec, pspec, pspec, P(None, None), P(), P(), P()), qspec)


def ragged_call_shape(t: int, h: int, kvh: int, nbmax: int, bs: int,
                      dk: int, dv: int, *, tq_max: Optional[int] = None,
                      mesh: Optional[Mesh] = None,
                      shard_axis: str = "model") -> Optional[dict]:
    """The static shapes of the ``ragged_paged_flash`` call that
    ``ragged_attention`` makes for a (t, h, dk) stream over pools of
    ``bs``-row blocks with ``kvh`` KV heads and ``nbmax``-block tables:
    the keywords of ``make_ragged_paged_flash`` after its slot count,
    which are those of ``ragged_walk_counts``.  Head counts are per shard
    under a multi-device ``mesh``.  None where no kernel runs — pools
    the kernel cannot take, served by the gather oracle."""
    if bs % 128 or dk % 128 or dv % 128:
        return None
    tp = (attn_shard_size(mesh, shard_axis)
          if mesh is not None and mesh.size > 1 else 1)
    return dict(h=h // tp, kvh=kvh // tp, nbmax=nbmax, bs=bs,
                t_pad=_round_up(t, 8), tq=min(tq_max, t) if tq_max else t,
                dk=dk, dv=dv)


def ragged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_tables: jax.Array, q_start: jax.Array,
                     q_len: jax.Array, kv_len: jax.Array, *,
                     kv_frac_bits: Optional[int] = None,
                     scale: Optional[float] = None,
                     tq_max: Optional[int] = None,
                     mesh: Optional[Mesh] = None,
                     shard_axis: str = "model") -> jax.Array:
    """Unified ragged-batch attention over the paged KV pool (DESIGN §12).

    One call serves a MIXED serving step: q (T, H, Dk) is the flattened
    token stream — prefill chunks, decode rows, and speculative tails
    packed back to back — and the per-sequence descriptors ``q_start`` /
    ``q_len`` / ``kv_len`` (S,) + ``block_tables`` (S, NBmax) say which
    stream rows belong to which sequence and how much KV each one sees.
    Descriptor contract (host-built): ``q_start`` nondecreasing, windows
    disjoint, ``q_len <= kv_len``, padding slots all-zero with trash
    tables.  Returns (T, H, Dv) with non-descriptor rows exactly zero.

    MXU-aligned pools (bs/dk/dv lane multiples) launch the single
    ``ragged_flash`` pallas_call — descriptors ride scalar prefetch, the
    block walk happens in the DMA engine, int8 codes dequantize
    in-register.  ``tq_max`` (static) bounds the per-sequence q_len so
    the kernel's q window stays narrow; None means the whole stream
    width.  On the CPU (interpret mode) other shapes take the gather
    oracle (``ref.ragged_attention_ref``); on an accelerator they raise
    — the serving hot path never silently leaves the kernel.  With a
    multi-device ``mesh``, KV heads shard over ``shard_axis`` (whole GQA
    groups — §8) and descriptors replicate.
    """
    t, h, dk = q.shape
    nb, bs, kvh = k_pool.shape[:3]
    dv = v_pool.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dk)
    nkv = _resolve_kv_frac_bits(k_pool, kv_frac_bits)
    shape = ragged_call_shape(t, h, kvh, block_tables.shape[1], bs, dk, dv,
                              tq_max=tq_max)
    if shape is None and not use_interpret():
        raise ValueError(
            f"ragged attention kernel needs 128-multiple block size and "
            f"head dims (got block_size={bs}, dk={dk}, dv={dv}); the "
            f"gather reference is a CPU-only oracle")
    bt = jnp.asarray(block_tables, jnp.int32)
    qs = jnp.asarray(q_start, jnp.int32)
    ql = jnp.asarray(q_len, jnp.int32)
    kl = jnp.asarray(kv_len, jnp.int32)
    sharded = mesh is not None and mesh.size > 1
    if sharded:
        tp = attn_shard_size(mesh, shard_axis)
        _check_head_divisibility(kvh, tp, shard_axis)
    if shape is None:
        # plain jnp — under a mesh GSPMD partitions it directly
        return ref.ragged_attention_ref(
            q, k_pool, v_pool, bt, qs, ql, kl,
            kv_frac_bits=kv_frac_bits, scale=scale)
    if sharded:
        call = _make_sharded_ragged(mesh, shard_axis if tp > 1 else None,
                                    kv_frac_bits, scale, tq_max)
        return call(q, k_pool, v_pool, bt, qs, ql, kl)
    t_pad = shape["t_pad"]
    qp = _pad_to(q, 8, 0).reshape(t_pad, h * dk)
    call = make_ragged_paged_flash(
        bt.shape[0], **shape, score_scale=scale * 2.0 ** (-nkv),
        v_scale=2.0 ** (-nkv), out_dtype=q.dtype, interpret=use_interpret())
    out = call(qs, ql, kl, bt, qp, k_pool.reshape(nb, bs, kvh * dk),
               v_pool.reshape(nb, bs, kvh * dv)).reshape(t_pad, h, dv)
    # rows covered by no descriptor were never written by the kernel —
    # pin them to the contract's zero
    _, valid, _ = ref.ragged_token_meta(qs, ql, kl, t)
    return jnp.where(valid[:, None, None], out[:t], 0)


def attention_kv_bytes(skv: int, kvh: int, dk: int, dv: int, *,
                       kv_bits: int = 16, fused: bool = True,
                       batch: int = 1, groups: int = 1) -> int:
    """Analytic HBM bytes touched for the KV operands of one attention call.

    ``fused``: codes are DMA'd once and dequantized in VMEM (this module).
    ``not fused``: the dequantize-then-attend pipeline, staged uniformly —
    [int8 only] dequant pass reads the codes and writes a bf16 copy;
    [groups > 1 only] the repeat reads that copy and writes it ``groups``x;
    attention then reads whatever the last stage produced.
    """
    elems = batch * skv * kvh * (dk + dv)
    code_bytes = kv_bits // 8
    if fused:
        return elems * code_bytes
    bf16 = 2
    total, cur = 0, code_bytes
    if kv_bits < 16:
        total += code_bytes + bf16     # dequant: read codes, write bf16 copy
        cur = bf16
    if groups > 1:
        total += cur + bf16 * groups   # repeat: read copy, write groups x
        cur = bf16 * groups
    return elems * (total + cur)       # + the attention read itself


def quantize_act(x: jax.Array, n: int, bits: int = 8,
                 unsigned: bool = False) -> jax.Array:
    """Elementwise Eq.-1 quantization of an activation tensor."""
    *batch, c = x.shape
    rows = 1
    for d in batch:
        rows *= d
    if rows < 8 or c < 128:
        return ref.quantize_ref(x, n=n, bits=bits, unsigned=unsigned)
    x2 = x.reshape(rows, c)
    br, bc = min(256, rows), min(512, c)
    x2 = _pad_to(_pad_to(x2, br, 0), bc, 1)
    call = make_quantize(x2.shape[0], x2.shape[1], br=br, bc=bc, n=n,
                         bits=bits, unsigned=unsigned,
                         interpret=use_interpret())
    return call(x2)[:rows, :c].reshape(*batch, c)


def residual_requant(a_int: jax.Array, b_int: jax.Array, *, n_a: int,
                     n_b: int, n_o: int, bits: int = 8,
                     relu: bool = False) -> jax.Array:
    """Fused Fig. 1(c)/(d) residual add on int8 codes."""
    assert a_int.shape == b_int.shape
    *batch, c = a_int.shape
    rows = 1
    for d in batch:
        rows *= d
    if rows < 8 or c < 128:
        return ref.residual_requant_ref(a_int, b_int, n_a=n_a, n_b=n_b,
                                        n_o=n_o, bits=bits, relu=relu)
    a2 = a_int.reshape(rows, c)
    b2 = b_int.reshape(rows, c)
    br, bc = min(256, rows), min(512, c)
    a2 = _pad_to(_pad_to(a2, br, 0), bc, 1)
    b2 = _pad_to(_pad_to(b2, br, 0), bc, 1)
    call = make_residual_requant(a2.shape[0], a2.shape[1], br=br, bc=bc,
                                 n_a=n_a, n_b=n_b, n_o=n_o, bits=bits,
                                 relu=relu, interpret=use_interpret())
    return call(a2, b2)[:rows, :c].reshape(*batch, c)
