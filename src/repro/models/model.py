"""Top-level model API: init / forward / prefill / decode / loss for every
architecture family, built on the block zoo in ``transformer.py``.

Layer stacks are scanned (stacked params, leading axis = depth).  All entry
points are pure functions of (params, batch) with static (cfg, ctx), so they
jit/pjit directly and ``jax.eval_shape`` gives allocation-free param trees
for the dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from repro.models.scan_lib import scan as _scan

from repro.configs.base import ModelConfig
from repro.core.qmodel import QuantContext
from repro.distributed.sharding import constrain
from repro.models import attention as att
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models import transformer as tfm
from repro.models.common import Initializer, embed, rmsnorm, unembed

__all__ = ["init_params", "init_cache", "init_paged_cache",
           "init_paged_state", "forward", "prefill", "decode_step",
           "paged_step", "paged_recurrent_step", "ragged_step", "loss_fn"]


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _stack_init(key: jax.Array, n: int, fn, dtype) -> Any:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: fn(Initializer(k, dtype)))(keys)


def _sinusoid(seq: int, d: int, dtype) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)[None]


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    dt = _dtype(cfg)
    k_embed, k_blocks, k_head, k_extra = jax.random.split(key, 4)
    init0 = Initializer(k_embed, dt)
    params: dict[str, Any] = {
        "embed": init0.dense((cfg.vocab_padded, cfg.d_model)),
        "ln_f": init0.ones((cfg.d_model,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = Initializer(k_head, dt).dense(
            (cfg.d_model, cfg.vocab_padded))

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["blocks"] = _stack_init(
            k_blocks, cfg.n_layers, lambda i: tfm.init_dense_block(i, cfg), dt)
    elif fam == "moe":
        nd = cfg.moe.n_dense_layers
        if nd:
            params["dense_blocks"] = _stack_init(
                k_extra, nd, lambda i: tfm.init_dense_block(i, cfg), dt)
        params["blocks"] = _stack_init(
            k_blocks, cfg.n_layers - nd,
            lambda i: tfm.init_moe_block(i, cfg), dt)
    elif fam == "audio":
        params["enc_blocks"] = _stack_init(
            k_extra, cfg.encdec.n_encoder_layers,
            lambda i: tfm.init_encoder_block(i, cfg), dt)
        params["ln_enc"] = init0.ones((cfg.d_model,))
        params["blocks"] = _stack_init(
            k_blocks, cfg.n_layers, lambda i: tfm.init_decoder_block(i, cfg), dt)
    elif fam == "ssm":
        params["blocks"] = _stack_init(
            k_blocks, cfg.n_layers, lambda i: tfm.init_rwkv_block(i, cfg), dt)
    elif fam == "hybrid":
        g = cfg.hybrid.attn_every
        n_groups = cfg.n_layers // g
        params["blocks"] = {"mamba": _stack_init(
            k_blocks, n_groups,
            lambda i: _stack_init(i.next_key(), g,
                                  lambda j: tfm.init_mamba_block(j, cfg), dt),
            dt)}
        params["shared"] = tfm.init_shared_attn(Initializer(k_extra, dt), cfg)
    else:
        raise ValueError(fam)
    return params


# ---------------------------------------------------------------------------
# cache init
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int) -> Any:
    dt = _dtype(cfg)
    kv_dt = jnp.int8 if cfg.kv_cache_bits == 8 else dt  # Eq.-1 codes
    hd = cfg.resolved_head_dim
    fam = cfg.family

    def kv(n_layers):
        return att.KVCache(
            k=jnp.zeros((n_layers, batch, max_seq, cfg.n_kv_heads, hd), kv_dt),
            v=jnp.zeros((n_layers, batch, max_seq, cfg.n_kv_heads, hd), kv_dt))

    def mla(n_layers):
        m = cfg.mla
        return att.MLACache(
            c_kv=jnp.zeros((n_layers, batch, max_seq, m.kv_lora_rank), dt),
            k_pe=jnp.zeros((n_layers, batch, max_seq, m.qk_rope_head_dim), dt))

    if fam in ("dense", "vlm"):
        return {"kv": mla(cfg.n_layers) if cfg.mla else kv(cfg.n_layers)}
    if fam == "moe":
        nd = cfg.moe.n_dense_layers
        c = {"kv": mla(cfg.n_layers - nd) if cfg.mla else kv(cfg.n_layers - nd)}
        if nd:
            c["kv_dense"] = mla(nd) if cfg.mla else kv(nd)
        return c
    if fam == "audio":
        enc_seq = cfg.encdec.encoder_seq
        return {"kv": kv(cfg.n_layers),
                "memory": jnp.zeros((batch, enc_seq, cfg.d_model), dt)}
    if fam == "ssm":
        st = rwkv_lib.zero_state(cfg, batch, dt)
        return {"state": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape).copy(), st)}
    if fam == "hybrid":
        g = cfg.hybrid.attn_every
        n_groups = cfg.n_layers // g
        st = ssm_lib.zero_state(cfg, batch, dt)
        return {
            "ssm": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_groups, g) + x.shape).copy(), st),
            "kv": kv(n_groups),
        }
    raise ValueError(fam)


def init_paged_cache(cfg: ModelConfig, num_blocks: int,
                     block_size: int) -> Any:
    """KV BLOCK POOL for the continuous-batching serving engine (DESIGN §9).

    Unlike :func:`init_cache` (one dense (B, S_max) cache per batch), the
    pool is a single (L, NB, BS, KVH, D) arena shared by every in-flight
    request; the host-side :class:`repro.serving.kv_pool.BlockPool` hands
    out blocks and per-sequence block tables.  Block 0 is the trash block
    (inactive slots write there), so ``num_blocks`` must be >= 2.
    """
    if cfg.family not in ("dense", "vlm") or cfg.mla is not None:
        raise NotImplementedError(
            f"paged serving covers GQA KV caches (family dense/vlm); "
            f"got family={cfg.family!r} mla={cfg.mla is not None}")
    if num_blocks < 2:
        raise ValueError("pool needs >= 2 blocks (block 0 is the trash "
                         "block inactive slots write to)")
    dt = _dtype(cfg)
    kv_dt = jnp.int8 if cfg.kv_cache_bits == 8 else dt  # Eq.-1 codes
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads,
             cfg.resolved_head_dim)
    return {"paged_kv": att.PagedKVCache(k=jnp.zeros(shape, kv_dt),
                                         v=jnp.zeros(shape, kv_dt))}


def init_paged_state(cfg: ModelConfig, num_slabs: int, *,
                     num_blocks: Optional[int] = None,
                     block_size: Optional[int] = None) -> Any:
    """STATE SLAB arenas for the fixed-slab recurrent substrate (DESIGN §16).

    One (L, S, ...) device arena per state component, S = ``num_slabs``;
    the host-side :class:`repro.serving.state_pool.StateSlabPool` hands out
    one slab per live sequence.  Slab 0 is the trash slab idle batch lanes
    read and write harmlessly (their q_len is 0, so the masked forward
    passes the slab state through bit-exactly), so ``num_slabs`` >= 2.

    With ``cfg.state_bits == 8`` the slabs hold Eq.-1 int8 codes on a
    per-slab power-of-two grid (``exp``, fixed at admission); ``None``
    keeps fp32 slabs — the parity-oracle mode.  The hybrid family also
    carries the shared attention block's KV pool (L = n_groups), sized by
    ``num_blocks`` / ``block_size`` exactly like :func:`init_paged_cache`.
    """
    if cfg.family not in ("ssm", "hybrid"):
        raise NotImplementedError(
            f"the fixed-slab substrate covers recurrent families "
            f"(ssm/hybrid); got family={cfg.family!r}")
    if num_slabs < 2:
        raise ValueError("state pool needs >= 2 slabs (slab 0 is the trash "
                         "slab idle lanes read and write)")
    dt = _dtype(cfg)
    st_dt = jnp.int8 if cfg.state_bits == 8 else jnp.float32
    exp = jnp.full((num_slabs,),
                   cfg.state_frac_bits if cfg.state_bits == 8 else 0,
                   jnp.int32)
    if cfg.family == "ssm":
        h = cfg.d_model // rwkv_lib.HEAD_DIM
        hd = rwkv_lib.HEAD_DIM
        ls = (cfg.n_layers, num_slabs)
        return {"state": {
            "x_att": jnp.zeros(ls + (cfg.d_model,), st_dt),
            "x_ffn": jnp.zeros(ls + (cfg.d_model,), st_dt),
            "wkv": jnp.zeros(ls + (h, hd, hd), st_dt)},
            "exp": exp}
    if num_blocks is None or block_size is None:
        raise ValueError("hybrid slabs need num_blocks/block_size for the "
                         "shared attention block's KV pool")
    if num_blocks < 2:
        raise ValueError("pool needs >= 2 blocks (block 0 is the trash "
                         "block inactive slots write to)")
    g = cfg.hybrid.attn_every
    n_groups = cfg.n_layers // g
    st = ssm_lib.zero_state(cfg, num_slabs)
    ssm_states = jax.tree.map(
        lambda z: jnp.broadcast_to(
            z.astype(st_dt), (n_groups, g) + z.shape).copy(), st)
    kv_dt = jnp.int8 if cfg.kv_cache_bits == 8 else dt
    kv_shape = (n_groups, num_blocks, block_size, cfg.n_kv_heads,
                cfg.resolved_head_dim)
    return {"ssm": ssm_states, "exp": exp,
            "paged_kv": att.PagedKVCache(k=jnp.zeros(kv_shape, kv_dt),
                                         v=jnp.zeros(kv_shape, kv_dt))}


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

_REMAT_POLICIES = {
    "full": None,  # jax.checkpoint default: save nothing, recompute all
    "dots": "dots_with_no_batch_dims_saveable",
}


def forward(params: dict, batch: dict, cfg: ModelConfig, ctx: QuantContext,
            *, remat: bool | str = False, cache: Any = None
            ) -> tuple[jax.Array, Any]:
    """Full-sequence forward.  If ``cache`` is given (prefill), K/V (or
    recurrent states) are written into it and returned.  Returns
    (logits fp32 (B,S,V), cache).

    remat: False (save everything) | 'full' / True (recompute each block in
    backward — the production default: saved state per layer is ONE bf16
    residual) | 'dots' (save matmul outputs).

    W8A8 deploy (DESIGN §13): ``params`` may be a ``QuantizedParams.tree``
    — matmul weights as int8 codes — in which case ``ctx`` MUST be the
    matching INT-mode context (qlinear raises otherwise); embeddings and
    norm gains stay float, so embed/rmsnorm paths are unchanged.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    dt = _dtype(cfg)
    x = constrain(embed(params["embed"], tokens, dt), ("batch", None, None))
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    fam = cfg.family
    new_cache = None

    def maybe_remat(f):
        if not remat:
            return f
        if remat in (True, "full"):
            return jax.checkpoint(f)
        return jax.checkpoint(
            f, policy=getattr(jax.checkpoint_policies, _REMAT_POLICIES[remat]))

    if fam in ("dense", "vlm", "moe"):
        block = tfm.moe_block if fam == "moe" else tfm.dense_block

        def body_nocache(x, p_l):
            y, _ = block(ctx, p_l, x, cfg, positions=positions)
            return y, None

        def body_cache(x, inp):
            p_l, c_l = inp
            y, c = block(ctx, p_l, x, cfg, positions=positions,
                         cache=c_l, cache_pos=0)
            return y, c

        if fam == "moe" and cfg.moe.n_dense_layers:
            def dense_body_nocache(x, p_l):
                y, _ = tfm.dense_block(ctx, p_l, x, cfg, positions=positions)
                return y, None

            def dense_body_cache(x, inp):
                p_l, c_l = inp
                y, c = tfm.dense_block(ctx, p_l, x, cfg, positions=positions,
                                       cache=c_l, cache_pos=0)
                return y, c

            if cache is None:
                x, _ = _scan(maybe_remat(dense_body_nocache), x,
                                    params["dense_blocks"])
            else:
                x, kvd = _scan(maybe_remat(dense_body_cache), x,
                                      (params["dense_blocks"], cache["kv_dense"]))
        if cache is None:
            x, _ = _scan(maybe_remat(body_nocache), x, params["blocks"])
        else:
            x, kvm = _scan(maybe_remat(body_cache), x,
                                  (params["blocks"], cache["kv"]))
            new_cache = {"kv": kvm}
            if fam == "moe" and cfg.moe.n_dense_layers:
                new_cache["kv_dense"] = kvd

    elif fam == "audio":
        memory = _encode(params, batch, cfg, ctx, remat)

        def dec_body(x, inp):
            p_l, c_l = inp
            y, c = tfm.decoder_block(ctx, p_l, x, memory, cfg,
                                     positions=positions, cache=c_l,
                                     cache_pos=0 if c_l is not None else None)
            return y, c

        if cache is None:
            def dec_nocache(x, p_l):
                y, _ = tfm.decoder_block(ctx, p_l, x, memory, cfg,
                                         positions=positions)
                return y, None
            x, _ = _scan(maybe_remat(dec_nocache), x, params["blocks"])
        else:
            x, kvm = _scan(maybe_remat(dec_body), x,
                                  (params["blocks"], cache["kv"]))
            new_cache = {"kv": kvm, "memory": memory}

    elif fam == "ssm":
        states = cache["state"] if cache is not None else jax.tree.map(
            lambda z: jnp.broadcast_to(z, (cfg.n_layers,) + z.shape).copy(),
            rwkv_lib.zero_state(cfg, b, dt))

        def body(x, inp):
            p_l, st_l = inp
            y, st = tfm.rwkv_block_fwd(ctx, p_l, x, cfg, state=st_l)
            return y, st

        x, new_states = _scan(maybe_remat(body), x,
                                     (params["blocks"], states))
        if cache is not None:
            new_cache = {"state": new_states}

    elif fam == "hybrid":
        g = cfg.hybrid.attn_every
        n_groups = cfg.n_layers // g
        c = cache if cache is not None else init_cache(cfg, b, s)
        x_embed = x

        def body(carry, inp):
            x_c = carry
            p_g, ssm_g, kv_g = inp
            y, st, kv = tfm.hybrid_group_fwd(
                ctx, p_g, params["shared"], x_c, x_embed, cfg,
                positions=positions, ssm_states=ssm_g,
                attn_cache=kv_g, cache_pos=0)
            return y, (st, kv)

        x, (new_ssm, new_kv) = _scan(
            maybe_remat(body), x,
            (params["blocks"]["mamba"], c["ssm"], c["kv"]))
        if cache is not None:
            new_cache = {"ssm": new_ssm, "kv": new_kv}
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = constrain(unembed(ctx, x, head), ("batch", None, "vocab"))
    return logits, new_cache


def _encode(params, batch, cfg, ctx, remat=False):
    """Whisper encoder over stub frame embeddings (frontend per assignment)."""
    feats = batch["encoder_features"]                       # (B, T, d) stub
    x = feats.astype(_dtype(cfg)) + _sinusoid(
        feats.shape[1], cfg.d_model, _dtype(cfg))

    def body(x, p_l):
        return tfm.encoder_block(ctx, p_l, x, cfg), None

    f = jax.checkpoint(body) if remat else body
    x, _ = _scan(f, x, params["enc_blocks"])
    return rmsnorm(x, params["ln_enc"], cfg.norm_eps)


def prefill(params, batch, cfg, ctx, max_seq: Optional[int] = None):
    b, s = batch["tokens"].shape
    cache = init_cache(cfg, b, max_seq or s)
    logits, cache = forward(params, batch, cfg, ctx, cache=cache)
    return logits[:, -1], cache


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_step(params: dict, tokens: jax.Array, cache: Any, pos: jax.Array,
                cfg: ModelConfig, ctx: QuantContext, batch: Optional[dict] = None
                ) -> tuple[jax.Array, Any]:
    """One serving step: tokens (B, 1) at absolute position ``pos`` (scalar),
    KV/state cache from prefill.  Returns (logits (B, V), new cache)."""
    b = tokens.shape[0]
    dt = _dtype(cfg)
    x = embed(params["embed"], tokens, dt)
    positions = jnp.broadcast_to(pos[None, None] if pos.ndim == 0 else pos,
                                 (b, 1))
    fam = cfg.family
    new_cache = dict(cache) if isinstance(cache, dict) else cache

    if fam in ("dense", "vlm", "moe"):
        block = tfm.moe_block if fam == "moe" else tfm.dense_block

        def body(x, inp):
            p_l, c_l = inp
            y, c = block(ctx, p_l, x, cfg, positions=positions,
                         cache=c_l, cache_pos=pos)
            return y, c

        if fam == "moe" and cfg.moe.n_dense_layers:
            def dbody(x, inp):
                p_l, c_l = inp
                y, c = tfm.dense_block(ctx, p_l, x, cfg, positions=positions,
                                       cache=c_l, cache_pos=pos)
                return y, c
            x, kvd = _scan(dbody, x,
                                  (params["dense_blocks"], cache["kv_dense"]))
            new_cache["kv_dense"] = kvd
        x, kvm = _scan(body, x, (params["blocks"], cache["kv"]))
        new_cache["kv"] = kvm

    elif fam == "audio":
        memory = cache["memory"]

        def body(x, inp):
            p_l, c_l = inp
            y, c = tfm.decoder_block(ctx, p_l, x, memory, cfg,
                                     positions=positions, cache=c_l,
                                     cache_pos=pos)
            return y, c

        x, kvm = _scan(body, x, (params["blocks"], cache["kv"]))
        new_cache["kv"] = kvm

    elif fam == "ssm":
        def body(x, inp):
            p_l, st_l = inp
            y, st = tfm.rwkv_block_decode(ctx, p_l, x, cfg, st_l)
            return y, st

        x, st = _scan(body, x, (params["blocks"], cache["state"]))
        new_cache["state"] = st

    elif fam == "hybrid":
        x_embed = x

        def body(x_c, inp):
            p_g, ssm_g, kv_g = inp
            y, st, kv = tfm.hybrid_group_fwd(
                ctx, p_g, params["shared"], x_c, x_embed, cfg,
                positions=positions, ssm_states=ssm_g, attn_cache=kv_g,
                cache_pos=pos, decode=True)
            return y, (st, kv)

        x, (st, kv) = _scan(body, x,
                                   (params["blocks"]["mamba"], cache["ssm"],
                                    cache["kv"]))
        new_cache = {"ssm": st, "kv": kv}
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(ctx, x, head)
    return logits[:, 0], new_cache


def paged_step(params: dict, tokens: jax.Array, cache: Any,
               positions: jax.Array, block_tables: jax.Array,
               cfg: ModelConfig, ctx: QuantContext) -> tuple[jax.Array, Any]:
    """One serving-engine step over the paged KV block pool (DESIGN §9).

    tokens (B, C) at PER-TOKEN absolute ``positions`` (B, C);
    ``block_tables`` (B, NBmax) maps each slot's logical blocks to pool
    blocks.  Covers BOTH engine shapes: continuous-batching decode
    (B = n_slots, C = 1 — every slot at its own live length) and chunked
    prefill (B = 1, C = chunk bucket).  Returns (logits fp32 (B, C, V),
    new cache); the engine samples from the last REAL token's row.
    """
    b, c = tokens.shape
    if cfg.family not in ("dense", "vlm") or cfg.mla is not None:
        raise NotImplementedError(
            f"paged_step covers GQA dense/vlm families; got {cfg.family!r}")
    dt = _dtype(cfg)
    x = constrain(embed(params["embed"], tokens, dt), ("batch", None, None))

    def body(x, inp):
        p_l, c_l = inp
        y, cl = tfm.dense_block(ctx, p_l, x, cfg, positions=positions,
                                cache=c_l, cache_pos=positions,
                                block_tables=block_tables)
        return y, cl

    x, kv = _scan(body, x, (params["blocks"], cache["paged_kv"]))
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(ctx, x, head)
    return logits, {"paged_kv": kv}


def paged_recurrent_step(params: dict, tokens: jax.Array, cache: Any,
                         slab_ids: jax.Array, q_len: jax.Array,
                         positions: Optional[jax.Array],
                         block_tables: Optional[jax.Array],
                         cfg: ModelConfig, ctx: QuantContext
                         ) -> tuple[jax.Array, Any]:
    """One serving step on the fixed-slab recurrent substrate (DESIGN §16).

    ``tokens`` (B, C) right-padded; ``q_len`` (B,) real tokens per row
    (prefill chunks use c_real <= C, decode rows 1, idle lanes 0 parked on
    the trash slab); ``slab_ids`` (B,) each row's state slab.  The whole
    gathered state is dequantized ONCE on its per-slab po2 grid
    (``cache['exp']``), the masked forward advances every row by its own
    q_len in one fixed shape, and the new state requantizes ONCE before
    scattering back — so the requant count per token is independent of
    context length (the paper's dataflow thesis on recurrent state).
    Idle lanes pass their slab through bit-exactly (inert masking), which
    keeps duplicate trash-slab scatters deterministic.

    For the hybrid family, per-token ``positions`` (B, C) (invalid entries
    pointed past the last real block) and ``block_tables``
    (B, NBmax + 1, last column = trash block) drive the shared attention
    block's KV pool exactly like :func:`paged_step`; pure recurrent
    families ignore both.  Returns (logits fp32 (B, V) at each row's last
    real token, new cache).
    """
    from repro.core.qscheme import dequant, quant
    b, c = tokens.shape
    if cfg.family not in ("ssm", "hybrid"):
        raise NotImplementedError(
            f"paged_recurrent_step covers ssm/hybrid families; "
            f"got {cfg.family!r}")
    dt = _dtype(cfg)
    x = constrain(embed(params["embed"], tokens, dt), ("batch", None, None))
    valid = jnp.arange(c, dtype=jnp.int32)[None, :] < q_len[:, None]
    int8 = cfg.state_bits == 8
    exps = cache["exp"][slab_ids]                            # (B,) int32

    if cfg.family == "ssm":
        st = cache["state"]

        def load(a, out_dt):
            g = a[:, slab_ids]                               # (L, B, ...)
            if int8:
                n = exps.reshape((1, b) + (1,) * (g.ndim - 2))
                return dequant(g, n, out_dtype=out_dt)
            return g.astype(out_dt)

        states = rwkv_lib.RWKVState(
            x_prev_att=load(st["x_att"], dt)[:, :, None, :],
            x_prev_ffn=load(st["x_ffn"], dt)[:, :, None, :],
            wkv=load(st["wkv"], jnp.float32))

        def body(x, inp):
            p_l, st_l = inp
            y, st2 = tfm.rwkv_block_paged(ctx, p_l, x, cfg, st_l, valid)
            return y, st2

        x, ns = _scan(body, x, (params["blocks"], states))

        def store(old, new):
            if int8:
                n = exps.reshape((1, b) + (1,) * (new.ndim - 2))
                codes = quant(new, n, 8)
            else:
                codes = new.astype(old.dtype)
            return old.at[:, slab_ids].set(codes)

        new_cache = {"state": {
            "x_att": store(st["x_att"], ns.x_prev_att[:, :, 0]),
            "x_ffn": store(st["x_ffn"], ns.x_prev_ffn[:, :, 0]),
            "wkv": store(st["wkv"], ns.wkv)},
            "exp": cache["exp"]}

    else:
        x_embed = x
        st = cache["ssm"]

        def load_h(a, out_dt):
            g = a[:, :, slab_ids]                            # (G, g, B, ...)
            if int8:
                n = exps.reshape((1, 1, b) + (1,) * (g.ndim - 3))
                return dequant(g, n, out_dtype=out_dt)
            return g.astype(out_dt)

        states = ssm_lib.SSMState(conv=load_h(st.conv, dt),
                                  ssm=load_h(st.ssm, jnp.float32))

        def body(x_c, inp):
            p_g, ssm_g, kv_g = inp
            y, st2, kv2 = tfm.hybrid_group_fwd(
                ctx, p_g, params["shared"], x_c, x_embed, cfg,
                positions=positions, ssm_states=ssm_g, attn_cache=kv_g,
                cache_pos=positions, block_tables=block_tables, valid=valid)
            return y, (st2, kv2)

        x, (ns, nkv) = _scan(
            body, x, (params["blocks"]["mamba"], states, cache["paged_kv"]))

        def store_h(old, new):
            if int8:
                n = exps.reshape((1, 1, b) + (1,) * (new.ndim - 3))
                codes = quant(new, n, 8)
            else:
                codes = new.astype(old.dtype)
            return old.at[:, :, slab_ids].set(codes)

        new_cache = {"ssm": ssm_lib.SSMState(conv=store_h(st.conv, ns.conv),
                                             ssm=store_h(st.ssm, ns.ssm)),
                     "exp": cache["exp"], "paged_kv": nkv}

    rows = jnp.arange(b)
    last = jnp.maximum(q_len - 1, 0)
    xe = x[rows, last][:, None, :]                           # (B, 1, d)
    xe = rmsnorm(xe, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = unembed(ctx, xe, head)
    return logits[:, 0], new_cache


def ragged_step(params: dict, tokens: jax.Array, cache: Any,
                positions: jax.Array, ragged: att.RaggedBatch,
                cfg: ModelConfig, ctx: QuantContext) -> tuple[jax.Array, Any]:
    """One UNIFIED serving step over a flattened mixed token stream.

    ``tokens``/``positions`` are (T,) — every live token of the step
    (prefill chunks, decode rows, speculative tails) packed back to
    back; ``ragged`` carries the per-sequence descriptors and the
    flattened pool destinations (DESIGN §12).  Returns (logits fp32
    (T, V), new cache); the engine samples per sequence from the rows
    its descriptor names.  Padding rows (covered by no descriptor)
    produce garbage logits that no descriptor samples.
    """
    if cfg.family not in ("dense", "vlm") or cfg.mla is not None:
        raise NotImplementedError(
            f"ragged_step covers GQA dense/vlm families; got {cfg.family!r}")
    dt = _dtype(cfg)
    # named scopes (embed / layers / head, and the layer's own inside
    # dense_block) tag each op's HLO metadata, so a device trace can
    # charge every op of the step to a part of the model
    with jax.named_scope("embed"):
        x = constrain(embed(params["embed"], tokens[None], dt),
                      ("batch", None, None))

    def body(x, inp):
        p_l, c_l = inp
        y, cl = tfm.dense_block(ctx, p_l, x, cfg, positions=positions[None],
                                cache=c_l, cache_pos=positions[None],
                                ragged=ragged)
        return y, cl

    with jax.named_scope("layers"):
        x, kv = _scan(body, x, (params["blocks"], cache["paged_kv"]))
    with jax.named_scope("head"):
        x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = unembed(ctx, x, head)
    return logits[0], {"paged_kv": kv}


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def loss_fn(params: dict, batch: dict, cfg: ModelConfig, ctx: QuantContext,
            *, remat: bool | str = "full") -> tuple[jax.Array, dict]:
    """Next-token cross entropy (fp32 logsumexp) + z-loss regularizer.

    The gold-logit pick uses a fused one-hot reduction instead of
    take_along_axis: a vocab-dim gather makes GSPMD re-shard the logits to
    full-batch (observed 33 GB/device temps); the one-hot product keeps
    both batch and vocab shardings intact.
    """
    logits, _ = forward(params, batch, cfg, ctx, remat=remat)
    targets = batch["labels"]
    logits = logits[:, :-1]
    targets = targets[:, 1:]
    # stable CE with bf16 logits and f32 reduction accumulators: max/exp per
    # element in bf16 (transient), sums in f32 — no (B,S,V) f32 buffers.
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    expd = jnp.exp(logits - m)
    sumexp = jnp.sum(expd, axis=-1, dtype=jnp.float32)
    lse = jnp.log(sumexp) + m[..., 0].astype(jnp.float32)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1, dtype=jnp.float32)
    nll = lse - gold
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:].astype(nll.dtype)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        loss = jnp.mean(nll)
    z_loss = 1e-4 * jnp.mean(lse * lse)
    metrics = {"nll": loss, "z_loss": z_loss,
               "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0))}
    return loss + z_loss, metrics
