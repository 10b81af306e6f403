"""Block assembly for every architecture family.

Blocks are homogeneous pure functions scanned over stacked params
(``jax.lax.scan`` keeps the HLO size O(1) in depth — 95-layer configs
compile in seconds instead of minutes, and remat policies apply uniformly).

Families:
  dense   — pre-norm GQA + SwiGLU (qwen3 / llama / deepseek-67b / chameleon)
  moe     — pre-norm attention (GQA or MLA) + MoE FFN (granite / deepseek-v3;
            deepseek-v3 keeps its first k layers dense — two scan stacks)
  encdec  — whisper: encoder (bidirectional) + decoder (causal + cross-attn)
  rwkv    — RWKV6 time-mix + channel-mix
  hybrid  — zamba2: groups of Mamba2 blocks + ONE shared GQA block applied
            between groups (two-level scan; shared params broadcast)
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from repro.models.scan_lib import scan as _scan

from repro.configs.base import ModelConfig
from repro.core.qmodel import QuantContext
from repro.distributed.sharding import constrain
from repro.models import attention as att
from repro.models import mlp as mlp_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.common import Initializer, linear, rmsnorm

__all__ = ["init_dense_block", "dense_block", "init_moe_block", "moe_block",
           "init_rwkv_block", "rwkv_block_fwd", "init_hybrid_group",
           "hybrid_group_fwd", "BlockCache"]

BlockCache = Any  # per-family cache pytree


# ---------------------------------------------------------------------------
# dense / moe transformer blocks
# ---------------------------------------------------------------------------

def init_dense_block(init: Initializer, cfg: ModelConfig) -> dict:
    p = {
        "ln1": init.ones((cfg.d_model,)),
        "ln2": init.ones((cfg.d_model,)),
        "mlp": mlp_lib.init_mlp(init, cfg.d_model, cfg.d_ff, cfg.act),
    }
    if cfg.mla is not None:
        p["attn"] = att.init_mla(init, cfg)
    else:
        p["attn"] = att.init_gqa(init, cfg)
    return p


def _attn_dispatch(ctx, p, x, cfg, positions, cache, cache_pos,
                   use_rope=True, block_tables=None, ragged=None):
    if cfg.mla is not None:
        if block_tables is not None or ragged is not None:
            raise NotImplementedError(
                "paged serving covers GQA caches only; MLA's compressed "
                "latent cache has no block-pool layout yet (DESIGN §9)")
        return att.mla_attention(ctx, p["attn"], x, cfg, positions=positions,
                                 cache=cache, cache_pos=cache_pos)
    return att.gqa_attention(ctx, p["attn"], x, cfg, positions=positions,
                             cache=cache, cache_pos=cache_pos,
                             use_rope=use_rope, block_tables=block_tables,
                             ragged=ragged)


def dense_block(ctx: QuantContext, p: dict, x: jax.Array, cfg: ModelConfig,
                *, positions: jax.Array, cache=None, cache_pos=None,
                use_rope: bool = True, block_tables=None, ragged=None):
    # W8A8 (DESIGN §13): all quantization lives inside the qlinear
    # modules; residual adds and rmsnorms run in float between module
    # grids, so a block over int8 weight codes is bit-identical to the
    # float-weight INT forward module-for-module (the parity rig's
    # full-layer case leans on exactly this).
    # The scopes name the layer's parts in the ops' HLO metadata (the
    # attention's own parts are named inside it); each norm and residual
    # add goes with the part it feeds or closes.
    with jax.named_scope("attn_qkv"):
        xn = rmsnorm(x, p["ln1"], cfg.norm_eps)
    h, new_cache = _attn_dispatch(ctx, p, xn, cfg, positions, cache,
                                  cache_pos, use_rope, block_tables, ragged)
    with jax.named_scope("attn_out"):
        x = constrain(x + h, ("batch", None, None))
    with jax.named_scope("mlp"):
        x = x + mlp_lib.mlp(ctx, p["mlp"], rmsnorm(x, p["ln2"], cfg.norm_eps),
                            cfg.act)
        x = constrain(x, ("batch", None, None))
    return x, new_cache


def init_moe_block(init: Initializer, cfg: ModelConfig) -> dict:
    p = {
        "ln1": init.ones((cfg.d_model,)),
        "ln2": init.ones((cfg.d_model,)),
        "moe": mlp_lib.init_moe(init, cfg),
    }
    if cfg.mla is not None:
        p["attn"] = att.init_mla(init, cfg)
    else:
        p["attn"] = att.init_gqa(init, cfg)
    return p


def moe_block(ctx: QuantContext, p: dict, x: jax.Array, cfg: ModelConfig,
              *, positions: jax.Array, cache=None, cache_pos=None,
              block_tables=None):
    h, new_cache = _attn_dispatch(ctx, p, rmsnorm(x, p["ln1"], cfg.norm_eps),
                                  cfg, positions, cache, cache_pos,
                                  block_tables=block_tables)
    x = constrain(x + h, ("batch", None, None))
    x = x + mlp_lib.moe(ctx, p["moe"], rmsnorm(x, p["ln2"], cfg.norm_eps), cfg)
    return constrain(x, ("batch", None, None)), new_cache


# ---------------------------------------------------------------------------
# whisper enc-dec blocks (no rope; sinusoidal positions added at embed time)
# ---------------------------------------------------------------------------

def init_encoder_block(init: Initializer, cfg: ModelConfig) -> dict:
    return {
        "ln1": init.ones((cfg.d_model,)),
        "ln2": init.ones((cfg.d_model,)),
        "attn": att.init_gqa(init, cfg),
        "mlp": mlp_lib.init_mlp(init, cfg.d_model, cfg.d_ff, "gelu"),
    }


def encoder_block(ctx: QuantContext, p: dict, x: jax.Array, cfg: ModelConfig):
    h, _ = att.gqa_attention(ctx, p["attn"],
                             rmsnorm(x, p["ln1"], cfg.norm_eps), cfg,
                             positions=jnp.arange(x.shape[1])[None],
                             causal=False, use_rope=False)
    x = x + h
    x = x + mlp_lib.mlp(ctx, p["mlp"],
                        rmsnorm(x, p["ln2"], cfg.norm_eps), "gelu")
    return x


def init_decoder_block(init: Initializer, cfg: ModelConfig) -> dict:
    return {
        "ln1": init.ones((cfg.d_model,)),
        "ln_cross": init.ones((cfg.d_model,)),
        "ln2": init.ones((cfg.d_model,)),
        "attn": att.init_gqa(init, cfg),
        "cross": att.init_gqa(init, cfg),
        "mlp": mlp_lib.init_mlp(init, cfg.d_model, cfg.d_ff, "gelu"),
    }


def decoder_block(ctx: QuantContext, p: dict, x: jax.Array, memory: jax.Array,
                  cfg: ModelConfig, *, positions, cache=None, cache_pos=None):
    h, new_cache = att.gqa_attention(
        ctx, p["attn"], rmsnorm(x, p["ln1"], cfg.norm_eps), cfg,
        positions=positions, cache=cache, cache_pos=cache_pos,
        use_rope=True)  # rope in lieu of whisper's learned positions (DESIGN §7)
    x = x + h
    h, _ = att.gqa_attention(
        ctx, p["cross"], rmsnorm(x, p["ln_cross"], cfg.norm_eps), cfg,
        positions=positions, kv_x=memory, use_rope=False, name="cross")
    x = x + h
    x = x + mlp_lib.mlp(ctx, p["mlp"],
                        rmsnorm(x, p["ln2"], cfg.norm_eps), "gelu")
    return x, new_cache


# ---------------------------------------------------------------------------
# rwkv6 block
# ---------------------------------------------------------------------------

def init_rwkv_block(init: Initializer, cfg: ModelConfig) -> dict:
    return {
        "ln1": init.ones((cfg.d_model,)),
        "ln2": init.ones((cfg.d_model,)),
        "rwkv": rwkv_lib.init_rwkv6(init, cfg),
    }


def rwkv_block_fwd(ctx: QuantContext, p: dict, x: jax.Array, cfg: ModelConfig,
                   state: Optional[rwkv_lib.RWKVState] = None):
    att_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
    att_out, _, st = rwkv_lib.rwkv6_block(ctx, p["rwkv"], att_in, cfg,
                                          state=state)
    x = x + att_out
    ffn_in = rmsnorm(x, p["ln2"], cfg.norm_eps)
    x = x + rwkv_lib.rwkv6_channel_mix(
        ctx, p["rwkv"], ffn_in, cfg,
        x_prev=state.x_prev_ffn if state is not None else None)
    new_state = rwkv_lib.RWKVState(x_prev_att=att_in[:, -1:],
                                   x_prev_ffn=ffn_in[:, -1:], wkv=st.wkv)
    return x, new_state


def rwkv_block_paged(ctx: QuantContext, p: dict, x: jax.Array,
                     cfg: ModelConfig, state: rwkv_lib.RWKVState,
                     valid: jax.Array):
    """Right-padded batched RWKV block for the serving engine (§16).

    Every row advances by its own ``q_len = sum(valid)`` tokens in one
    fixed-shape call: invalid positions are inert inside the chunked WKV
    (r/k/v -> 0, log-decay -> 0), and the token-shift streams are gathered
    per-row at the last VALID position instead of ``[:, -1:]``.  Rows with
    ``q_len == 0`` (empty slots / trash-slab lanes) carry their state
    through bit-exactly."""
    b = x.shape[0]
    att_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
    att_out, _, st = rwkv_lib.rwkv6_block(ctx, p["rwkv"], att_in, cfg,
                                          state=state, valid=valid)
    x = x + att_out
    ffn_in = rmsnorm(x, p["ln2"], cfg.norm_eps)
    x = x + rwkv_lib.rwkv6_channel_mix(ctx, p["rwkv"], ffn_in, cfg,
                                       x_prev=state.x_prev_ffn)
    q_len = jnp.sum(valid.astype(jnp.int32), axis=1)
    last = jnp.maximum(q_len - 1, 0)
    rows = jnp.arange(b)
    keep = (q_len > 0)[:, None, None]
    new_state = rwkv_lib.RWKVState(
        x_prev_att=jnp.where(keep, att_in[rows, last][:, None],
                             state.x_prev_att),
        x_prev_ffn=jnp.where(keep, ffn_in[rows, last][:, None],
                             state.x_prev_ffn),
        wkv=st.wkv)
    return x, new_state


def rwkv_block_decode(ctx: QuantContext, p: dict, x: jax.Array,
                      cfg: ModelConfig, state: rwkv_lib.RWKVState):
    att_in = rmsnorm(x, p["ln1"], cfg.norm_eps)
    att_out, st = rwkv_lib.rwkv6_decode(ctx, p["rwkv"], att_in, cfg, state)
    x = x + att_out
    ffn_in = rmsnorm(x, p["ln2"], cfg.norm_eps)
    x = x + rwkv_lib.rwkv6_channel_mix(ctx, p["rwkv"], ffn_in, cfg,
                                       x_prev=state.x_prev_ffn)
    new_state = rwkv_lib.RWKVState(x_prev_att=att_in, x_prev_ffn=ffn_in,
                                   wkv=st.wkv)
    return x, new_state


# ---------------------------------------------------------------------------
# zamba2 hybrid group: ``attn_every`` mamba blocks + shared GQA block
# ---------------------------------------------------------------------------

def init_mamba_block(init: Initializer, cfg: ModelConfig) -> dict:
    return {"ln": init.ones((cfg.d_model,)),
            "ssm": ssm_lib.init_mamba2(init, cfg)}


def init_shared_attn(init: Initializer, cfg: ModelConfig) -> dict:
    """The ONE shared transformer block (zamba2): sees concat(h, embed)."""
    d = cfg.d_model
    return {
        "in_proj": init.dense((2 * d, d), fan_in=2 * d),
        "ln1": init.ones((d,)),
        "ln2": init.ones((d,)),
        "attn": att.init_gqa(init, cfg),
        "mlp": mlp_lib.init_mlp(init, d, cfg.d_ff, cfg.act),
    }


def hybrid_group_fwd(ctx: QuantContext, group_p: dict, shared_p: dict,
                     x: jax.Array, x_embed: jax.Array, cfg: ModelConfig,
                     *, positions, ssm_states=None, attn_cache=None,
                     cache_pos=None, decode: bool = False,
                     block_tables=None, valid=None):
    """One group = ``attn_every`` stacked mamba blocks (inner scan) then the
    shared attention block.  ``group_p`` holds the stacked mamba block
    params (leading axis = attn_every); ssm_states likewise.

    Paged serving (§16) threads ``valid`` (B, S) into the Mamba blocks
    (invalid positions contribute nothing and do not decay the slab state)
    and ``block_tables`` into the shared attention block, whose cache then
    scatters through the block pool at per-token ``cache_pos``."""

    def inner(x_carry, inp):
        p_l, st_l = inp
        h_in = rmsnorm(x_carry, p_l["ln"], cfg.norm_eps)
        if decode:
            h, new_st = ssm_lib.mamba2_decode(ctx, p_l["ssm"], h_in, cfg, st_l)
        else:
            h, new_st = ssm_lib.mamba2(ctx, p_l["ssm"], h_in, cfg,
                                       init_state=st_l, valid=valid)
        return x_carry + h, new_st

    x, new_states = _scan(inner, x, (group_p, ssm_states))

    # shared attention block on concat(h, embedding) (zamba2 dataflow)
    z = jnp.concatenate([x, x_embed], axis=-1)
    z = linear(ctx, "shared/in_proj", z, shared_p["in_proj"])
    h, new_cache = att.gqa_attention(
        ctx, shared_p["attn"], rmsnorm(z, shared_p["ln1"], cfg.norm_eps),
        cfg, positions=positions, cache=attn_cache, cache_pos=cache_pos,
        block_tables=block_tables, name="shared/attn")
    z = z + h
    z = z + mlp_lib.mlp(ctx, shared_p["mlp"],
                        rmsnorm(z, shared_p["ln2"], cfg.norm_eps), cfg.act,
                        name="shared/mlp")
    return x + z, new_states, new_cache
