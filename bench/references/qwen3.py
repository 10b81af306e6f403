"""Plain float32 reference of the Qwen3 decoder (Hugging Face ``Qwen3ForCausalLM``):
RMSNorm before attention and MLP, q/k RMSNorm over the head dimension
before rotary embedding (rotate-half form, theta from the config), grouped
query attention (query head h reads KV head h // (heads / kv_heads)),
softmax scale 1 / sqrt(head_dim), SwiGLU MLP down(silu(gate(x)) * up(x)),
final RMSNorm, LM head tied to the embedding when the config says so.

Straight ``jax.numpy`` at matmul precision HIGHEST: no kernels, no cache,
no batching, and nothing imported from the program.  Weights are the
benchmark's own, made here from the seed (``make_weights``); the program
is handed the same values under its own leaf names (``PROGRAM_NAMES``).

``precision`` gives the control: ``"int8"`` / ``"int4"`` fake-quantise
every matmul's weight (per output channel) and input (per row) to that
many bits, symmetric, in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
SEQ_BUCKET = 1024      # sequences are right-padded to a multiple of this
ROW_BUCKET = 256       # logit rows are padded to a multiple of this

# program leaf name (path joined by "/") -> reference weight name
PROGRAM_NAMES = {
    "embed": "embed", "ln_f": "final_norm", "lm_head": "lm_head",
    "blocks/ln1": "attn_norm", "blocks/ln2": "mlp_norm",
    "blocks/attn/wq": "wq", "blocks/attn/wk": "wk", "blocks/attn/wv": "wv",
    "blocks/attn/wo": "wo", "blocks/attn/q_norm": "q_norm",
    "blocks/attn/k_norm": "k_norm",
    "blocks/mlp/w1": "gate", "blocks/mlp/w3": "up", "blocks/mlp/w2": "down",
}


def weight_shapes(hf: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """Reference weight name -> (shape, dtype): matrices in the served
    dtype, norm gains in float32.  Layer weights carry a leading layer
    axis."""
    L, d = hf["num_hidden_layers"], hf["hidden_size"]
    h, kvh, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    f, v = hf["intermediate_size"], hf["vocab_size"]
    mat = hf["torch_dtype"]
    out = {
        "embed": ((v, d), mat), "final_norm": ((d,), "float32"),
        "attn_norm": ((L, d), "float32"), "mlp_norm": ((L, d), "float32"),
        "wq": ((L, d, h * hd), mat), "wk": ((L, d, kvh * hd), mat),
        "wv": ((L, d, kvh * hd), mat), "wo": ((L, h * hd, d), mat),
        "q_norm": ((L, hd), "float32"), "k_norm": ((L, hd), "float32"),
        "gate": ((L, d, f), mat), "up": ((L, d, f), mat),
        "down": ((L, f, d), mat),
    }
    if not hf["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), mat)
    return out


@functools.lru_cache(maxsize=None)
def _maker(shapes: tuple, std: float):
    def make(key):
        out = {}
        for i, (name, shape, dtype) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, shape, jnp.float32)
            if dtype == "float32":          # norm gains: 1 +- 0.1
                out[name] = 1.0 + 0.1 * x
            else:                           # the config's initializer range
                out[name] = (std * x).astype(dtype)
        return out
    return jax.jit(make)


def make_weights(hf: dict, seed: int) -> dict:
    """All weights, on the device, in one jitted call from ``seed``."""
    shapes = tuple((n, s, dt) for n, (s, dt) in sorted(weight_shapes(hf).items()))
    key = jax.random.PRNGKey(np.random.default_rng(
        [int(seed) % (1 << 63), 7]).integers(1 << 31))
    return _maker(shapes, float(hf["initializer_range"]))(key)


def _fq(x, bits, axis):
    """Symmetric fake quantisation to ``bits`` along ``axis`` (absmax)."""
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / qmax
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def _mm(x, w, bits):
    w = w.astype(jnp.float32)
    if bits:
        x, w = _fq(x, bits, -1), _fq(w, bits, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(q, k, v, n_real):
    """Causal GQA over one padded sequence, in query blocks.  q (S, H, D),
    k/v (S, KVH, D); keys at or beyond ``n_real`` are masked."""
    s, h, dh = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(dh)
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_real)
        sc = jnp.where(ok[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))
    return out.reshape(s, h, dh)


@functools.lru_cache(maxsize=None)
def _hidden_fn(hf_items: tuple, bits: int):
    hf = dict(hf_items)
    h, kvh, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])

    def layer(x, p, pos, n_real):
        a = _rms(x, p["attn_norm"], eps)
        s = x.shape[0]
        q = _mm(a, p["wq"], bits).reshape(s, h, hd)
        k = _mm(a, p["wk"], bits).reshape(s, kvh, hd)
        v = _mm(a, p["wv"], bits).reshape(s, kvh, hd)
        q = _rope(_rms(q, p["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, p["k_norm"], eps), pos, theta)
        o = _attention(q, k, v, n_real).reshape(s, h * hd)
        x = x + _mm(o, p["wo"], bits)
        m = _rms(x, p["mlp_norm"], eps)
        u = jax.nn.silu(_mm(m, p["gate"], bits)) * _mm(m, p["up"], bits)
        return x + _mm(u, p["down"], bits)

    def hidden(w, tokens, n_real, rows):
        x = w["embed"][tokens].astype(jnp.float32)
        pos = jnp.arange(tokens.shape[0])
        names = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "q_norm",
                 "k_norm", "gate", "up", "down")
        stack = {n: w[n] for n in names}

        def body(x, p):
            return layer(x, p, pos, n_real), None

        x, _ = jax.lax.scan(body, x, stack)
        return _rms(x[rows], w["final_norm"], eps)

    return jax.jit(hidden)


@functools.lru_cache(maxsize=None)
def _gap_fn(tied: bool, bits: int):
    def gaps(w, hid, picked):
        head = w["embed"].T if tied else w["lm_head"]
        logits = _mm(hid, head, bits)
        top = logits.max(-1)
        got = jnp.take_along_axis(logits, picked[:, None], -1)[:, 0]
        return top, got, logits.std(-1), logits.argmax(-1).astype(jnp.int32)
    return jax.jit(gaps)


def _pad(n, m):
    return -(-n // m) * m


def token_gaps(weights: dict, hf: dict, tokens: np.ndarray, rows: np.ndarray,
               picked: np.ndarray, precision: str = "fp32") -> dict:
    """Teacher-forced logits of ``tokens`` at positions ``rows`` (the row
    at position p predicts token p + 1), reduced per row: the best logit,
    the logit of ``picked``, the logits' standard deviation and the
    argmax.  ``precision``: "fp32" (the reference), "int8" or "int4" (the
    control).  Arrays of length len(rows)."""
    bits = {"fp32": 0, "int8": 8, "int4": 4}[precision]
    n = len(tokens)
    tok = np.zeros(_pad(n, SEQ_BUCKET), np.int32)
    tok[:n] = tokens
    r = len(rows)
    rr = np.zeros(_pad(r, ROW_BUCKET), np.int32)
    rr[:r] = rows
    pk = np.zeros(len(rr), np.int32)
    pk[:r] = picked
    hfk = tuple(sorted((k, v) for k, v in hf.items()
                       if isinstance(v, (int, float, str, bool))))
    hid = _hidden_fn(hfk, bits)(weights, jnp.asarray(tok),
                                jnp.asarray(n, jnp.int32), jnp.asarray(rr))
    top, got, std, arg = _gap_fn(bool(hf["tie_word_embeddings"]), bits)(
        weights, hid, jnp.asarray(pk))
    return {"top": np.asarray(top)[:r], "picked": np.asarray(got)[:r],
            "std": np.asarray(std)[:r], "argmax": np.asarray(arg)[:r]}


def check_program_config(hf: dict, cfg) -> list[str]:
    """Where the program's model config departs from the published one."""
    want = {"n_layers": hf["num_hidden_layers"], "d_model": hf["hidden_size"],
            "n_heads": hf["num_attention_heads"],
            "n_kv_heads": hf["num_key_value_heads"],
            "resolved_head_dim": hf["head_dim"],
            "d_ff": hf["intermediate_size"], "vocab_size": hf["vocab_size"],
            "vocab_padded": hf["vocab_size"],
            "rope_theta": float(hf["rope_theta"]),
            "norm_eps": hf["rms_norm_eps"], "qk_norm": True,
            "tie_embeddings": hf["tie_word_embeddings"],
            "act": hf["hidden_act"], "attn_bias": hf["attention_bias"],
            "family": "dense"}
    return [f"{k}: program {getattr(cfg, k)!r}, published {v!r}"
            for k, v in want.items() if getattr(cfg, k) != v]
