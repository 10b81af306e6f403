"""Multi-device parity harness for the shard_map'd flash kernels (DESIGN §8).

Runs on a forced 4-device CPU backend (``conftest.py`` sets
``--xla_force_host_platform_device_count=4``; the CI ``multidevice`` job
exports it explicitly).  Every combination of

    {prefill, decode} x GQA {1, 4} x KV {int8, bf16} x mesh {1x1, 2x2,
    4x1, 1x4}   ((data, model) shapes)

is compared against the SINGLE-DEVICE pure-JAX ``chunked_attention``
oracle evaluated in fp32 — the sharded fused path must agree to fp32
tolerances, and it must NOT demote to the chunked path on multi-device
meshes (the pre-PR-2 behavior this harness exists to prevent).

Dims are chosen so the Pallas kernel genuinely launches on EVERY shard of
every mesh (per-shard sq >= 16, skv >= 128, dk = dv = 128, cache length
with an MXU tile divisor); smaller dims would silently compare the
fallback against itself.  kvh = 4 divides every model-axis size used, so
whole GQA groups land on each shard.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qscheme import dequant, quant
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models.attention import _repeat_kv, chunked_attention

NKV = 4                # Eq.-1 fractional bits for the int8 KV grid
B, SQ, SMAX = 4, 256, 256
KVH, DK, DV = 4, 128, 128

MESHES = {"1x1": (1, 1), "2x2": (2, 2), "4x1": (4, 1), "1x4": (1, 4)}


def _mesh(name):
    d, m = MESHES[name]
    if jax.device_count() < d * m:
        pytest.skip(f"needs {d * m} devices, have {jax.device_count()}")
    return make_mesh((d, m), ("data", "model"))


def _make_qkv(seed, groups, kv):
    """Returns (q, k, v) as the kernel sees them and (qf, kf, vf) as the
    fp32 oracle sees them (dequantized codes / upcast bf16)."""
    h = KVH * groups
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, SQ, h, DK)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(B, SMAX, KVH, DK)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(B, SMAX, KVH, DV)), jnp.float32)
    if kv == "int8":
        k, v = quant(kf, NKV, 8), quant(vf, NKV, 8)
        return q, k, v, q, dequant(k, NKV), dequant(v, NKV)
    q16 = q.astype(jnp.bfloat16)
    k16, v16 = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
    return (q16, k16, v16, q16.astype(jnp.float32),
            k16.astype(jnp.float32), v16.astype(jnp.float32))


def _tol(kv):
    # acceptance: atol <= 2e-2 vs the fp32 chunked reference.  fp32/int8
    # differs only by reassociation; bf16 carries the cast error.
    return dict(atol=2e-2, rtol=2e-2) if kv == "bf16" else \
        dict(atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
@pytest.mark.parametrize("kv", ["int8", "bf16"])
@pytest.mark.parametrize("groups", [1, 4])
def test_prefill_parity(groups, kv, mesh_name):
    mesh = _mesh(mesh_name)
    q, k, v, qf, kf, vf = _make_qkv(3, groups, kv)
    nkv = NKV if kv == "int8" else None
    out = ops.flash_attention(q, k, v, causal=True, kv_frac_bits=nkv,
                              mesh=mesh)
    ref = chunked_attention(qf, _repeat_kv(kf, groups),
                            _repeat_kv(vf, groups), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(kv))


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
@pytest.mark.parametrize("kv", ["int8", "bf16"])
@pytest.mark.parametrize("groups", [1, 4])
def test_decode_parity(groups, kv, mesh_name):
    mesh = _mesh(mesh_name)
    q, k, v, qf, kf, vf = _make_qkv(5, groups, kv)
    q, qf = q[:, :1], qf[:, :1]
    nkv = NKV if kv == "int8" else None
    for pos in (0, 131, SMAX - 1):
        pos_t = jnp.asarray(pos, jnp.int32)       # traced, like a real step
        out = ops.flash_decode(q, k, v, pos=pos_t, kv_frac_bits=nkv,
                               mesh=mesh)
        ref = chunked_attention(qf, _repeat_kv(kf, groups),
                                _repeat_kv(vf, groups), causal=True,
                                q_offset=pos_t)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   err_msg=f"pos={pos}", **_tol(kv))


def test_sharded_grad_parity():
    """Float-KV training path: the custom VJP (fused forward, chunked-
    recompute backward) must differentiate correctly THROUGH the shard_map
    boundary — gradients match differentiating the oracle directly."""
    mesh = _mesh("2x2")
    q, k, v, qf, kf, vf = _make_qkv(7, 4, "int8")  # fp32 q; use float KV
    k, v = kf, vf

    def loss_flash(q_, k_, v_):
        out = ops.flash_attention(q_, k_, v_, causal=True, mesh=mesh)
        return jnp.sum(out ** 2)

    def loss_ref(q_, k_, v_):
        out = chunked_attention(q_, _repeat_kv(k_, 4), _repeat_kv(v_, 4),
                                causal=True)
        return jnp.sum(out ** 2)

    g_fl = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3)


# ---------------------------------------------------------------------------
# no silent fallback / explicit errors (launch/steps resolver)
# ---------------------------------------------------------------------------

def test_no_demotion_on_multi_device_mesh():
    """_resolve_attn_kernel must KEEP flash on a multi-device mesh whose
    tensor axis divides the KV heads (pre-PR-2 it silently demoted to
    chunked — the hottest serving path ran unfused)."""
    from repro.configs import get_smoke_config
    from repro.launch.steps import _resolve_attn_kernel
    cfg = get_smoke_config("qwen3_1_7b")          # n_kv_heads = 2
    mesh = _mesh("2x2")                           # model axis = 2, divides
    out = _resolve_attn_kernel(cfg, "flash", mesh)
    assert out.attn_kernel == "flash"


def test_non_dividing_mesh_raises():
    """Mesh shapes that would split a GQA group get an explicit error at
    build time, never a silent fallback."""
    from repro.configs import get_smoke_config
    from repro.launch.steps import _resolve_attn_kernel, build_serve_step
    from repro.core.qmodel import QuantContext, QuantMode
    cfg = get_smoke_config("qwen3_1_7b")          # n_kv_heads = 2
    mesh = _mesh("1x4")                           # model axis = 4: 2 % 4 != 0
    with pytest.raises(NotImplementedError,
                       match=r"must divide the KV head count \(2"):
        _resolve_attn_kernel(cfg, "flash", mesh)
    # the step builders surface the same error
    with pytest.raises(NotImplementedError, match="KV head count"):
        build_serve_step(cfg, QuantContext(mode=QuantMode.FP),
                         attn_kernel="flash", mesh=mesh)


def test_mla_resolver_checks_full_head_count():
    """MLA's flash prefill shards kvh == n_heads (n_kv_heads is nominal
    there): the build-time check must validate the head count the kernel
    actually partitions."""
    from repro.configs import get_smoke_config
    from repro.launch.steps import _resolve_attn_kernel
    mesh = _mesh("1x4")
    cfg = get_smoke_config("deepseek_v3_671b")    # MLA, n_heads = 4
    # nominal n_kv_heads would NOT divide, but n_heads does -> accepted
    cfg = dataclasses.replace(cfg, n_kv_heads=2)
    assert _resolve_attn_kernel(cfg, "flash", mesh).attn_kernel == "flash"
    # and an MLA head count that doesn't divide is refused with the
    # MLA-labeled message
    bad = dataclasses.replace(cfg, n_heads=6)
    with pytest.raises(NotImplementedError, match="n_heads for MLA"):
        _resolve_attn_kernel(bad, "flash", mesh)


def test_non_model_shard_axis_raises():
    """Only 'model' is threaded through the cache/activation sharding
    rules; other axes must be refused, not silently reshard the cache."""
    from repro.configs import get_smoke_config
    from repro.launch.steps import _resolve_attn_kernel
    cfg = dataclasses.replace(get_smoke_config("qwen3_1_7b"),
                              attn_shard_axis="data")
    with pytest.raises(NotImplementedError, match="attn_shard_axis"):
        _resolve_attn_kernel(cfg, "flash", _mesh("2x2"))


def test_ops_level_divisibility_backstop():
    """Direct ops calls (no cfg) hit the same check inside the wrapper."""
    mesh = _mesh("1x4")
    q, k, v, *_ = _make_qkv(9, 1, "int8")
    with pytest.raises(NotImplementedError, match=r"KV head count \(3\)"):
        ops.flash_attention(q[:, :, :3], k[:, :, :3], v[:, :, :3],
                            causal=True, kv_frac_bits=NKV, mesh=mesh)


# ---------------------------------------------------------------------------
# model-level: sharded flash serve step vs single-device chunked
# ---------------------------------------------------------------------------

def test_end_to_end_sharded_flash_decode():
    """jit'd serve step on a (1, 2) mesh with attn_kernel='flash' + int8 KV
    cache matches the single-device chunked dequantize-then-attend path:
    the full steps -> model -> shard_map'd kernel wiring, including the
    head-sharded cache constraint."""
    from repro.configs import get_smoke_config
    from repro.core.qmodel import QuantContext, QuantMode
    from repro.launch import steps as S
    from repro.models import model as M
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    ctx = QuantContext(mode=QuantMode.FP)
    cfg8 = dataclasses.replace(
        get_smoke_config("qwen3_1_7b").scaled(dtype="float32",
                                              head_dim=128),
        kv_cache_bits=8)                          # n_heads=4, n_kv_heads=2
    cfg8f = dataclasses.replace(cfg8, attn_kernel="flash")
    mesh = make_mesh((1, 2), ("data", "model"))
    params = M.init_params(cfg8, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 121), 0,
                              cfg8.vocab_size)
    pre = {"tokens": toks[:, :120]}

    # reference: single-device chunked
    _, cache = M.prefill(params, pre, cfg8, ctx, max_seq=128)
    l_ref, _ = M.decode_step(params, toks[:, 120:], cache,
                             jnp.asarray(120), cfg8, ctx)

    # sharded flash: builders thread the mesh; prefill writes int8 codes
    prefill_fn = jax.jit(S.build_prefill_step(cfg8f, ctx, mesh=mesh,
                                              max_seq=128))
    serve_fn = jax.jit(S.build_serve_step(cfg8f, ctx, mesh=mesh))
    _, cache_f = prefill_fn(params, pre)
    assert cache_f["kv"].k.dtype == jnp.int8
    tok_f, _ = serve_fn(params, toks[:, 120:], cache_f, jnp.asarray(120))

    tok_ref = jnp.argmax(l_ref, axis=-1).astype(jnp.int32)[:, None]
    np.testing.assert_array_equal(np.asarray(tok_f), np.asarray(tok_ref))


def test_flash_cache_rules_head_sharded():
    """cache_sharding_rules(attn_kernel='flash') keeps the KV cache
    partitioned on heads (shard residency) instead of sequence."""
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.distributed import sharding as shd
    from repro.launch import steps as S
    mesh = _mesh("2x2")
    cfg = get_smoke_config("qwen3_1_7b")          # n_kv_heads = 2
    cache_abs = S.abstract_cache(cfg, batch=4, max_seq=128)
    flash = shd.cache_sharding_rules(cache_abs, mesh, attn_kernel="flash")
    chunked = shd.cache_sharding_rules(cache_abs, mesh)
    assert flash["kv"].k[3] == "model" and flash["kv"].k[2] is None
    assert chunked["kv"].k[2] == "model" and chunked["kv"].k[3] is None
