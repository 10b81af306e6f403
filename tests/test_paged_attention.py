"""Paged attention parity vs the dense-cache oracle (DESIGN §9).

Grid: {int8, bf16} KV x GQA {1, 4}, per-slot positions, SHUFFLED block
tables (blocks physically scattered through the pool — catching any
implicit logical==physical assumption), plus the fused-kernel fallback
shapes, multi-token chunk queries, and a 4-device shard_map case riding
``tests/conftest.py``'s forced CPU mesh.  The dense oracle is the
pure-JAX ``chunked_attention`` over the dequantized, repeated cache — the
exact dataflow the paged kernel deletes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qscheme import dequant, quant
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models.attention import _repeat_kv, chunked_attention

NKV = 4
B, SMAX, DK = 4, 256, 128
POS = (0, 131, 255, 77)         # per-slot live positions, incl. edges


def _build_pool(seed, kvh, groups, kv, *, bs=128, smax=SMAX, dk=DK):
    """Dense (B, S, KVH, D) K/V chopped into blocks scattered through a
    pool via a SHUFFLED block table; returns kernel + oracle views."""
    rng = np.random.default_rng(seed)
    h = kvh * groups
    nbmax = smax // bs
    q = jnp.asarray(rng.normal(size=(B, 1, h, dk)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(B, smax, kvh, dk)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(B, smax, kvh, dk)), jnp.float32)
    if kv == "int8":
        kc, vc = quant(kf, NKV, 8), quant(vf, NKV, 8)
        kd, vd = dequant(kc, NKV), dequant(vc, NKV)
        nkv = NKV
    else:
        kc, vc = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
        kd, vd = kc.astype(jnp.float32), vc.astype(jnp.float32)
        q = q.astype(jnp.bfloat16)
        nkv = None
    nb = 1 + B * nbmax
    bt = rng.permutation(np.arange(1, nb)).reshape(B, nbmax).astype(np.int32)
    kp = np.zeros((nb, bs, kvh, dk), np.asarray(kc).dtype)
    vp = np.zeros_like(kp)
    for b_ in range(B):
        for i in range(nbmax):
            kp[bt[b_, i]] = np.asarray(kc[b_, i * bs:(i + 1) * bs])
            vp[bt[b_, i]] = np.asarray(vc[b_, i * bs:(i + 1) * bs])
    return (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
            q.astype(jnp.float32), kd, vd, nkv)


def _tol(kv):
    return dict(atol=2e-2, rtol=2e-2) if kv == "bf16" else \
        dict(atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kv", ["int8", "bf16"])
@pytest.mark.parametrize("groups", [1, 4])
def test_paged_decode_parity(groups, kv):
    """Fused paged kernel (MXU-aligned shapes) vs dense chunked oracle at
    per-slot positions through a shuffled block table."""
    q, kp, vp, bt, qf, kd, vd, nkv = _build_pool(3, 2, groups, kv)
    pos = jnp.asarray(np.asarray(POS, np.int32))[:, None]
    out = ops.paged_attention(q, kp, vp, bt, pos, kv_frac_bits=nkv)
    for b_ in range(B):
        ref = chunked_attention(
            qf[b_:b_ + 1], _repeat_kv(kd[b_:b_ + 1], groups),
            _repeat_kv(vd[b_:b_ + 1], groups), causal=True,
            q_offset=jnp.asarray(POS[b_], jnp.int32))
        np.testing.assert_allclose(
            np.asarray(out[b_], np.float32), np.asarray(ref[0], np.float32),
            err_msg=f"slot {b_} pos {POS[b_]}", **_tol(kv))


@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_paged_decode_fallback_small_dims(kv):
    """Engine smoke shapes (block 16, head_dim 16) refuse the kernel and
    take the reference gather path — same contract."""
    q, kp, vp, bt, qf, kd, vd, nkv = _build_pool(5, 2, 2, kv, bs=16,
                                                 smax=64, dk=16)
    pos = jnp.asarray(np.asarray([0, 17, 63, 31], np.int32))[:, None]
    out = ops.paged_attention(q, kp, vp, bt, pos, kv_frac_bits=nkv)
    for b_ in range(B):
        ref = chunked_attention(
            qf[b_:b_ + 1], _repeat_kv(kd[b_:b_ + 1], 2),
            _repeat_kv(vd[b_:b_ + 1], 2), causal=True,
            q_offset=jnp.asarray(int(pos[b_, 0]), jnp.int32))
        np.testing.assert_allclose(
            np.asarray(out[b_], np.float32), np.asarray(ref[0], np.float32),
            **_tol(kv))


def test_paged_chunk_prefill_parity():
    """Multi-token chunk (C > 1) with per-query positions — the chunked-
    prefill path — matches the dense oracle at the chunk's offset."""
    q, kp, vp, bt, qf, kd, vd, nkv = _build_pool(7, 2, 2, "int8")
    rng = np.random.default_rng(11)
    C, start = 32, 100
    qc = jnp.asarray(rng.normal(size=(1, C, 4, DK)), jnp.float32)
    qpos = (start + jnp.arange(C))[None]
    out = ops.paged_attention(qc, kp, vp, bt[:1], qpos, kv_frac_bits=nkv)
    ref = chunked_attention(qc, _repeat_kv(kd[:1], 2), _repeat_kv(vd[:1], 2),
                            causal=True, q_offset=jnp.asarray(start,
                                                              jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("mesh_shape", [(2, 2), (1, 4)])
@pytest.mark.parametrize("groups", [1, 4])
def test_paged_decode_sharded_parity(groups, mesh_shape):
    """4-device shard_map case: pool head-sharded over 'model', block
    tables + positions replicated across it — must match the single-device
    oracle exactly like the dense flash path does (DESIGN §8/§9)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (tests/conftest.py forces them)")
    mesh = make_mesh(mesh_shape, ("data", "model"))
    q, kp, vp, bt, qf, kd, vd, nkv = _build_pool(9, 4, groups, "int8")
    pos = jnp.asarray(np.asarray(POS, np.int32))[:, None]
    out = ops.paged_attention(q, kp, vp, bt, pos, kv_frac_bits=nkv,
                              mesh=mesh)
    for b_ in range(B):
        ref = chunked_attention(
            qf[b_:b_ + 1], _repeat_kv(kd[b_:b_ + 1], groups),
            _repeat_kv(vd[b_:b_ + 1], groups), causal=True,
            q_offset=jnp.asarray(POS[b_], jnp.int32))
        np.testing.assert_allclose(
            np.asarray(out[b_], np.float32), np.asarray(ref[0], np.float32),
            err_msg=f"slot {b_}", atol=1e-4, rtol=1e-4)


def test_paged_non_dividing_heads_raise():
    """Same no-silent-fallback contract as the dense kernels: a tensor
    axis that would split a GQA group is refused at the ops level."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = make_mesh((1, 4), ("data", "model"))
    q, kp, vp, bt, *_ , nkv = _build_pool(13, 2, 1, "int8")
    pos = jnp.asarray(np.asarray(POS, np.int32))[:, None]
    with pytest.raises(NotImplementedError, match=r"KV head count \(2\)"):
        ops.paged_attention(q, kp, vp, bt, pos, kv_frac_bits=nkv, mesh=mesh)


def test_paged_pool_sharding_rule_head_sharded():
    """cache_sharding_rules places the pool head-sharded on 'model' with
    NO batch/sequence sharding (the pool is shared by every slot)."""
    import dataclasses as dc
    from repro.configs import get_smoke_config
    from repro.distributed import sharding as shd
    from repro.launch import steps as S
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = make_mesh((2, 2), ("data", "model"))
    cfg = dc.replace(get_smoke_config("qwen3_1_7b"), kv_cache_bits=8)
    abs_pool = S.abstract_paged_cache(cfg, num_blocks=9, block_size=16)
    spec = shd.cache_sharding_rules(abs_pool, mesh, attn_kernel="flash")
    k_spec = spec["paged_kv"].k
    assert k_spec[3] == "model"
    assert all(k_spec[i] is None for i in (0, 1, 2, 4))


def test_int8_pool_requires_frac_bits():
    q, kp, vp, bt, *_ = _build_pool(15, 2, 1, "int8")
    pos = jnp.asarray(np.asarray(POS, np.int32))[:, None]
    with pytest.raises(ValueError, match="kv_frac_bits"):
        ops.paged_attention(q, kp, vp, bt, pos)
