"""Unified ragged paged attention parity (DESIGN §12).

Grid: one MIXED work-list — a prefill chunk mid-prompt, a decode row, a
speculative tail, and a from-scratch prefill chunk packed into a single
flattened stream — x GQA {1, 4} x KV {int8, bf16} x mesh {1x1, 2x2,
4x1}, checked against (a) the fp32 gather oracle
(``kernels.ref.ragged_attention_ref``), (b) the dense chunked-attention
oracle per item, and (c) the EXISTING per-shape paged kernels serving
each item at its own legacy shape.  MXU-aligned builds run the Pallas
body in interpret mode on CPU CI; the engine-shape build exercises the
gather fallback.  Plus the engine-level regression that the ragged path
dispatches strictly less padding than the bucketed per-shape path on a
mixed workload.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qscheme import dequant, quant
from repro.kernels import ops
from repro.kernels.ref import ragged_attention_ref
from repro.launch.mesh import make_mesh
from repro.models.attention import _repeat_kv, chunked_attention

NKV = 4
SMAX, DK = 256, 128

# the mixed step: (q_len, kv_len) per sequence — a 32-token prefill
# chunk continuing 128 resident rows, a decode row at context 131, a
# 5-token speculative tail rooted at context 32, and a fresh 16-token
# prefill opening a sequence
ITEMS = ((32, 160), (1, 131), (5, 37), (16, 16))


def _build_mixed(seed, kvh, groups, kv, *, bs=128, smax=SMAX, dk=DK,
                 items=ITEMS):
    """Pack the ITEMS work-list into one stream over a shuffled pool.

    Returns (q_stream, k_pool, v_pool, bt, q_start, q_len, kv_len, nkv,
    qf, kd, vd): qf/kd/vd are the fp32 dense per-sequence views the
    oracle consumes (kd/vd dequantized, length smax per sequence)."""
    rng = np.random.default_rng(seed)
    h = kvh * groups
    nbmax = smax // bs
    ns = len(items)
    t = sum(q for q, _ in items)
    q = jnp.asarray(rng.normal(size=(t, h, dk)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(ns, smax, kvh, dk)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(ns, smax, kvh, dk)), jnp.float32)
    if kv == "int8":
        kc, vc = quant(kf, NKV, 8), quant(vf, NKV, 8)
        kd, vd = dequant(kc, NKV), dequant(vc, NKV)
        nkv = NKV
    else:
        kc, vc = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
        kd, vd = kc.astype(jnp.float32), vc.astype(jnp.float32)
        q = q.astype(jnp.bfloat16)
        nkv = None
    nb = 1 + ns * nbmax
    bt = rng.permutation(np.arange(1, nb)).reshape(ns, nbmax).astype(np.int32)
    kp = np.zeros((nb, bs, kvh, dk), np.asarray(kc).dtype)
    vp = np.zeros_like(kp)
    for s in range(ns):
        for i in range(nbmax):
            kp[bt[s, i]] = np.asarray(kc[s, i * bs:(i + 1) * bs])
            vp[bt[s, i]] = np.asarray(vc[s, i * bs:(i + 1) * bs])
    q_len = np.asarray([ql for ql, _ in items], np.int32)
    kv_len = np.asarray([kl for _, kl in items], np.int32)
    q_start = np.concatenate([[0], np.cumsum(q_len)[:-1]]).astype(np.int32)
    return (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
            jnp.asarray(q_start), jnp.asarray(q_len), jnp.asarray(kv_len),
            nkv, q.astype(jnp.float32), kd, vd)


def _tol(kv):
    return dict(atol=2e-2, rtol=2e-2) if kv == "bf16" else \
        dict(atol=1e-4, rtol=1e-4)


def _check_vs_dense(out, qf, kd, vd, groups, items, kv):
    """Every work-list item against the dense chunked-attention oracle
    at its own (q_len, kv_len) — the dataflow the ragged kernel fuses."""
    off = 0
    for s, (ql, kl) in enumerate(items):
        ref = chunked_attention(
            qf[None, off:off + ql], _repeat_kv(kd[s:s + 1, :kl], groups),
            _repeat_kv(vd[s:s + 1, :kl], groups), causal=True,
            q_offset=jnp.asarray(kl - ql, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(out[off:off + ql], np.float32),
            np.asarray(ref[0], np.float32),
            err_msg=f"item {s} (q_len={ql}, kv_len={kl})", **_tol(kv))
        off += ql


@pytest.mark.parametrize("kv", ["int8", "bf16"])
@pytest.mark.parametrize("groups", [1, 4])
def test_ragged_mixed_parity(groups, kv):
    """One pallas_call (interpret on CPU) serves the whole mixed step:
    matches both the gather oracle and the dense oracle per item."""
    (q, kp, vp, bt, qs, ql, kl, nkv, qf, kd, vd) = \
        _build_mixed(3, 2, groups, kv)
    out = ops.ragged_attention(q, kp, vp, bt, qs, ql, kl,
                               kv_frac_bits=nkv, tq_max=32)
    oracle = ragged_attention_ref(qf, kp, vp, bt, qs, ql, kl,
                                  kv_frac_bits=nkv)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle, np.float32), **_tol(kv))
    _check_vs_dense(out, qf, kd, vd, groups, ITEMS, kv)


@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_ragged_matches_per_shape_kernels(kv):
    """The unified call reproduces what the RETIRED per-shape dispatches
    computed: each item re-served at its legacy shape through
    ``ops.paged_attention`` (fused decode kernel / chunk reference) must
    match its rows of the ragged output."""
    groups = 2
    (q, kp, vp, bt, qs, ql, kl, nkv, qf, kd, vd) = \
        _build_mixed(7, 2, groups, kv)
    out = ops.ragged_attention(q, kp, vp, bt, qs, ql, kl,
                               kv_frac_bits=nkv, tq_max=32)
    off = 0
    for s, (ql_i, kl_i) in enumerate(ITEMS):
        legacy = ops.paged_attention(
            q[None, off:off + ql_i], kp, vp, bt[s:s + 1],
            (kl_i - ql_i + jnp.arange(ql_i, dtype=jnp.int32))[None],
            kv_frac_bits=nkv)
        np.testing.assert_allclose(
            np.asarray(out[off:off + ql_i], np.float32),
            np.asarray(legacy[0], np.float32),
            err_msg=f"item {s} (q_len={ql_i}, kv_len={kl_i})", **_tol(kv))
        off += ql_i


@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_ragged_fallback_small_dims(kv):
    """Engine smoke shapes (block 16, head_dim 16) refuse the kernel and
    take the gather reference — same contract, same mixed step."""
    items = ((8, 40), (1, 33), (3, 11), (4, 4))
    (q, kp, vp, bt, qs, ql, kl, nkv, qf, kd, vd) = \
        _build_mixed(5, 2, 2, kv, bs=16, smax=64, dk=16, items=items)
    out = ops.ragged_attention(q, kp, vp, bt, qs, ql, kl, kv_frac_bits=nkv)
    _check_vs_dense(out, qf, kd, vd, 2, items, kv)


def test_ragged_small_dims_raise_off_cpu(monkeypatch):
    """Off the CPU the gather reference is never a silent stand-in: a
    shape the kernel cannot take raises."""
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    (q, kp, vp, bt, qs, ql, kl, nkv, *_rest) = _build_mixed(
        5, 2, 2, "int8", bs=16, smax=64, dk=16, items=((8, 40), (1, 33)))
    with pytest.raises(ValueError, match="block_size=16, dk=16"):
        ops.ragged_attention(q, kp, vp, bt, qs, ql, kl, kv_frac_bits=nkv)


@pytest.mark.parametrize("mesh_shape", [(2, 2), (1, 4)])
@pytest.mark.parametrize("groups", [1, 4])
def test_ragged_sharded_parity(groups, mesh_shape):
    """4-device shard_map case (DESIGN §8 composes unchanged): pool and
    stream head-sharded over 'model', descriptors replicated — must match
    the single-device dense oracle."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (tests/conftest.py forces them)")
    mesh = make_mesh(mesh_shape, ("data", "model"))
    (q, kp, vp, bt, qs, ql, kl, nkv, qf, kd, vd) = \
        _build_mixed(9, 4, groups, "int8")
    out = ops.ragged_attention(q, kp, vp, bt, qs, ql, kl,
                               kv_frac_bits=nkv, tq_max=32, mesh=mesh)
    _check_vs_dense(out, qf, kd, vd, groups, ITEMS, "int8")


def test_ragged_non_dividing_heads_raise():
    """No-silent-fallback contract: a tensor axis that would split a GQA
    group is refused at the ops level, like every other flash kernel."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = make_mesh((1, 4), ("data", "model"))
    (q, kp, vp, bt, qs, ql, kl, nkv, *_rest) = _build_mixed(11, 2, 1, "int8")
    with pytest.raises(NotImplementedError, match=r"KV head count \(2\)"):
        ops.ragged_attention(q, kp, vp, bt, qs, ql, kl,
                             kv_frac_bits=nkv, mesh=mesh)


def test_ragged_padding_rows_zero():
    """Stream rows covered by no descriptor are EXACTLY zero — on the
    kernel path they are never written (the wrapper pins them), on the
    gather path the all-masked softmax NaN is pinned the same way."""
    for bs, smax, dk in ((128, 256, 128), (16, 64, 16)):
        (q, kp, vp, bt, *_rest) = _build_mixed(
            13, 2, 2, "int8", bs=bs, smax=smax, dk=dk,
            items=((8, 16), (8, 16)))
        # 16 stream rows, but the descriptors claim only 9 of them
        qs = jnp.asarray([0, 8], jnp.int32)
        ql = jnp.asarray([8, 1], jnp.int32)
        kl = jnp.asarray([16, 9], jnp.int32)
        out = ops.ragged_attention(q, kp, vp, bt, qs, ql, kl,
                                   kv_frac_bits=NKV, tq_max=8)
        pad = np.asarray(out)[9:]
        assert np.all(pad == 0), "unclaimed stream rows must be zero"
        assert np.all(np.isfinite(np.asarray(out)))


def test_ragged_int8_requires_frac_bits():
    (q, kp, vp, bt, qs, ql, kl, *_rest) = _build_mixed(15, 2, 1, "int8")
    with pytest.raises(ValueError, match="kv_frac_bits"):
        ops.ragged_attention(q, kp, vp, bt, qs, ql, kl)
