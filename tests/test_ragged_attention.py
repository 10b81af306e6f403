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
from repro.kernels.ragged_flash import _walk, ragged_walk_counts
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

# the kernel's walk copies P = 8 pool blocks of 128 rows per grid step
# (``ragged_flash._walk``); these lists sit on its edges over 9-block
# (1152-row) tables, a block count P does not divide.  EDGES: kv_len at
# P*bs - 1, P*bs and P*bs + 1 (the last a 3-token spec tail), a sequence
# of one live block, and one of a part block.  PADDED: padding slots
# (q_len == kv_len == 0) between live ones, a spec tail, a prefill chunk
WALK_SMAX = 1152
EDGES = ((1, 1023), (1, 1024), (3, 1025), (1, 128), (2, 100))
PADDED = ((8, 200), (0, 0), (1, 1025), (0, 0), (5, 37))
# WIDE: a 2400-row chunk over 8 KV heads, whose q window leaves VMEM for
# fewer than 8 KV heads a grid step — each head step copies its own
# lanes of every pool block and owns its own q/o lanes
WIDE_SMAX = 2560
WIDE = ((2400, 2500), (0, 0), (1, 1025), (3, 140))
# name -> (work-list, table rows, KV heads)
WORKLISTS = {"mixed": (ITEMS, SMAX, 2), "walk_edges": (EDGES, WALK_SMAX, 2),
             "padding_slots": (PADDED, WALK_SMAX, 2),
             "wide_chunk_head_steps": (WIDE, WIDE_SMAX, 8)}


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


def _gather_oracle_at(rows, qf, kp, vp, bt, qs, ql, kl, nkv):
    """The gather oracle at the given stream rows: each row restated as a
    one-row sequence at its own absolute position.  The oracle gathers a
    whole table per row, so this is the same per-row math at the memory
    of ``len(rows)`` rows."""
    qs, ql, kl = (np.asarray(a) for a in (qs, ql, kl))
    seq = np.searchsorted(qs + ql, rows, side="right")
    n = len(rows)
    return ragged_attention_ref(
        qf[rows], kp, vp, jnp.asarray(bt)[seq],
        jnp.arange(n, dtype=jnp.int32), jnp.ones(n, jnp.int32),
        jnp.asarray(kl[seq] - ql[seq] + rows - qs[seq] + 1, jnp.int32),
        kv_frac_bits=nkv)


def _check_vs_dense(out, qf, kd, vd, groups, items, kv):
    """Every work-list item against the dense chunked-attention oracle
    at its own (q_len, kv_len) — the dataflow the ragged kernel fuses."""
    off = 0
    for s, (ql, kl) in enumerate(items):
        if ql == 0:
            continue                                    # padding slot
        ref = chunked_attention(
            qf[None, off:off + ql], _repeat_kv(kd[s:s + 1, :kl], groups),
            _repeat_kv(vd[s:s + 1, :kl], groups), causal=True,
            q_offset=jnp.asarray(kl - ql, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(out[off:off + ql], np.float32),
            np.asarray(ref[0], np.float32),
            err_msg=f"item {s} (q_len={ql}, kv_len={kl})", **_tol(kv))
        off += ql


@pytest.mark.parametrize("worklist", list(WORKLISTS))
@pytest.mark.parametrize("kv", ["int8", "bf16"])
@pytest.mark.parametrize("groups", [1, 4])
def test_ragged_mixed_parity(groups, kv, worklist):
    """One pallas_call (interpret on CPU) serves the whole mixed step:
    matches both the gather oracle and the dense oracle per item."""
    items, smax, kvh = WORKLISTS[worklist]
    tq = max(32, max(q for q, _ in items))
    (q, kp, vp, bt, qs, ql, kl, nkv, qf, kd, vd) = \
        _build_mixed(3, kvh, groups, kv, smax=smax, items=items)
    heads = _walk(**ops.ragged_call_shape(q.shape[0], kvh * groups, kvh,
                                          smax // 128, 128, DK, DK,
                                          tq_max=tq)).heads
    assert (heads < kvh) == worklist.startswith("wide")
    out = ops.ragged_attention(q, kp, vp, bt, qs, ql, kl,
                               kv_frac_bits=nkv, tq_max=tq)
    # every row of a short stream; about 32 rows spread over a wide one,
    # whose whole-stream gather would not fit the host's memory
    t = q.shape[0]
    rows = np.unique(np.r_[np.arange(0, t, max(1, t // 32)), t - 1])
    oracle = _gather_oracle_at(rows, qf, kp, vp, bt, qs, ql, kl, nkv)
    np.testing.assert_allclose(np.asarray(out, np.float32)[rows],
                               np.asarray(oracle, np.float32), **_tol(kv))
    _check_vs_dense(out, qf, kd, vd, groups, items, kv)


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


@pytest.mark.parametrize("mesh_shape,kvh", [((2, 2), 4), ((1, 4), 4),
                                            ((1, 4), 8)],
                         ids=["2x2", "1x4", "1x4_two_kv_heads_a_shard"])
@pytest.mark.parametrize("groups", [1, 4])
def test_ragged_sharded_parity(groups, mesh_shape, kvh):
    """4-device shard_map case (DESIGN §8 composes unchanged): pool and
    stream head-sharded over 'model', descriptors replicated — must match
    the single-device dense oracle."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (tests/conftest.py forces them)")
    mesh = make_mesh(mesh_shape, ("data", "model"))
    (q, kp, vp, bt, qs, ql, kl, nkv, qf, kd, vd) = \
        _build_mixed(9, kvh, groups, "int8")
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


def test_ragged_walk_counts_hand_built():
    """The kernel's walk from a hand-built descriptor set: every slot
    takes ceil(NBmax / P) steps per head step, and each head step copies
    ceil(kv_len / bs) blocks of each live slot, none of a padding slot's
    (kv_len 0)."""
    kv_len = np.asarray([0, 1, 128, 129, 1024, 1025, 0, 5000], np.int32)
    shapes = dict(h=16, kvh=8, nbmax=40, bs=128, dk=128, dv=128)
    # a decode stream: P = 8 blocks, all 8 KV heads in one step
    steps, blocks = ragged_walk_counts(kv_len, t_pad=16, tq=16, **shapes)
    assert steps == 8 * 5
    assert blocks == 0 + 1 + 1 + 2 + 8 + 9 + 0 + 40
    # a 4096-row chunk: its q window keeps 2 KV heads a step, so 4 head
    # steps each walk every slot and copy its live blocks again
    steps, blocks = ragged_walk_counts(kv_len, t_pad=4096, tq=4096,
                                       **shapes)
    assert (steps, blocks) == (4 * 8 * 5, 4 * 61)
    # a table P does not divide: the last step of each slot is partial
    steps, blocks = ragged_walk_counts([1152, 0], t_pad=8, tq=8,
                                       **dict(shapes, nbmax=9))
    assert (steps, blocks) == (2 * 2, 9)
    # all padding: the walk is the grid alone
    assert ragged_walk_counts(np.zeros(24, np.int32), t_pad=16, tq=16,
                              **shapes) == (24 * 5, 0)


def test_ragged_int8_requires_frac_bits():
    (q, kp, vp, bt, qs, ql, kl, *_rest) = _build_mixed(15, 2, 1, "int8")
    with pytest.raises(ValueError, match="kv_frac_bits"):
        ops.ragged_attention(q, kp, vp, bt, qs, ql, kl)
