"""Compile the serving main path's kernels for a TPU v5e without a chip.

The TPU compiler is installed with JAX and compiles for a DESCRIBED
``v5e:2x2`` topology, so tiling, VMEM and lowering errors that interpret
mode cannot see (block shapes whose last two dims are neither (8, 128)
multiples nor full, scoped-VMEM overruns) fail here instead of on the
chip.  Nothing runs: these tests only compile, at qwen3_1_7b's published
widths (16/8 heads, head_dim 128, d_model 2048, d_ff 6144, vocab 151936)
and at the stream buckets ``chip_smoke.py``'s engine settings dispatch
(chunk 512, 4 slots: T_pad from 8 up to 520).

The topology is described inside a module fixture — never at import —
because only one process may load the TPU library: under pytest-xdist
the worker that is handed this file loads it, the others never do.  The
persistent compilation cache is off while these compile (a TPU entry
written here could not be read back without a chip).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import make_flash_prefill
from repro.kernels.int8_matmul import make_int8_matmul
from repro.kernels.ragged_flash import (make_ragged_paged_flash,
                                       ragged_walk_counts)

H, KVH, D, BS = 16, 8, 128, 128          # qwen3_1_7b attention widths
D_MODEL, D_FF, VOCAB = 2048, 6144, 151936
N_SLOTS, CHUNK, NBMAX = 4, 512, 5        # chip_smoke engine settings
T_MIN, T_MAX = 8, 520                    # its smallest / largest bucket
BENCH_SLOTS, BENCH_NBMAX = 24, 40        # the benchmark's engine (bench/)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return ops.tpu_kernels(compiled.as_text())


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("t_pad", [T_MIN, T_MAX])
@pytest.mark.parametrize("heads", [(H, KVH), (H // 4, KVH // 4)],
                         ids=["one_chip", "model_shard_of_4"])
@pytest.mark.parametrize("kv_dtype", [jnp.int8, jnp.bfloat16],
                         ids=["int8_kv", "bf16_kv"])
def test_ragged_flash_compiles(one_chip, t_pad, heads, kv_dtype):
    """The unified ragged kernel lowers at the engine's smallest and
    largest stream bucket, for the whole model and for one shard of the
    (1, 4) mesh path (4/2 heads per shard)."""
    h, kvh = heads
    tq = min(CHUNK, t_pad)
    call = make_ragged_paged_flash(
        N_SLOTS, h, kvh, NBMAX, BS, t_pad, tq, D, D, score_scale=0.088,
        v_scale=1 / 16, out_dtype=jnp.bfloat16)
    nb = 1 + N_SLOTS * NBMAX
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)
    kernels = _compile(
        call, i32(N_SLOTS), i32(N_SLOTS), i32(N_SLOTS), i32(N_SLOTS, NBMAX),
        _sds(one_chip, (t_pad, h * D), jnp.bfloat16),
        _sds(one_chip, (nb, BS, kvh * D), kv_dtype),
        _sds(one_chip, (nb, BS, kvh * D), kv_dtype))
    assert kernels == {"ragged_paged_flash"}


@pytest.mark.parametrize("t_pad", [16, 536, 1024],
                         ids=["decode", "engine_max", "prefill"])
@pytest.mark.parametrize("kv_dtype", [jnp.int8, jnp.bfloat16],
                         ids=["int8_kv", "bf16_kv"])
def test_ragged_flash_bench_engine_compiles(one_chip, t_pad, kv_dtype):
    """The benchmark engine's own shapes (24 slots, 40-block tables,
    chunk 512): a decode-only stream, its widest stream (the 512-row
    prefill budget plus one row a slot: 536) and a wider one.  The call
    stays one kernel, and its walk is a few steps per slot."""
    tq = min(CHUNK, t_pad)
    call = make_ragged_paged_flash(
        BENCH_SLOTS, H, KVH, BENCH_NBMAX, BS, t_pad, tq, D, D,
        score_scale=0.088, v_scale=1 / 16, out_dtype=jnp.bfloat16)
    nb = 1 + BENCH_SLOTS * BENCH_NBMAX
    i32 = lambda *sh: _sds(one_chip, sh, jnp.int32)
    kernels = _compile(
        call, i32(BENCH_SLOTS), i32(BENCH_SLOTS), i32(BENCH_SLOTS),
        i32(BENCH_SLOTS, BENCH_NBMAX),
        _sds(one_chip, (t_pad, H * D), jnp.bfloat16),
        _sds(one_chip, (nb, BS, KVH * D), kv_dtype),
        _sds(one_chip, (nb, BS, KVH * D), kv_dtype))
    assert kernels == {"ragged_paged_flash"}
    steps, _ = ragged_walk_counts(
        np.zeros(BENCH_SLOTS, np.int32), h=H, kvh=KVH, nbmax=BENCH_NBMAX,
        bs=BS, t_pad=t_pad, tq=tq, dk=D, dv=D)
    assert steps <= BENCH_SLOTS * -(-BENCH_NBMAX // 8)


def test_ragged_flash_wide_chunk_compiles(one_chip):
    """A 4096-token prefill chunk: the q window's scratch outgrows the
    default scoped VMEM, so the kernel raises its own limit."""
    t_pad = tq = 4096
    s, nbmax = 8, 32
    call = make_ragged_paged_flash(
        s, H, KVH, nbmax, BS, t_pad, tq, D, D, score_scale=0.088,
        v_scale=1 / 16, out_dtype=jnp.bfloat16)
    nb = 1 + s * nbmax
    i32 = lambda *sh: _sds(one_chip, sh, jnp.int32)
    kernels = _compile(
        call, i32(s), i32(s), i32(s), i32(s, nbmax),
        _sds(one_chip, (t_pad, H * D), jnp.bfloat16),
        _sds(one_chip, (nb, BS, KVH * D), jnp.int8),
        _sds(one_chip, (nb, BS, KVH * D), jnp.int8))
    assert kernels == {"ragged_paged_flash"}


@pytest.mark.parametrize("m", [T_MIN, T_MAX])
@pytest.mark.parametrize("n", [D_FF, VOCAB], ids=["mlp_up", "lm_head"])
def test_int8_matmul_compiles(one_chip, m, n):
    """W8A8 matmuls at d_model 2048 into the MLP and LM-head widths, with
    the blocks the ops wrapper picks (the 151936-wide head is tiled, not
    padded)."""
    bm, bk, bn = ops._pick_blocks(m, D_MODEL, n)
    assert n % bn == 0 and D_MODEL % bk == 0
    mp = -(-m // bm) * bm
    call = make_int8_matmul(mp, D_MODEL, n, bm=bm, bk=bk, bn=bn, shift=7,
                            has_bias=False)
    kernels = _compile(call, _sds(one_chip, (mp, D_MODEL), jnp.int8),
                       _sds(one_chip, (D_MODEL, n), jnp.int8))
    assert kernels == {"int8_matmul"}


@pytest.mark.parametrize("kernel", ["int8_matmul", "ragged_paged_flash"])
def test_kernels_compile_under_highest_precision(one_chip, kernel):
    """The float32 references run the model (and so the kernels) under
    ``default_matmul_precision("highest")``; the kernels' narrow-operand
    dots pin their own precision, or Mosaic refuses the contraction."""
    if kernel == "int8_matmul":
        call = make_int8_matmul(T_MAX, D_MODEL, D_FF, bm=128, bk=512,
                                bn=512, shift=7, has_bias=False)
        args = (_sds(one_chip, (T_MAX, D_MODEL), jnp.int8),
                _sds(one_chip, (D_MODEL, D_FF), jnp.int8))
    else:
        call = make_ragged_paged_flash(
            N_SLOTS, H, KVH, NBMAX, BS, T_MAX, CHUNK, D, D,
            score_scale=0.088, v_scale=1 / 16, out_dtype=jnp.bfloat16)
        nb = 1 + N_SLOTS * NBMAX
        i32 = lambda *sh: _sds(one_chip, sh, jnp.int32)
        args = (i32(N_SLOTS), i32(N_SLOTS), i32(N_SLOTS),
                i32(N_SLOTS, NBMAX),
                _sds(one_chip, (T_MAX, H * D), jnp.bfloat16),
                _sds(one_chip, (nb, BS, KVH * D), jnp.int8),
                _sds(one_chip, (nb, BS, KVH * D), jnp.int8))
    with jax.default_matmul_precision("highest"):
        assert _compile(call, *args) == {kernel}


def test_flash_prefill_compiles(one_chip):
    """The dense-cache prefill kernel over int8 KV codes, heads in lanes."""
    sq = 512
    call = make_flash_prefill(
        1, H, KVH, sq, sq, D, D, bq=256, bk=512, causal=True, q_offset=0,
        sq=sq, skv=sq, score_scale=0.088, v_scale=1 / 16,
        k_dtype=jnp.int8, out_dtype=jnp.bfloat16)
    kernels = _compile(call, _sds(one_chip, (1, sq, H * D), jnp.bfloat16),
                       _sds(one_chip, (1, sq, KVH * D), jnp.int8),
                       _sds(one_chip, (1, sq, KVH * D), jnp.int8))
    assert kernels == {"flash_prefill"}


def test_ragged_step_compiles(one_chip, monkeypatch):
    """The whole 28-layer ragged serving step (bf16 weights, int8 KV
    pool) at the smallest bucket: the attention kernel is a custom call
    inside it.  The ops wrappers pick interpret mode from the default
    backend (the CPU here), so the test steers them to the TPU kernels."""
    from repro.configs import get_config
    from repro.core.qmodel import QuantContext, QuantMode
    from repro.launch import steps as S
    from repro.models import model as M
    from repro.models.attention import RaggedBatch
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen3_1_7b"), kv_cache_bits=8)

    def place(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    cache = place(jax.eval_shape(
        lambda: M.init_paged_cache(cfg, 1 + N_SLOTS * NBMAX, BS)))
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)
    rb = RaggedBatch(dest=i32(T_MIN), block_tables=i32(N_SLOTS, NBMAX),
                     q_start=i32(N_SLOTS), q_len=i32(N_SLOTS),
                     kv_len=i32(N_SLOTS), tq_max=CHUNK)
    step = S.build_ragged_step(cfg, QuantContext(mode=QuantMode.FP))
    kernels = _compile(step, params, i32(T_MIN), cache, i32(T_MIN), rb)
    assert kernels == {"ragged_paged_flash"}
