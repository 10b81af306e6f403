"""True-W8A8 serving differential parity rig (DESIGN §13).

The deploy path — pre-quantized int8 weight codes + activation quant at
module boundaries + the fused shift-requant matmul — must be BIT-EXACT
against the fp32 ``fake_quant`` dataflow oracle: a float implementation
of Eq. 1/3/5 (input/weight fake-quant, exact fp32 accumulate, bias
aligned to the accumulator grid, output rounded half-away onto the N_o
grid with int8 saturation).  At smoke scale every accumulator stays far
below 2^24 product-LSBs, so the float oracle's arithmetic is exact and
any code mismatch is a real dataflow divergence, not float noise.

Grid: {attention-proj, MLP, full layer, full model} modules bit-exact vs
the oracle; {greedy decode, spec-decode, prefix-shared prefill} engine
runs token-identical to the dense-INT reference engine (same calibrated
grids, weights quantized on the fly — the int8 passthrough makes the
codes identical by construction, so ANY drift is a kernel/container
bug) and within the calibrated error budget of the fp engine; plus the
§8 shard_map 4-device case on the CPU parity grid.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import qmodel
from repro.core.lm_calibrate import calibrate_lm
from repro.core.qmodel import (QuantContext, QuantMode, qlinear,
                               quantize_params)
from repro.core.qscheme import dequant, fake_quant, round_half_away
from repro.launch.mesh import make_mesh
from repro.models import common as common_lib
from repro.models import model as M
from repro.models import transformer as tfm
from repro.serving import Request, ServingEngine

SCALE = dict(dtype="float32", n_layers=2, d_model=64, n_heads=4,
             n_kv_heads=2, d_ff=128, head_dim=16)


def _cfg(**kw):
    cfg = get_smoke_config("qwen3_1_7b").scaled(**SCALE)
    return dataclasses.replace(cfg, kv_cache_bits=8, **kw)


# ---------------------------------------------------------------------------
# the fp32 fake_quant oracle — Eq. 1/3/5 in float arithmetic
# ---------------------------------------------------------------------------

def oracle_qlinear(ctx, name, x, w, b=None, *, use_kernel=True):
    """Float reference of one unified module's integer dataflow.

    Mirrors ``int_linear`` step by step: Eq. 1 on input and weight, exact
    accumulate, Eq. 3 bias alignment (re-rounding when the bias grid is
    finer than the accumulator grid), Eq. 5 output requant with int8
    saturation.  Bit-exact vs the int path while accumulators < 2^24
    product-LSBs."""
    mb = ctx.bits_for(name)
    xq = fake_quant(x, mb.n_x, ctx.bits)
    if w.dtype == jnp.int8:
        wq = dequant(w, mb.n_w, out_dtype=jnp.float32)
    else:
        wq = fake_quant(w, mb.n_w, ctx.bits)
    y = xq.astype(jnp.float32) @ wq.astype(jnp.float32)
    if b is not None:
        n_b = mb.n_b if mb.n_b is not None else mb.n_w
        bq = fake_quant(b, n_b, ctx.bits).astype(jnp.float32)
        if mb.n_x + mb.n_w < n_b:
            # accumulator grid coarser than the bias grid: bias_align
            # right-shifts with round-half-away (integer_ops.bias_align)
            g = 2.0 ** (mb.n_x + mb.n_w)
            bq = round_half_away(bq * g) / g
        y = y + bq
    return fake_quant(y, mb.n_o, ctx.bits).astype(x.dtype)


def _codes(x, n):
    """Integer codes of a float tensor living on the 2^-n grid."""
    return np.asarray(jnp.round(x.astype(jnp.float32) * 2.0 ** n), np.int64)


@pytest.fixture(scope="module")
def cal():
    """One calibrated tiny model shared by the whole rig."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(2, 32)), jnp.int32)}
    ctx_cal, report = calibrate_lm(
        lambda p, b, c: M.forward(p, b, cfg, c), params, batch)
    ctx_int = dataclasses.replace(ctx_cal, mode=QuantMode.INT)
    qp = quantize_params(params, ctx_int)
    return dict(cfg=cfg, params=params, ctx_int=ctx_int, qp=qp,
                report=report, batch=batch)


def _logits(out):
    return out[0] if isinstance(out, tuple) else out


# ---------------------------------------------------------------------------
# module grid: attention projections / MLP / full layer / full model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["attn/wq", "attn/wk", "attn/wv",
                                  "attn/wo", "lm_head"])
def test_attention_proj_bit_exact_vs_oracle(cal, name):
    """Every projection module: INT path codes == fp32 oracle codes, with
    float weights AND with pre-quantized int8 codes (identical by the
    qlinear passthrough contract)."""
    ctx = cal["ctx_int"]
    mb = ctx.bits_for(name)
    rng = np.random.default_rng(hash(name) % 2**31)
    k = {"attn/wq": 64, "attn/wk": 64, "attn/wv": 64, "attn/wo": 64,
         "lm_head": 64}[name]
    n_out = 256 if name == "lm_head" else 64
    x = jnp.asarray(rng.normal(0, 2.0, size=(24, k)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.05, size=(k, n_out)), jnp.float32)
    got = qlinear(ctx, name, x, w)
    want = oracle_qlinear(ctx, name, x, w)
    assert np.array_equal(_codes(got, mb.n_o), _codes(want, mb.n_o))
    # pre-quantized codes produce the same output bit-for-bit
    from repro.core.qscheme import quant
    w_codes = quant(w, mb.n_w, ctx.bits)
    got_pre = qlinear(ctx, name, x, w_codes)
    assert np.array_equal(np.asarray(got), np.asarray(got_pre))


def test_mlp_bit_exact_vs_oracle(cal, monkeypatch):
    """The up/gate/down MLP through the INT path == the oracle dataflow
    (SiLU and the Hadamard product run in float between quant points on
    both sides)."""
    from repro.models import mlp as mlp_lib
    cfg, ctx = cal["cfg"], cal["ctx_int"]
    p = {k.split("/")[-1]: v for k, v in (
        ("w1", cal["qp"].tree["blocks"]["mlp"]["w1"][0]),
        ("w3", cal["qp"].tree["blocks"]["mlp"]["w3"][0]),
        ("w2", cal["qp"].tree["blocks"]["mlp"]["w2"][0]))}
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(0, 1.0, size=(2, 8, cfg.d_model)),
                    jnp.float32)
    got = mlp_lib.mlp(ctx, p, x, cfg.act)
    monkeypatch.setattr(common_lib, "qlinear", oracle_qlinear)
    p_f = {k.split("/")[-1]: v for k, v in (
        ("w1", cal["params"]["blocks"]["mlp"]["w1"][0]),
        ("w3", cal["params"]["blocks"]["mlp"]["w3"][0]),
        ("w2", cal["params"]["blocks"]["mlp"]["w2"][0]))}
    want = mlp_lib.mlp(ctx, p_f, x, cfg.act)
    n_o = ctx.bits_for("mlp/w2").n_o
    assert np.array_equal(_codes(got, n_o), _codes(want, n_o))


def test_full_layer_bit_exact_vs_oracle(cal, monkeypatch):
    """One dense transformer block (attn + MLP + residuals + norms): the
    INT path and the oracle path must agree on every module's codes, so
    the block outputs are identical floats (residual adds and norms are
    float on both sides)."""
    cfg, ctx = cal["cfg"], cal["ctx_int"]
    layer_q = jax.tree.map(lambda a: a[0], cal["qp"].tree["blocks"])
    layer_f = jax.tree.map(lambda a: a[0], cal["params"]["blocks"])
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(0, 1.0, size=(1, 16, cfg.d_model)),
                    jnp.float32)
    pos = jnp.arange(16)[None]
    got, _ = tfm.dense_block(ctx, layer_q, x, cfg, positions=pos)
    monkeypatch.setattr(common_lib, "qlinear", oracle_qlinear)
    want, _ = tfm.dense_block(ctx, layer_f, x, cfg, positions=pos)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_full_model_bit_exact_vs_oracle(cal, monkeypatch):
    """End-to-end forward: W8A8 logits (int8 codes, INT path) equal the
    fp32 fake_quant oracle's logits code-for-code on the lm_head grid —
    the fused dataflow implements Eq. 3/5, not an approximation."""
    cfg, ctx = cal["cfg"], cal["ctx_int"]
    got = _logits(M.forward(cal["qp"].tree, cal["batch"], cfg, ctx))
    monkeypatch.setattr(common_lib, "qlinear", oracle_qlinear)
    want = _logits(M.forward(cal["params"], cal["batch"], cfg, ctx))
    n_o = ctx.bits_for("lm_head").n_o
    assert np.array_equal(_codes(got, n_o), _codes(want, n_o))


def test_quantize_params_container(cal):
    """The deploy container: converts exactly the calibrated matmul
    weights to int8, leaves embeddings/norms/biases float, and records
    what it converted."""
    qp = cal["qp"]
    assert qp.converted, "nothing was converted"
    flat_q = dict(jax.tree_util.tree_flatten_with_path(qp.tree)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            cal["params"])[0]:
        nm = "/".join(str(getattr(p, "key", p)) for p in path)
        q_leaf = flat_q[path]
        if nm in qp.converted:
            assert q_leaf.dtype == jnp.int8, nm
            assert q_leaf.shape == leaf.shape, nm
        else:
            assert q_leaf.dtype == leaf.dtype, nm
            assert "embed" in nm or "norm" in nm or "ln" in nm \
                or leaf.ndim < 2 or qmodel.module_name_for_path(
                    nm, cal["ctx_int"].table) is None, nm


def test_int8_codes_refused_outside_int_mode(cal):
    """fp/fake forwards over a code tree are garbage — qlinear refuses."""
    w_codes = cal["qp"].tree["blocks"]["attn"]["wq"][0]
    x = jnp.ones((4, cal["cfg"].d_model), jnp.float32)
    with pytest.raises(ValueError, match="int8 weight codes"):
        qlinear(QuantContext(mode=QuantMode.FP), "attn/wq", x, w_codes)


# ---------------------------------------------------------------------------
# engine grid: greedy / spec-decode / prefix-shared prefill
# ---------------------------------------------------------------------------

def _workload(rng, n, vocab, *, prefix=0):
    pre = rng.integers(0, vocab, size=prefix).astype(np.int32)
    reqs = []
    for i in range(n):
        tail = rng.integers(0, vocab, size=int(rng.integers(6, 14))
                            ).astype(np.int32)
        reqs.append(Request(
            rid=i, prompt=np.concatenate([pre, tail]) if prefix else tail,
            max_new_tokens=int(rng.integers(3, 7))))
    return reqs


def _run(cfg, params, ctx, reqs, **kw):
    eng = ServingEngine(cfg, params, ctx, n_slots=2, block_size=8,
                        max_model_len=48, chunk=8, **kw)
    rep = eng.run([dataclasses.replace(r) for r in reqs])
    return eng, rep


SCENARIOS = {
    "greedy": dict(),
    "spec": dict(spec_k=2),
    "prefix": dict(),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_engine_w8a8_token_parity(cal, scenario):
    """The W8A8 engine (int8 weight codes, matmul_kernel='int8') emits
    EXACTLY the dense-INT reference engine's tokens across greedy,
    speculative, and prefix-shared serving — and stays within the
    calibrated error budget of the fp engine."""
    ctx = cal["ctx_int"]
    rng = np.random.default_rng(11)
    prefix = 16 if scenario == "prefix" else 0
    reqs = _workload(rng, 5, cal["cfg"].vocab_size, prefix=prefix)
    kw = SCENARIOS[scenario]

    cfg_w8 = _cfg(matmul_kernel="int8")
    eng_w8, rep_w8 = _run(cfg_w8, cal["qp"], ctx, reqs, **kw)
    eng_ref, _ = _run(cfg_w8, cal["params"], ctx, reqs, **kw)
    assert rep_w8["completed"] == len(reqs)
    for r in reqs:
        assert eng_w8.outputs()[r.rid].tolist() == \
            eng_ref.outputs()[r.rid].tolist(), f"req {r.rid} ({scenario})"

    hw = rep_w8["hwcost"]
    assert hw["w8a8"] and hw["requant_ops_forward"] > 0
    assert hw["energy_uj_forward_bit_shift"] > 0
    if scenario == "prefix":
        assert rep_w8["prefix_cache"]["hit_rate"] > 0
        assert hw["requant_ops_forward_avoided_prefix_cache"] > 0
    if scenario == "spec":
        assert rep_w8["spec_steps"] > 0

    # fp comparison: free-running greedy decode on a random-init smoke
    # model flips near-uniform argmaxes, so the budget is agreement well
    # above chance (1/vocab) plus the module-level calibration error bound
    eng_fp, _ = _run(_cfg(), cal["params"],
                     QuantContext(mode=QuantMode.FP), reqs, **kw)
    num = den = 0
    for r in reqs:
        a = eng_w8.outputs()[r.rid]
        b = eng_fp.outputs()[r.rid]
        n = min(len(a), len(b))
        num += int((a[:n] == b[:n]).sum())
        den += max(len(a), len(b))
    assert num / den > 0.2, f"{scenario}: fp agreement {num}/{den}"
    errs = sorted(r.error / max(r.fp_norm, 1e-9)
                  for r in cal["report"].results.values())
    assert errs[len(errs) // 2] < 0.2


def test_engine_w8a8_matches_dense_cache_oracle(cal):
    """Paged W8A8 engine vs the static dense-cache decode loop under the
    SAME quantized params and INT ctx: the pool/paged-attention plumbing
    must not perturb the W8A8 forward."""
    ctx = cal["ctx_int"]
    cfg_w8 = _cfg(matmul_kernel="int8")
    rng = np.random.default_rng(13)
    reqs = _workload(rng, 3, cfg_w8.vocab_size)
    eng, rep = _run(cfg_w8, cal["qp"], ctx, reqs)
    assert rep["completed"] == len(reqs)
    for r in reqs:
        p_len = len(r.prompt)
        logits, cache = M.prefill(
            cal["qp"].tree, {"tokens": jnp.asarray(r.prompt[None])},
            cfg_w8, ctx, max_seq=p_len + r.max_new_tokens)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        oracle = [int(tok[0, 0])]
        for i in range(r.max_new_tokens - 1):
            l, cache = M.decode_step(cal["qp"].tree, tok, cache,
                                     jnp.asarray(p_len + i, jnp.int32),
                                     cfg_w8, ctx)
            tok = jnp.argmax(l, -1)[:, None].astype(jnp.int32)
            oracle.append(int(tok[0, 0]))
        got = eng.outputs()[r.rid].tolist()
        assert got == oracle[:len(got)], f"req {r.rid}"


def test_engine_w8a8_shard_map_4dev(cal):
    """§8 composition: the W8A8 engine on a 4-way model-parallel mesh —
    int8 weight codes sharded exactly like their float counterparts,
    exponents as compile-time kernel constants — is token-identical to
    the single-device W8A8 engine."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (conftest forces them on CPU)")
    # flash/ragged attention shards KV heads over 'model' — needs 4 | kvh
    cfg_w8 = dataclasses.replace(_cfg(matmul_kernel="int8"), n_kv_heads=4)
    params = M.init_params(cfg_w8, jax.random.PRNGKey(1))
    rng = np.random.default_rng(17)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg_w8.vocab_size, size=(2, 32)), jnp.int32)}
    ctx_cal, _ = calibrate_lm(
        lambda p, b, c: M.forward(p, b, cfg_w8, c), params, batch)
    ctx = dataclasses.replace(ctx_cal, mode=QuantMode.INT)
    qp = quantize_params(params, ctx)
    reqs = _workload(rng, 3, cfg_w8.vocab_size)
    eng_1, _ = _run(cfg_w8, qp, ctx, reqs)
    mesh = make_mesh((1, 4), ("data", "model"))
    eng_4, rep_4 = _run(cfg_w8, qp, ctx, reqs, mesh=mesh)
    assert rep_4["completed"] == len(reqs)
    for r in reqs:
        assert eng_4.outputs()[r.rid].tolist() == \
            eng_1.outputs()[r.rid].tolist(), f"req {r.rid}"


def test_build_time_validation():
    """matmul_kernel='int8' without an INT-mode context is a build error,
    and unknown values are rejected — never a silently-wrong forward."""
    from repro.launch import steps as S
    cfg = _cfg(matmul_kernel="int8")
    with pytest.raises(NotImplementedError, match="W8A8"):
        S.build_paged_step(cfg, QuantContext(mode=QuantMode.FP))
    with pytest.raises(ValueError, match="matmul_kernel"):
        S.build_paged_step(_cfg(matmul_kernel="nope"),
                           QuantContext(mode=QuantMode.FP))


def test_serve_engine_w8a8_entry(cal):
    """The launch wiring (serve --engine --w8a8): calibrates, quantizes,
    runs, and reports full-forward Table-5 energy."""
    from repro.launch.serve import serve_engine
    out = serve_engine("qwen3_1_7b", n_requests=3, rate=500.0, n_slots=2,
                       block_size=8, chunk=8, seed=3, w8a8=True,
                       cfg_overrides=dict(SCALE))
    hw = out["report"]["hwcost"]
    assert hw["w8a8"] and hw["requant_ops_forward"] > 0
    assert hw["energy_uj_forward_bit_shift"] > 0
    assert out["quantized"] is not None and out["quantized"].converted
    assert out["ctx"].mode is QuantMode.INT
    assert out["report"]["completed"] == 3
