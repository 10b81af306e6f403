"""Chip smoke test: the serving engine's main path on a TPU at qwen3_1_7b's
published widths (28 layers, d_model 2048, 16/8 heads, head_dim 128,
vocab 151936), through the normal entry point ``serve_engine``.

    python chip_smoke.py                # one chip: phases 1 and 2
    python chip_smoke.py --four-chips   # (1,4) model mesh vs device 0 only

Weights are random, made from ``--seed``; nothing is downloaded.  Four
requests (prompts of 100-600 tokens, 16-32 generated) are served by the
continuous-batching engine on the paged int8-KV pool with block size 128,
so every step is one ``ragged_paged_flash`` dispatch.

  phase 1  bf16 weights + int8 KV.  Every request completes with its
           token count.  The longest prompt, fed through the engine's
           ragged step as one prefill chunk and one decode row, gives
           logits within ``LOGITS_RTOL`` of the float32 ``M.forward``
           (matmul precision "highest") and within ``FLOOR_RATIO`` of the
           bf16 dense forward's own error.  Every generated token is a
           near-argmax of the float32 dense-cache oracle (the same forward
           over an int8 KV cache) given the engine's own history (teacher
           forcing), within ``REGRET_TOL`` logit standard deviations.
  phase 2  W8A8 (``w8a8=True``): calibrated int8 weight codes and the
           fused int8 matmul kernel.  The kernel is bit-exact against the
           jnp ``int_linear`` at every weight's real shape.  The oracle is
           the dense-INT forward (``M.forward`` over the same codes, INT
           mode, int8 KV, dense cache) on ``int_linear`` and the jnp
           attention, so it shares no Pallas kernel with the engine.  INT
           mode rounds every module boundary to int8 codes, so a float
           rounding difference flips codes and grows through the layers:
           the noise floor is the oracle's own bf16 error against its
           float32 run.  Logits within ``FLOOR_RATIO`` of that floor;
           tokens within ``REGRET_TOL`` of the bf16 oracle, which runs the
           engine's dtype.  Its logits are int8 LM-head codes, so the
           script also prints how many tokens tie the oracle's max code
           and how often the bf16 and float32 oracles pick the same token.
  --four-chips  the engine's mesh path (KV heads over 'model', DESIGN §8)
           on a (1,4) mesh against the same requests on device 0 alone:
           logits within ``LOGITS_RTOL`` of device 0's and, as in phase 1,
           of the float32 forward; tokens near-argmax of the float32
           dense-cache oracle; and where a served mesh request first
           differs from device 0's, both tokens are near-argmax there.

Each phase asserts from the compiled step HLO that the attention kernel
(and in phase 2 ``int8_matmul``) is a ``tpu_custom_call``, i.e. nothing
fell back to a jnp reference, and from the references' HLO that they
launch no Pallas kernel.  The script refuses to run without a TPU:
it then exits nonzero and prints no result.  The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
ARCH = "qwen3_1_7b"
PROMPT_LENS = (100, 257, 431, 600)
GEN_LENS = (16, 24, 32, 20)
BLOCK_SIZE = 128        # the ragged kernel takes 128-multiples only
CHUNK = 512             # per-step prefill token budget = q-window bound
N_SLOTS = 4
CAL_LEN = 128           # W8A8 calibration batch: 4 x CAL_LEN tokens
# bf16 weights/activations + int8 KV (Eq. 1, 4 fractional bits) against
# the float32 forward with float KV: the relative L2 error of the logits
LOGITS_RTOL = 0.1
# ... and against the noise floor, the same error of the bf16 dense forward
# over the same int8 cache (jnp attention): the engine may add a quarter.
# PERF.md records what sound runs and planted ragged-kernel faults read
FLOOR_RATIO = 1.25
# regret of a generated token = (max logit - logit of that token) / std of
# the logits, both from the reference; a token picked at random from the
# 151936-entry vocabulary sits ~4.5 std below the max
REGRET_TOL = 0.5


def fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def workload(vocab: int, seed: int):
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, size=p).astype(np.int32),
                    max_new_tokens=g)
            for i, (p, g) in enumerate(zip(PROMPT_LENS, GEN_LENS))]


def fresh(reqs):
    """Unserved copies (``replace`` alone would share ``generated``)."""
    return [dataclasses.replace(r, generated=[]) for r in reqs]


def ragged_prompt_logits(cfg, params, ctx, prompt, *, mesh=None):
    """Logits of ``prompt`` through the engine's ragged step builder (the
    serving engine's executable minus sampling) on a fresh paged pool, fed
    as the engine feeds one sequence: all but the last token as one
    prefill chunk, then the last token as a decode row over the cached
    blocks.  (2, V) float32: the chunk's last row (position p-2) and the
    decode row (position p-1)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import steps as S
    from repro.models import model as M
    from repro.models.attention import RaggedBatch
    p = len(prompt)
    nb = -(-p // BLOCK_SIZE)
    cache = M.init_paged_cache(cfg, nb + 1, BLOCK_SIZE)   # block 0: trash
    table = np.arange(1, nb + 1, dtype=np.int32)
    step = jax.jit(S.build_ragged_step(cfg, ctx, mesh=mesh))
    one = lambda v: jnp.asarray([v], jnp.int32)
    rows = []
    for lo, hi in ((0, p - 1), (p - 1, p)):
        n = hi - lo
        t_pad = -(-n // 8) * 8
        pos = np.arange(lo, hi, dtype=np.int32)
        tokens = np.zeros(t_pad, np.int32)
        positions = np.zeros(t_pad, np.int32)
        dest = np.zeros(t_pad, np.int32)      # padding rows -> trash block
        tokens[:n], positions[:n] = prompt[lo:hi], pos
        dest[:n] = table[pos // BLOCK_SIZE] * BLOCK_SIZE + pos % BLOCK_SIZE
        rb = RaggedBatch(dest=jnp.asarray(dest),
                         block_tables=jnp.asarray(table[None]),
                         q_start=one(0), q_len=one(n), kv_len=one(hi),
                         tq_max=n)
        logits, cache = step(params, jnp.asarray(tokens), cache,
                             jnp.asarray(positions), rb)
        rows.append(np.asarray(logits[n - 1], np.float32))
    return np.stack(rows)


ORACLE_KERNELS: set = set()     # Pallas kernels any reference launched


def reference_logits(cfg, params, ctx, tokens, *, kv_cache=False):
    """Teacher-forced logits (L, V) of the dense ``M.forward`` over
    ``tokens``, in ``cfg``'s dtype at matmul precision "highest".  With
    ``kv_cache`` the forward writes a dense cache in ``cfg``'s KV format
    and attends over it (int8 codes, dequantized: the dense-cache oracle
    of the paged engine); without, K/V stay float.  Attention is the jnp
    one (``cfg.attn_kernel`` "chunked").  Inputs are right-padded to a
    multiple of 128 (causal: rows before the pad are unchanged), so a few
    lengths share one executable.  The Pallas kernels of the compiled
    forward go into ``ORACLE_KERNELS``, which must stay empty."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ops import tpu_kernels
    from repro.models import model as M
    n = len(tokens)
    padded = np.zeros(-(-n // 128) * 128, np.int32)
    padded[:n] = tokens
    padded = jnp.asarray(padded)[None]

    def fwd(p, t):
        cache = M.init_cache(cfg, 1, t.shape[1]) if kv_cache else None
        return M.forward(p, {"tokens": t}, cfg, ctx, cache=cache)[0]

    with jax.default_matmul_precision("highest"):
        exe = jax.jit(fwd).lower(params, padded).compile()
    ORACLE_KERNELS.update(tpu_kernels(exe.as_text()))
    return np.asarray(exe(params, padded)[0, :n], np.float32)


@contextlib.contextmanager
def jnp_int_matmul():
    """While tracing inside, INT-mode matmuls run the jnp ``int_linear``
    instead of the Pallas ``int8_matmul`` (the same bit-exact contract),
    so a forward traced here shares no kernel with the engine."""
    from unittest import mock
    from repro.core.integer_ops import int_linear
    from repro.kernels import ops

    def matmul(x, w, b, spec, *, relu=False, force_kernel=False):
        return int_linear(x, w, b, spec, apply_relu=relu)

    with mock.patch.object(ops, "int8_matmul", matmul):
        yield


def token_check(cfg, params, ctx, reqs, outputs, *, code=None,
                other_cfg=None) -> dict:
    """Each generated token against the dense-cache oracle's logits at its
    position, given the engine's own history (teacher forcing, so one
    near-tie flip does not derail the rest): exact-argmax agreement, the
    worst regret in reference logit standard deviations, and each
    request's regrets (``regret``, by rid).  With ``code`` (the logits'
    code step, for int8 LM-head codes) also the share of tokens whose
    code ties the max (``max_code``), the mean number of vocabulary
    entries sharing the max code, and the worst regret in code steps.
    With ``other_cfg`` (the oracle in another dtype) also how often the
    two oracles' argmaxes agree on the same history (``oracle_agreement``:
    the agreement two sound implementations reach)."""
    import numpy as np
    agree = ties = total = same = 0
    worst = worst_code = 0.0
    n_max = []
    regrets = {}
    for r in reqs:
        gen = np.asarray(outputs[r.rid])
        seq = np.concatenate([r.prompt, gen[:-1]])
        start = len(r.prompt) - 1
        ref = reference_logits(cfg, params, ctx, seq, kv_cache=True)[start:]
        top = ref.max(-1)
        picked = ref[np.arange(len(gen)), gen]
        regret = regrets[r.rid] = (top - picked) / ref.std(-1)
        agree += int((ref.argmax(-1) == gen).sum())
        total += len(gen)
        worst = max(worst, float(regret.max()))
        if code is not None:
            ties += int((picked == top).sum())
            n_max += (ref == top[:, None]).sum(-1).tolist()
            worst_code = max(worst_code, float((top - picked).max()) / code)
        if other_cfg is not None:
            other = reference_logits(other_cfg, params, ctx, seq,
                                     kv_cache=True)[start:]
            same += int((other.argmax(-1) == ref.argmax(-1)).sum())
    out = {"agreement": agree / total, "worst_regret_std": worst,
           "regret": regrets}
    if code is not None:
        out.update(max_code=ties / total, n_at_max=float(np.mean(n_max)),
                   worst_regret_codes=worst_code)
    if other_cfg is not None:
        out["oracle_agreement"] = same / total
    return out


def int8_matmul_exact(quantized, ctx, seed) -> dict:
    """``int8_matmul`` (the Pallas kernel) against the jnp ``int_linear``
    at every int8 weight's real shape (layer 0 of a scanned stack), for a
    decode-sized and a prefill-sized row count, on random activation
    codes: {module: (outputs that differ, outputs compared, share of
    outputs saturated at the int8 bounds)}."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.integer_ops import LinearQuantSpec, int_linear
    from repro.core.qmodel import module_name_for_path
    from repro.kernels import ops
    rng = np.random.default_rng(seed)
    out = {}
    for path, w in jax.tree_util.tree_flatten_with_path(quantized.tree)[0]:
        nm = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path)
        if nm not in quantized.converted:
            continue
        w = w[0] if w.ndim == 3 else w
        mod = module_name_for_path(nm, ctx.table)
        mb = ctx.bits_for(mod)
        spec = LinearQuantSpec(
            n_x=mb.n_x, n_w=mb.n_w, n_o=mb.n_o, bits=ctx.bits,
            n_b=mb.n_b if mb.n_b is not None else mb.n_w)
        diff = n = sat = 0
        for m in (8, 520):
            x = jnp.asarray(np.clip(np.rint(rng.normal(
                0, 16, (m, w.shape[0]))), -128, 127), jnp.int8)
            got = np.asarray(ops.int8_matmul(x, w, None, spec,
                                             force_kernel=True))
            want = np.asarray(int_linear(x, w, None, spec))
            diff += int((got != want).sum())
            n += want.size
            sat += int(((want == 127) | (want == -128)).sum())
        out[mod] = (diff, n, sat / n)
    return out


def rel_err(got, want) -> float:
    import numpy as np
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def logits_check(name, got, want, floor=None,
                 rtol=LOGITS_RTOL) -> tuple[str, list[str]]:
    """Relative L2 error of the (prefill, decode) logit rows against the
    reference rows, each within ``rtol`` (None: no absolute limit) and,
    given the noise floor's rows (the bf16 dense forward over the same
    int8 cache), within ``FLOOR_RATIO`` times the floor's own error.
    (text, errors)."""
    errors, parts = [], []
    for i, row in enumerate(("prefill", "decode")):
        err = rel_err(got[i], want[i])
        parts.append(f"{row} {err:.4g}")
        if rtol is not None and not err <= rtol:
            errors.append(f"{name}: {row} logits rel_err {err} > {rtol}")
        if floor is not None:
            fl = rel_err(floor[i], want[i])
            parts[-1] += f" (floor {fl:.4g}, ratio {err / fl:.3f})"
            if not err <= FLOOR_RATIO * fl:
                errors.append(f"{name}: {row} logits rel_err {err} > "
                              f"{FLOOR_RATIO} x floor {fl}")
    return ", ".join(parts), errors


def step_kernels(engine) -> dict:
    """Pallas kernels in each compiled ragged step the engine ran (the
    profiler keeps each shape's lowered step when cost capture is on)."""
    from repro.kernels.ops import tpu_kernels
    return {f"ragged_{k[1]}xS{k[2]}":
            sorted(tpu_kernels(lw.compile().as_text()))
            for k, lw in engine.profiler.lowered.items()}


def phase_times(report: dict) -> tuple[float, float]:
    """(compile s, run s): first call of a shape minus its steady time is
    compilation; the rest of the engine's wall clock is serving."""
    shapes = {k: v for k, v in report["step_shapes"].items()
              if k.startswith("ragged_")}
    compile_s = sum(v["first_s"] - (v["steady_s"] or 0.0)
                    for v in shapes.values())
    return compile_s, report["wall_s"] - compile_s


def serve(reqs, seed, **kw) -> tuple[dict, float]:
    from repro.launch.serve import serve_engine
    t0 = time.perf_counter()
    out = serve_engine(ARCH, smoke=False, requests=fresh(reqs),
                       n_slots=N_SLOTS, block_size=BLOCK_SIZE, chunk=CHUNK,
                       kv_bits=8, seed=seed, prompt_lens=(CAL_LEN,),
                       profile_cost=True, **kw)
    return out, time.perf_counter() - t0


def check_served(name, out, reqs, need) -> list[str]:
    """Completion, token counts and kernel presence of one engine run."""
    errors = []
    rep, eng = out["report"], out["engine"]
    outputs = eng.outputs()
    if rep["completed"] != len(reqs):
        errors.append(f"{name}: {rep['completed']}/{len(reqs)} completed")
    for r in reqs:
        n = len(outputs.get(r.rid, ()))
        if n != r.max_new_tokens:
            errors.append(f"{name}: request {r.rid} made {n} tokens, "
                          f"wanted {r.max_new_tokens}")
    kernels = step_kernels(eng)
    print(f"{name}: step kernels {kernels}")
    for shape, ks in kernels.items():
        missing = set(need) - set(ks)
        if missing:
            errors.append(f"{name}: step {shape} lacks {sorted(missing)}")
    compile_s, run_s = phase_times(rep)
    print(f"{name}: compile_s {compile_s:.1f} run_s {run_s:.1f} "
          f"distinct_step_shapes {len(kernels)} {sorted(kernels)}; "
          f"(calls, first_s, steady_s) per shape "
          f"{ {k: (v['calls'], v['first_s'], v['steady_s']) for k, v in rep['step_shapes'].items() if k.startswith('ragged_')} }")
    return errors


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def in_use(devices) -> list:
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def phase_fp(reqs, seed) -> list[str]:
    import jax
    from repro.core.qmodel import QuantContext, QuantMode
    out, wall = serve(reqs, seed, mode="fp")
    errors = check_served("phase1 bf16+int8kv", out, reqs,
                          ["ragged_paged_flash"])
    eng = out["engine"]
    cfg, params, ctx = eng.cfg, eng.params, out["ctx"]
    outputs = eng.outputs()
    del out, eng
    prompt = reqs[-1].prompt            # the longest: a multi-block walk
    got = ragged_prompt_logits(cfg, params, ctx, prompt)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    fp = QuantContext(mode=QuantMode.FP)
    want = reference_logits(cfg32, params, fp, prompt)[-2:]
    floor = reference_logits(cfg, params, ctx, prompt, kv_cache=True)[-2:]
    text, errs = logits_check("phase1", got, want, floor)
    kv = reference_logits(cfg32, params, fp, prompt, kv_cache=True)[-2:]
    tc = token_check(cfg32, params, fp, reqs, outputs)
    print(f"phase1 bf16+int8kv: wall_s {wall:.1f} logits rel_err vs fp32 "
          f"{text} (tol {LOGITS_RTOL}, {FLOOR_RATIO} x floor), vs fp32 with"
          f" int8 KV {rel_err(got[0], kv[0]):.4g}/{rel_err(got[1], kv[1]):.4g}"
          f"; tokens vs fp32 int8-KV dense cache agreement "
          f"{tc['agreement']:.3f} worst regret {tc['worst_regret_std']:.3f} "
          f"std (tol {REGRET_TOL}); peak_bytes_in_use "
          f"{peak_bytes(jax.devices()[:1])}")
    errors += errs
    if not tc["worst_regret_std"] <= REGRET_TOL:
        errors.append(f"phase1: token regret {tc['worst_regret_std']} > "
                      f"{REGRET_TOL}")
    return errors


def phase_w8a8(reqs, seed) -> list[str]:
    import jax
    out, wall = serve(reqs, seed, w8a8=True)
    errors = check_served("phase2 w8a8", out, reqs,
                          ["ragged_paged_flash", "int8_matmul"])
    eng, quantized, ctx = out["engine"], out["quantized"], out["ctx"]
    cfg, params = eng.cfg, eng.params
    outputs = eng.outputs()
    del out, eng
    exact = int8_matmul_exact(quantized, ctx, seed)
    print(f"phase2 w8a8: int8_matmul vs int_linear (differing, compared, "
          f"saturated share) {exact}")
    for mod, (diff, _, _) in exact.items():
        if diff:
            errors.append(f"phase2: int8_matmul differs from int_linear "
                          f"at {mod} in {diff} outputs")
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    code = 2.0 ** -ctx.bits_for("lm_head").n_o
    prompt = reqs[-1].prompt
    got = ragged_prompt_logits(cfg, params, ctx, prompt)
    with jnp_int_matmul():
        want = reference_logits(cfg32, params, ctx, prompt,
                                kv_cache=True)[-2:]
        floor = reference_logits(cfg, params, ctx, prompt,
                                 kv_cache=True)[-2:]
        tc = token_check(cfg, params, ctx, reqs, outputs, code=code,
                         other_cfg=cfg32)
    text, errs = logits_check("phase2", got, want, floor, rtol=None)
    print(f"phase2 w8a8: wall_s {wall:.1f} {len(quantized.converted)} "
          f"weight tensors as int8 codes; logits vs float32 dense-INT "
          f"rel_err {text} ({FLOOR_RATIO} x floor), vs bf16 dense-INT "
          f"{rel_err(got[0], floor[0]):.4g}/{rel_err(got[1], floor[1]):.4g}"
          f"; tokens vs bf16 dense-INT argmax agreement "
          f"{tc['agreement']:.3f} (bf16 vs float32 oracle "
          f"{tc['oracle_agreement']:.3f}), at the max code "
          f"{tc['max_code']:.3f} (mean {tc['n_at_max']:.2f} vocab entries "
          f"share it), worst regret {tc['worst_regret_codes']:g} codes = "
          f"{tc['worst_regret_std']:.3f} std (tol {REGRET_TOL}); "
          f"peak_bytes_in_use {peak_bytes(jax.devices()[:1])}")
    errors += errs
    if not tc["worst_regret_std"] <= REGRET_TOL:
        errors.append(f"phase2: token regret {tc['worst_regret_std']} > "
                      f"{REGRET_TOL}")
    return errors


def phase_mesh(reqs, seed) -> list[str]:
    import jax
    import numpy as np
    from repro.core.qmodel import QuantContext, QuantMode
    from repro.launch.mesh import make_mesh
    one, wall1 = serve(reqs, seed, mode="fp")
    errors = check_served("device0 bf16+int8kv", one, reqs,
                          ["ragged_paged_flash"])
    four, wall4 = serve(reqs, seed, mode="fp", mesh_shape=(1, 4))
    errors += check_served("mesh1x4 bf16+int8kv", four, reqs,
                           ["ragged_paged_flash"])
    eng1, eng4 = one["engine"], four["engine"]
    cfg, ctx = eng1.cfg, one["ctx"]
    out1, out4 = eng1.outputs(), eng4.outputs()
    prompt = reqs[-1].prompt
    # the mesh engine's own (sharded) weights through the mesh step
    got = ragged_prompt_logits(cfg, eng4.params, ctx, prompt,
                               mesh=make_mesh((1, 4)))
    params = eng1.params
    del one, four, eng1, eng4
    gc.collect()
    dev0 = ragged_prompt_logits(cfg, params, ctx, prompt)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    fp = QuantContext(mode=QuantMode.FP)
    want = reference_logits(cfg32, params, fp, prompt)[-2:]
    floor = reference_logits(cfg, params, ctx, prompt, kv_cache=True)[-2:]
    text0, errs0 = logits_check("mesh vs device 0", got, dev0)
    text, errs = logits_check("mesh vs fp32", got, want, floor)
    text1, _ = logits_check("device 0 vs fp32", dev0, want, floor)
    tc4 = token_check(cfg32, params, fp, reqs, out4)
    tc1 = token_check(cfg32, params, fp, reqs, out1)
    # where each served mesh request first differs from device 0's: both
    # histories agree up to there, so the oracle row is the same for both
    splits = {}
    for r in reqs:
        diff = np.flatnonzero(out1[r.rid] != out4[r.rid])
        if diff.size:
            i = int(diff[0])
            splits[r.rid] = (i, round(float(tc1["regret"][r.rid][i]), 4),
                             round(float(tc4["regret"][r.rid][i]), 4))
    split_worst = max((max(v[1:]) for v in splits.values()), default=0.0)
    print(f"mesh1x4: wall_s {wall4:.1f} (device0 {wall1:.1f}); "
          f"{len(reqs) - len(splits)}/{len(reqs)} requests token-identical "
          f"to device 0; first split (token index, device-0 regret, mesh "
          f"regret in std) by request {splits}; logits rel_err vs device 0 "
          f"{text0}, vs fp32 {text} (device 0 vs fp32 {text1}; tol "
          f"{LOGITS_RTOL}, {FLOOR_RATIO} x floor); tokens vs fp32 int8-KV "
          f"dense cache agreement {tc4['agreement']:.3f} worst regret "
          f"{tc4['worst_regret_std']:.3f} std (device 0 "
          f"{tc1['agreement']:.3f}, {tc1['worst_regret_std']:.3f}; tol "
          f"{REGRET_TOL}); peak_bytes_in_use "
          f"{peak_bytes(jax.devices()[:4])}")
    errors += errs0 + errs
    for what, w in (("tokens", tc4["worst_regret_std"]),
                    ("first split from device 0", split_worst)):
        if not w <= REGRET_TOL:
            errors.append(f"mesh: {what} regret {w} > {REGRET_TOL}")
    return errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (1,4) mesh phase and its device-0 "
                         "comparison (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(REPO / "src"))
    try:
        from repro.configs import get_config
        from repro.launch.serve import enable_compile_cache
    except ImportError as e:
        return fail(f"the repository's package is not next to this script "
                    f"({e})")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return fail(f"no TPU: JAX found {dev.platform} devices")
    n_need = 4 if args.four_chips else 1
    if len(devices) < n_need:
        return fail(f"--four-chips needs 4 devices, found {len(devices)}")
    print(f"device_kind {dev.device_kind} count {len(devices)}")
    print(f"compile cache {enable_compile_cache()}")

    cfg = get_config(ARCH)
    print(f"{ARCH}: {cfg.n_layers}L d_model {cfg.d_model} heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads} head_dim {cfg.resolved_head_dim} "
          f"vocab {cfg.vocab_size}; block {BLOCK_SIZE} chunk {CHUNK} "
          f"slots {N_SLOTS}")
    reqs = workload(cfg.vocab_size, args.seed)
    phases = ([phase_mesh] if args.four_chips else [phase_fp, phase_w8a8])
    errors = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            errors += phase(reqs, args.seed)
        except Exception as e:                  # a phase that raised failed
            import traceback
            traceback.print_exc()
            errors.append(f"{phase.__name__} raised {type(e).__name__}: {e}")
        # the engine sits in a reference cycle: free its device buffers
        # (weights, KV pool) before the next phase allocates its own
        gc.collect()
        print(f"{phase.__name__}: {time.perf_counter() - t0:.1f} s; "
              f"bytes_in_use after {in_use(devices[:n_need])}")
    print(f"Pallas kernels in the references: {sorted(ORACLE_KERNELS)}")
    if ORACLE_KERNELS:
        errors.append(f"a reference launched {sorted(ORACLE_KERNELS)}")
    if errors:
        for e in errors:
            print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
