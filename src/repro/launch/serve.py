"""Serving driver: integer-deploy path with batched prefill + decode.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_1_7b \
        --batch 4 --prompt-len 32 --gen 16 [--mode int] [--calibrate]

Pipeline (DESIGN §3): optional Algorithm-1 calibration on one batch ->
int8 weight conversion -> jit'd prefill + decode steps in the requested
quantization mode.  The decode loop is greedy (framework demo; sampling
plugs into serve_step).  Steps are AOT-compiled first, so the reported
``prefill_s`` / ``decode_s_per_tok`` are STEADY-STATE; compile time is
reported separately (``compile_prefill_s`` / ``compile_decode_s``).

``--engine`` switches to the continuous-batching serving engine
(DESIGN §9): a synthetic Poisson workload of mixed prompt/gen lengths is
served from the paged int8-KV block pool with slot-based continuous
batching, chunked prefill, and per-request sampling/stop handling; the
report adds throughput, latency percentiles, pool utilization, and the
paper-Table-5 requant-energy accounting.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.lm_calibrate import calibrate_lm
from repro.core.qmodel import QuantContext, QuantMode
from repro.data import SyntheticLMStream
from repro.launch import steps as S
from repro.launch.mesh import make_mesh
from repro.models import model as M


# KV pool block size, tokens: the ragged attention kernel takes multiples
# of 128 (and on an accelerator refuses anything else)
DEFAULT_BLOCK_SIZE = 128


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is (JAX reads
    it itself; nothing is set here).  Otherwise the cache lives at the
    fixed path ``<repo>/.jax_cache`` — fixed because the path is part of
    the cache key.  Called from entry points only, never at import and
    never from tests.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _resolve_cfg_mesh(arch: str, *, smoke: bool,
                      attn_kernel: str | None = None,
                      cfg_overrides: dict | None = None,
                      mesh_shape: tuple[int, int] | None = None):
    """Shared config/mesh setup for the classic and engine drivers.

    ``attn_kernel='flash'`` routes prefill/decode through the fused Pallas
    attention (DESIGN §2); int8 KV codes then skip the dequantized HBM
    copy.  ``cfg_overrides`` patches arbitrary config fields (e.g.
    head_dim=128 so the fused decode kernel genuinely launches on smoke
    configs — it refuses non-lane-multiple head dims).  ``mesh_shape``
    builds a (data, model) mesh: the fused kernels run per-shard via
    shard_map — KV heads over 'model', batch over 'data' (DESIGN §8/§9);
    the step builders raise NotImplementedError if 'model' doesn't divide
    n_kv_heads."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if attn_kernel is not None:
        cfg = dataclasses.replace(cfg, attn_kernel=attn_kernel)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    mesh = None
    if mesh_shape is not None:
        mesh = make_mesh(mesh_shape)
    return cfg, mesh


def serve(arch: str, *, batch: int = 4, prompt_len: int = 32, gen: int = 16,
          mode: str = "int", calibrate: bool = True, smoke: bool = True,
          seed: int = 0, params=None, attn_kernel: str | None = None,
          mesh_shape: tuple[int, int] | None = None,
          cfg_overrides: dict | None = None) -> dict:
    cfg, mesh = _resolve_cfg_mesh(arch, smoke=smoke, attn_kernel=attn_kernel,
                                  cfg_overrides=cfg_overrides,
                                  mesh_shape=mesh_shape)
    if params is None:
        params = M.init_params(cfg, jax.random.PRNGKey(seed))
    stream = SyntheticLMStream(
        cfg.vocab_size, prompt_len, batch, seed=seed,
        encoder_seq=cfg.encdec.encoder_seq if cfg.family == "audio" else None,
        d_model=cfg.d_model if cfg.family == "audio" else None)
    b0 = {k: jnp.asarray(v) for k, v in stream.batch(0).items()}
    prompt = {k: v for k, v in b0.items() if k in ("tokens",
                                                   "encoder_features")}

    ctx = QuantContext(mode=QuantMode(mode))
    report = None
    if calibrate and mode in ("fake", "int"):
        t0 = time.time()
        ctx_cal, report = calibrate_lm(
            lambda p, b, c: M.forward(p, b, cfg, c), params, prompt)
        ctx = dataclasses.replace(ctx_cal, mode=QuantMode(mode))
        print(f"calibrated {len(report.results)} modules "
              f"in {time.time()-t0:.1f}s")

    max_seq = prompt_len + gen
    prefill_fn = jax.jit(S.build_prefill_step(cfg, ctx, mesh=mesh,
                                              max_seq=max_seq))
    serve_fn = jax.jit(S.build_serve_step(cfg, ctx, mesh=mesh))

    # AOT-compile both steps so the timings below are steady-state: the
    # old code folded jit tracing+compilation into prefill_s and the first
    # decode step, which dwarfed the actual compute at smoke scale.
    t0 = time.time()
    prefill_c = prefill_fn.lower(params, prompt).compile()
    compile_prefill_s = time.time() - t0

    t0 = time.time()
    logits, cache = prefill_c(params, prompt)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    t0 = time.time()
    serve_c = serve_fn.lower(params, tok, cache,
                             jnp.asarray(prompt_len, jnp.int32)).compile()
    compile_decode_s = time.time() - t0

    out_tokens = [tok]
    t0 = time.time()
    for i in range(gen - 1):
        tok, cache = serve_c(params, tok, cache,
                             jnp.asarray(prompt_len + i, jnp.int32))
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen_tokens = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    return {"tokens": gen_tokens, "prefill_s": t_prefill,
            "decode_s_per_tok": t_decode / max(gen - 1, 1),
            "compile_prefill_s": compile_prefill_s,
            "compile_decode_s": compile_decode_s,
            "report": report, "ctx": ctx}


def poisson_workload(vocab_size: int, *, n_requests: int, rate: float,
                     prompt_lens=(8, 16, 24, 32), gen_lens=(4, 8, 16, 24),
                     temperature: float = 0.0, seed: int = 0,
                     shared_prefix: int = 0) -> list:
    """Synthetic open-loop workload: Poisson arrivals (exponential
    inter-arrival at ``rate`` req/s on the engine clock) with mixed
    prompt/generation lengths — the shape continuous batching exists for
    (a static batch pads every request to the longest member).

    ``shared_prefix`` prepends the SAME ``shared_prefix`` random tokens (a
    synthetic system prompt) to every request's prompt — the shape the
    content-addressed prefix cache exists for (DESIGN §10): real fleets
    are dominated by shared prefixes, and the cache quantizes them once.
    ``prompt_lens`` then sizes the per-request unique tail."""
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab_size, size=shared_prefix
                          ).astype(np.int32)
    t = 0.0
    reqs = []
    for i in range(n_requests):
        t += float(rng.exponential(1.0 / rate))
        tail = rng.integers(0, vocab_size,
                            size=int(rng.choice(prompt_lens))
                            ).astype(np.int32)
        reqs.append(Request(
            rid=i,
            prompt=np.concatenate([prefix, tail]) if shared_prefix else tail,
            max_new_tokens=int(rng.choice(gen_lens)),
            temperature=temperature,
            arrival=t))
    return reqs


def serve_engine(arch: str, *, n_requests: int = 16, rate: float = 50.0,
                 n_slots: int = 4, block_size: int = DEFAULT_BLOCK_SIZE,
                 chunk: int = 16,
                 max_model_len: int | None = None,
                 num_blocks: int | None = None, mode: str = "fp",
                 calibrate: bool = False, smoke: bool = True, seed: int = 0,
                 attn_kernel: str | None = None, kv_bits: int | None = 8,
                 temperature: float = 0.0, top_k: int = 0,
                 mesh_shape: tuple[int, int] | None = None,
                 prompt_lens=(8, 16, 24, 32), gen_lens=(4, 8, 16, 24),
                 requests=None, cfg_overrides: dict | None = None,
                 shared_prefix: int = 0, prefix_cache: bool | None = None,
                 num_slabs: int | None = None,
                 state_bits: int | None = None,
                 spec_k: int = 0, drafter="ngram",
                 ragged: bool = True, w8a8: bool = False,
                 trace: str | bool = False, trace_capacity: int = 65536,
                 metrics_path: str | None = None,
                 profile_dir: str | None = None,
                 profile_cost: bool = False,
                 record: str | bool = False, virtual_dt: float = 1e-3,
                 slo=None) -> dict:
    """Continuous-batching serving on the paged int8-KV block pool
    (DESIGN §9/§10).  Returns {"report", "outputs", "requests", "engine"}.

    ``shared_prefix`` prepends an N-token system prompt to every request
    (see :func:`poisson_workload`); ``prefix_cache=False`` disables the
    content-addressed cache for A/B comparison at equal pool size, and
    the default ``None`` lets the substrate decide (on for attention,
    off — and an error if forced on — for recurrent/hybrid models,
    whose state is a running summary with no addressable prefix).

    Recurrent / hybrid archs (``rwkv6_3b``, ``zamba2_2_7b``) serve from
    the fixed-slab substrate (DESIGN §16): ``num_slabs`` sizes the state
    pool (default 1 trash + one slab per slot) and ``state_bits=8``
    stores slabs as int8 Eq.-1 codes requantized once per engine step
    (``None`` = fp32 slabs, the parity-oracle mode).
    ``spec_k > 0`` turns on speculative decoding (DESIGN §11): up to K
    tokens per slot are drafted (``drafter``: 'ngram' prompt-lookup
    self-drafting, or any object with draft(history, k)) and verified in
    one paged step, with rollback-safe publishing — rejected drafts
    never reach the prefix cache.  ``ragged=False`` falls back to the
    legacy per-shape step trio (bucketed prefill / decode / spec-verify
    dispatches) instead of the unified ragged work-list (DESIGN §12) —
    kept for A/B padding and throughput comparison.  ``w8a8=True`` is the
    true-W8A8 deploy path (DESIGN §13): forces mode='int' with
    Algorithm-1 calibration (threaded along the dataflow), sets
    ``cfg.matmul_kernel='int8'`` and pre-quantizes the matmul weights to
    int8 codes, so every projection/MLP/head matmul in the engine runs
    int8 x int8 -> int32 with the fused bit-shift requant.

    Observability (DESIGN §14): ``trace`` turns on the ring-buffered
    event tracer — pass a path string to also export the Chrome
    trace-event JSON there (load it in Perfetto / ``chrome://tracing``).
    ``metrics_path`` writes the prometheus text exposition of the
    metrics registry after the run.  ``profile_dir`` wraps each jitted
    dispatch in a ``jax.profiler`` step annotation and captures the run
    into that directory; ``profile_cost`` additionally records XLA
    FLOPs/bytes per compiled shape via AOT ``cost_analysis()``.

    Flight recorder (DESIGN §15): ``record`` runs the engine on the
    deterministic virtual clock and freezes the run into a portable
    :class:`repro.obs.replay.WorkloadRecord` (returned under
    ``"record"``; pass a path string to also save it as JSON) — replay
    it with ``repro.obs.replay.replay_workload`` or ``--replay``.
    ``slo`` attaches an SLO burn-rate monitor (``True`` for the stock
    objectives or a list of ``SLObjective``); alerts land in the
    tracer and the report's ``slo`` section."""
    from repro.serving import ServingEngine
    overrides = dict(cfg_overrides or {})
    if kv_bits is not None:
        overrides.setdefault("kv_cache_bits", kv_bits)
    if state_bits is not None:
        overrides.setdefault("state_bits", state_bits)
    if w8a8:
        mode, calibrate = "int", True
        overrides["matmul_kernel"] = "int8"
    cfg, mesh = _resolve_cfg_mesh(arch, smoke=smoke, attn_kernel=attn_kernel,
                                  cfg_overrides=overrides,
                                  mesh_shape=mesh_shape)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))

    ctx = QuantContext(mode=QuantMode(mode))
    if calibrate and mode in ("fake", "int"):
        stream = SyntheticLMStream(cfg.vocab_size, max(prompt_lens), 4,
                                   seed=seed)
        b0 = {k: jnp.asarray(v) for k, v in stream.batch(0).items()
              if k == "tokens"}
        ctx_cal, _ = calibrate_lm(
            lambda p, b, c: M.forward(p, b, cfg, c), params, b0)
        ctx = dataclasses.replace(ctx_cal, mode=QuantMode(mode))

    quantized = None
    if cfg.matmul_kernel == "int8":
        # W8A8 deploy: one-time weight-code conversion on the calibrated
        # grids; the engine forward then passes int8 codes straight through
        # qlinear (bit-identical to on-the-fly quantization).  The codes
        # shard exactly like their float counterparts under §8 meshes.
        from repro.core.qmodel import quantize_params
        if ctx.mode is not QuantMode.INT:
            raise ValueError("matmul_kernel='int8' requires mode='int' "
                             "(pass w8a8=True or mode='int')")
        quantized = quantize_params(params, ctx)
        params = quantized.tree

    if requests is None:
        requests = poisson_workload(
            cfg.vocab_size, n_requests=n_requests, rate=rate,
            prompt_lens=prompt_lens, gen_lens=gen_lens,
            temperature=temperature, seed=seed,
            shared_prefix=shared_prefix)
    if max_model_len is None:
        need = max(len(r.prompt) + r.max_new_tokens for r in requests)
        max_model_len = -(-need // block_size) * block_size
    engine = ServingEngine(cfg, params, ctx, n_slots=n_slots,
                           block_size=block_size, chunk=chunk,
                           max_model_len=max_model_len,
                           num_blocks=num_blocks, num_slabs=num_slabs,
                           top_k=top_k, mesh=mesh,
                           seed=seed, prefix_cache=prefix_cache,
                           spec_k=spec_k, drafter=drafter, ragged=ragged,
                           trace=bool(trace), trace_capacity=trace_capacity,
                           profile_dir=profile_dir,
                           profile_cost=profile_cost,
                           record=bool(record), virtual_dt=virtual_dt,
                           slo=slo)
    if profile_dir is not None:
        with engine.profiler.capture():
            report = engine.run(requests)
    else:
        report = engine.run(requests)
    if isinstance(trace, str) and trace:
        engine.tracer.export(trace)
    if metrics_path is not None:
        with open(metrics_path, "w") as fh:
            fh.write(engine.metrics.to_prometheus())
    rec = None
    if record:
        rec = engine.workload_record(requests)
        if isinstance(record, str):
            rec.save(record)
    return {"report": report, "outputs": engine.outputs(),
            "requests": requests, "engine": engine,
            "quantized": quantized, "ctx": ctx, "record": rec}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", "--model", dest="arch", required=True,
                    help="architecture name (alias: --model) — includes "
                         "the recurrent/hybrid archs rwkv6_3b and "
                         "zamba2_2_7b, served from the fixed-slab "
                         "substrate (DESIGN §16)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mode", default="int",
                    choices=["fp", "fake", "fake_sf", "int"])
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--attn-kernel", default=None,
                    choices=["chunked", "flash"],
                    help="attention path (DESIGN §2); default: cfg's")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="serve on a (data, model) device mesh, e.g. '1x2';"
                         " with --attn-kernel flash the fused kernels run"
                         " per-shard via shard_map (DESIGN §8)")
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching engine on the paged int8-KV "
                         "block pool (DESIGN §9) against a synthetic "
                         "Poisson workload of mixed prompt/gen lengths")
    ap.add_argument("--requests", type=int, default=16,
                    help="[--engine] workload size")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="[--engine] Poisson arrival rate, req/s")
    ap.add_argument("--slots", type=int, default=4,
                    help="[--engine] continuous-batch width")
    ap.add_argument("--block-size", type=int, default=DEFAULT_BLOCK_SIZE,
                    help="[--engine] KV pool block size, tokens (the "
                         "ragged kernel takes multiples of 128)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="[--engine] prefill chunk / per-step token budget")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="[--engine] sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="[--engine] top-k sampling cutoff (0 = full)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="[--engine] prepend the SAME N-token system "
                         "prompt to every request — the workload the "
                         "content-addressed prefix cache serves with one "
                         "quantization pass (DESIGN §10)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="[--engine] force the content-addressed prefix "
                         "cache ON (default: substrate decides — on for "
                         "attention archs, unavailable on recurrent/"
                         "hybrid ones)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="[--engine] disable the prefix cache (baseline "
                         "for A/B at equal pool size)")
    ap.add_argument("--slabs", type=int, default=None, metavar="N",
                    help="[--engine] recurrent-state pool size in slabs "
                         "(DESIGN §16; default 1 trash + one per slot); "
                         "ignored on pure-attention archs")
    ap.add_argument("--state-bits", type=int, default=None, choices=[8],
                    help="[--engine] store recurrent state slabs as int8 "
                         "Eq.-1 codes, requantized once per engine step "
                         "(default: fp32 slabs, the parity-oracle mode); "
                         "ignored on pure-attention archs")
    ap.add_argument("--spec-k", type=int, default=0, metavar="K",
                    help="[--engine] speculative decoding (DESIGN §11): "
                         "draft up to K tokens per slot and verify them "
                         "in ONE paged step; accepted tokens commit, the "
                         "rejected tail's blocks retract so they never "
                         "publish to the prefix cache (0 = off)")
    ap.add_argument("--drafter", default="ngram", choices=["ngram"],
                    help="[--engine --spec-k] draft proposer: 'ngram' is "
                         "the model-free prompt-lookup self-drafter "
                         "(small-draft-model hooks plug in via the "
                         "serve_engine(drafter=...) API)")
    ap.add_argument("--w8a8", action="store_true",
                    help="[--engine] true W8A8 serving (DESIGN §13): "
                         "calibrate with Algorithm 1 threaded along the "
                         "dataflow, pre-quantize weights to int8 codes and "
                         "run every projection/MLP/head matmul through the "
                         "fused int8 shift-requant path (implies "
                         "--mode int)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="[--engine] enable structured event tracing "
                         "(DESIGN §14) and export the run as Chrome "
                         "trace-event JSON — open it in Perfetto "
                         "(ui.perfetto.dev) or chrome://tracing")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="[--engine --trace] trace ring-buffer size; the "
                         "ring is a hard memory bound — oldest events "
                         "drop first and the export reports the count")
    ap.add_argument("--metrics", default=None, metavar="OUT.prom",
                    help="[--engine] write the metrics registry as "
                         "prometheus text exposition after the run")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="[--engine] capture a jax.profiler trace of the "
                         "run into DIR (one StepTraceAnnotation per "
                         "jitted dispatch)")
    ap.add_argument("--profile-cost", action="store_true",
                    help="[--engine] record XLA FLOPs/bytes per compiled "
                         "shape via AOT cost_analysis() in the report's "
                         "profile section")
    ap.add_argument("--record", default=None, metavar="OUT.json",
                    help="[--engine] flight recorder (DESIGN §15): run "
                         "on the deterministic virtual clock and save a "
                         "portable workload record (arrivals, prompts, "
                         "sampling params, seeds, config fingerprint, "
                         "emitted tokens, scheduler-decision stream) "
                         "for later --replay")
    ap.add_argument("--replay", default=None, metavar="IN.json",
                    help="(implies --engine) replay a recorded workload: "
                         "re-inject the captured arrival process on the "
                         "virtual clock (engine knobs from the record) "
                         "and report token parity + the scheduler-"
                         "decision diff; exits nonzero on divergence")
    ap.add_argument("--slo", action="store_true",
                    help="[--engine] attach the stock SLO objectives "
                         "(TTFT/e2e percentile targets, pool-pressure "
                         "ceiling) with rolling-window burn-rate "
                         "alerting; alerts print after the run and land "
                         "in the trace on the 'slo' lane")
    ap.add_argument("--no-ragged", action="store_true",
                    help="[--engine] use the legacy per-shape step trio "
                         "(bucketed prefill / decode / spec-verify) "
                         "instead of the unified ragged work-list "
                         "dispatch (DESIGN §12) — A/B baseline for "
                         "padding waste and compile count")
    args = ap.parse_args(argv)
    enable_compile_cache()
    mesh_shape = None
    if args.mesh is not None:
        d, m = (int(x) for x in args.mesh.lower().split("x"))
        mesh_shape = (d, m)
    if args.prefix_cache and args.no_prefix_cache:
        ap.error("--prefix-cache and --no-prefix-cache are mutually "
                 "exclusive")
    # tri-state: None lets the substrate decide (engine errors with a
    # clear message if --prefix-cache is forced on a recurrent arch)
    prefix_cache = (True if args.prefix_cache
                    else False if args.no_prefix_cache else None)

    if args.replay:                   # implies --engine
        from repro.obs.replay import (WorkloadRecord, build_requests,
                                      replay_workload)
        rec = WorkloadRecord.load(args.replay)
        es = rec.engine
        # block_size/num_blocks are None in recurrent-substrate records
        # (no KV pool existed); serve_engine's defaults only matter for
        # archs that grow, where the record always carries real values.
        out = serve_engine(args.arch, requests=build_requests(rec),
                           n_slots=es["n_slots"],
                           block_size=es["block_size"] or DEFAULT_BLOCK_SIZE,
                           chunk=es["chunk"],
                           max_model_len=es["max_model_len"],
                           num_blocks=es["num_blocks"], mode=args.mode,
                           calibrate=not args.no_calibrate,
                           smoke=not args.full,
                           attn_kernel=args.attn_kernel,
                           top_k=es["default_top_k"], seed=es["seed"],
                           mesh_shape=mesh_shape,
                           prefix_cache=es["prefix_cache"],
                           num_slabs=es.get("num_slabs"),
                           spec_k=es["spec_k"], drafter=args.drafter,
                           ragged=es["ragged"], w8a8=args.w8a8,
                           record=True, virtual_dt=es["virtual_dt"])
        res = replay_workload(rec, out["engine"])
        print(f"replay {args.replay}: fingerprint "
              f"{'match' if res.fingerprint_match else 'MISMATCH'} "
              f"(record {res.record_fingerprint}, engine "
              f"{res.engine_fingerprint})")
        print("tokens: " + ("identical" if res.token_identical else
                            f"MISMATCH rids={res.mismatched_rids}"))
        print(f"scheduler-decision diff: {len(res.decision_diff)} lines"
              + ("" if res.decision_diff else " (empty — identical)"))
        for line in res.decision_diff[:40]:
            print("  " + line)
        raise SystemExit(0 if res.ok else 1)

    if args.engine:
        import json
        try:
            out = serve_engine(
                args.arch, n_requests=args.requests,
                rate=args.rate, n_slots=args.slots,
                block_size=args.block_size, chunk=args.chunk,
                mode=args.mode, calibrate=not args.no_calibrate,
                smoke=not args.full,
                attn_kernel=args.attn_kernel,
                temperature=args.temperature, top_k=args.top_k,
                mesh_shape=mesh_shape,
                shared_prefix=args.shared_prefix,
                prefix_cache=prefix_cache,
                num_slabs=args.slabs, state_bits=args.state_bits,
                spec_k=args.spec_k, drafter=args.drafter,
                ragged=not args.no_ragged, w8a8=args.w8a8,
                trace=args.trace if args.trace else False,
                trace_capacity=args.trace_capacity,
                metrics_path=args.metrics,
                profile_dir=args.profile_dir,
                profile_cost=args.profile_cost,
                record=args.record if args.record else False,
                slo=True if args.slo else None)
        except ValueError as e:
            # substrate incompatibilities (e.g. --spec-k / --prefix-cache
            # on a recurrent arch) surface as one actionable line, not a
            # traceback
            ap.exit(2, f"error: {e}\n")
        print(json.dumps(out["report"], indent=2))
        if args.record:
            rec = out["record"]
            print(f"record: {len(rec.requests)} requests, "
                  f"{len(rec.decisions)} scheduler decisions, "
                  f"fingerprint {rec.fingerprint} -> {args.record} "
                  f"(replay with --replay {args.record})")
        if args.slo:
            mon = out["engine"].slo
            state = "ALERT" if mon.alerts_active else "ok"
            print(f"slo: {state} — {mon.alerts_fired} alert(s) fired "
                  f"over {mon.evaluations} evaluations; worst burn "
                  f"rate {mon.worst_burn_rate()}")
            for a in mon.alerts:
                print(f"  alert {a['objective']}: burn {a['burn_rate']} "
                      f"({a['window_bad']}/{a['window_total']} over "
                      f"window) at t={a['t']:.3f}s")
        if args.trace:
            obs = out["report"]["obs"]
            print(f"trace: {obs['trace_events']} events "
                  f"({obs['trace_dropped']} dropped, ring "
                  f"{obs['trace_capacity']}) -> {args.trace} "
                  f"(open in ui.perfetto.dev)")
        if args.metrics:
            print(f"metrics: prometheus exposition -> {args.metrics}")
        en = out["report"]["energy"]
        print(f"energy proxy ({en['unit']}): "
              f"{en['proxy_uj_per_token']} uJ/token live "
              f"[prefill {en['prefill']['uj_per_token']}, "
              f"decode {en['decode']['uj_per_token']}, "
              f"spec-wasted {en['spec_wasted']['uj_per_token']}]")
        sl = out["report"].get("state_pool")
        if sl is not None:
            hw = out["report"].get("hwcost", {})
            print(f"state slabs ({out['report']['substrate']}): "
                  f"{sl['peak_live_slabs']}/{sl['num_slabs']} peak live "
                  f"({sl['allocs']} allocs, {sl['seq_evictions']} "
                  f"evictions), {sl['state_quant_ops_per_step']} state "
                  f"requant ops/step/seq (scale exp {sl['scale_exp']}); "
                  f"requant ops/token {hw.get('requant_ops_per_token')}")
        hw = out["report"].get("hwcost", {})
        if hw.get("w8a8"):
            print(f"w8a8 forward: {hw['requant_ops_forward']} requant ops "
                  f"-> {hw['energy_uj_forward_bit_shift']:.3f} uJ "
                  f"(bit-shift) vs "
                  f"{hw['energy_uj_forward_if_scaling_factor']:.3f} uJ "
                  f"(scaling-factor unit); "
                  f"{len(out['quantized'].converted)} weight tensors "
                  f"pre-quantized to int8 codes")
        pc = out["report"].get("prefix_cache")
        if pc is not None:
            print(f"prefix cache: hit-rate {pc['hit_rate']:.1%} "
                  f"({pc['hits']}/{pc['hits'] + pc['misses']} block "
                  f"lookups), {pc['cached_prefill_tokens']} prefill "
                  f"tokens served from cache, {pc['cow_copies']} COW "
                  f"copies, {pc['cache_evictions']} LRU evictions")
        sp = out["report"].get("speculative")
        if sp is not None:
            print(f"speculative (K={sp['spec_k']}, {sp['drafter']}): "
                  f"acceptance {sp['acceptance_rate']}, "
                  f"{sp['tokens_per_step']} tokens/step over "
                  f"{sp['verify_steps']} verify steps, "
                  f"{sp['retracted_blocks']} blocks retracted "
                  f"({sp['requant_ops_wasted']} quant ops spent on "
                  f"rejected drafts)")
        return
    out = serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
                gen=args.gen, mode=args.mode,
                calibrate=not args.no_calibrate, smoke=not args.full,
                attn_kernel=args.attn_kernel, mesh_shape=mesh_shape)
    print(f"generated {out['tokens'].shape} tokens | "
          f"compile {out['compile_prefill_s']:.2f}s+"
          f"{out['compile_decode_s']:.2f}s | "
          f"prefill {out['prefill_s']:.2f}s | "
          f"decode {1e3*out['decode_s_per_tok']:.1f} ms/tok (steady)")
    print("sample:", out["tokens"][0][:16])


if __name__ == "__main__":
    main()
