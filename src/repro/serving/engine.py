"""Continuous-batching step loop over the paged int8-KV block pool.

The engine owns the device state (params + the block-pool cache from
``models.model.init_paged_cache``) and, by default (``ragged=True``,
DESIGN §12), drives ONE jitted unified step
(``launch.steps.build_ragged_step``): every engine step flattens the
whole mixed work-list — prefill chunks, decode rows, speculative tails —
into a single (T,) token stream with per-sequence descriptors and serves
it in ONE dispatch.  jit specializes per padded stream length only
(pow2 buckets up to ``prefill_token_budget + n_slots * (spec_k + 1)``),
so the executable set is O(few) regardless of traffic mix, and the
descriptor arrays make padding waste a measured quantity
(``padded_tokens`` / ``padding_frac`` in the report).

``ragged=False`` keeps the retired per-shape dispatch trio for A/B:

* decode: (n_slots, 1) — every engine step decodes ALL live slots at
  their own positions; finished slots are backfilled by newly admitted
  requests, so the batch never drains (continuous batching).
* chunked prefill: (1, C) for C in the scheduler's bucket set — prompts
  are fed ``chunk`` tokens at a time under a per-step token budget.
* speculative verify: (n_slots, K+1) when drafting is on (DESIGN §11) —
  each live slot's last token plus up to K drafted tokens are scored in
  ONE step, with Leviathan/Chen rejection sampling fused into the jit;
  only accepted tokens commit to the pool, the rejected tail retracts.

There jit compiles 1 (decode) + |buckets| (prefill) + 1 (verify)
executables and serializes the phases the ragged path fuses.

KV codes are written once on the Eq.-1 power-of-two grid and stay
int8-resident in the pool until the request leaves; attention consumes
them in place (fused paged kernel on MXU-aligned shapes, gather reference
otherwise).  The report quantifies what that buys with the paper's Table 5
constants (``core.hwcost``): the requant ops actually executed vs the ops
a dequantize-the-cache-every-step dataflow would have executed.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import hwcost
from repro.core.qmodel import QuantContext
from repro.kernels import ops as kops
from repro.kernels.ragged_flash import ragged_walk_counts
from repro.launch import steps as S
from repro.models import model as M
from repro.models.attention import RaggedBatch
from repro.obs.metrics import MetricsRegistry
from repro.obs.profile import EnergyAccount, Profiler
from repro.obs.slo import SLOMonitor, default_slos
from repro.obs.trace import Tracer
from repro.serving.kv_pool import TRASH_BLOCK, BlockPool, BlockPoolError
from repro.serving.scheduler import (Request, RequestState, Scheduler,
                                     chunk_bucket)
from repro.serving.spec import apply_top_k, resolve_drafter, verify_tokens
from repro.serving.state_pool import TRASH_SLAB, StateSlabPool
from repro.serving.substrate import substrate_for

__all__ = ["ServingEngine", "sample_tokens", "summarize_step_times"]


def sample_tokens(logits: jax.Array, key: jax.Array,
                  temperatures: jax.Array,
                  top_k: Optional[jax.Array] = None,
                  k_cap: Optional[int] = None) -> jax.Array:
    """Greedy + temperature/top-k sampling hook.

    logits (B, V); temperatures (B,) — 0 selects greedy for that row;
    top_k (B,) int32 — 0 keeps the full vocabulary for that row.  Both
    are PER-ROW traced values, so one fixed-shape call serves a batch
    mixing greedy, full-vocab and top-k requests (continuous batching
    cannot afford a recompile per sampling config).  ``k_cap`` is a
    STATIC bound on the batch's largest top-k (the engine passes the
    host-known max): the cutoff comes from an O(V log k_cap)
    ``lax.top_k`` instead of a full-vocab sort in the decode hot loop,
    and ties at the threshold break by index so the candidate set is
    EXACTLY k (the old ``logits < kth`` kept every tied token)."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    if top_k is not None:
        logits = apply_top_k(logits, top_k, k_cap)
    scaled = logits / jnp.maximum(temperatures, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temperatures > 0, sampled, greedy).astype(jnp.int32)


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def summarize_step_times(step_times: dict) -> dict:
    """Per-shape compile-vs-steady split: the first call of a jitted shape
    pays tracing+compilation, the median of the rest is steady state.

    Keyed by the shape that was ACTUALLY dispatched: ragged work-list
    entries ``("ragged", T_pad, S_pad)`` become ``ragged_{T}xS{S}`` at
    the top level (these are the unified engine's only executables), and
    the retired per-shape tuples ``(B, C)`` are kept — verbatim ``BxC``
    keys — under a ``legacy_shapes`` section so older BENCH_serving.json
    entries stay comparable.  Preformatted string keys (the static
    baseline bench's) pass through at the top level.

    Edge cases are well-defined, never an IndexError (obs satellite): an
    EMPTY sample list reports ``calls 0`` with every latency field None;
    one call has a ``first_s`` but no steady state; ``p99_s`` — the
    steady-state tail over the post-compile samples — needs at least two
    steady samples, otherwise it is None rather than parroting a single
    observation back as a "percentile" (a p99 of one sample is just that
    sample, and reporting it as a tail bound is how 1-sample noise ends
    up gating a bench)."""
    shapes: dict = {}
    legacy: dict = {}
    for shape, ts in sorted(step_times.items(), key=lambda kv: str(kv[0])):
        steady_ts = ts[1:]
        steady = float(np.median(steady_ts)) if steady_ts else None
        p99 = _pct(steady_ts, 99) if len(steady_ts) >= 2 else None
        entry = {"calls": len(ts),
                 "first_s": round(ts[0], 4) if ts else None,
                 "steady_s": round(steady, 4) if steady is not None else None,
                 "p99_s": round(p99, 4) if p99 is not None else None}
        if isinstance(shape, tuple) and shape and shape[0] == "ragged":
            shapes[f"ragged_{shape[1]}xS{shape[2]}"] = entry
        elif isinstance(shape, tuple) and shape and shape[0] == "recurrent":
            # fixed-shape recurrent dispatch (DESIGN §16): one executable
            # per (n_slots, chunk), named at the top level like ragged
            shapes[f"recurrent_{shape[1]}xC{shape[2]}"] = entry
        elif isinstance(shape, tuple):
            legacy["x".join(map(str, shape))] = entry
        else:
            shapes[str(shape)] = entry
    if legacy:
        shapes["legacy_shapes"] = legacy
    return shapes


class ServingEngine:
    """Continuous-batching serving engine (DESIGN §9)."""

    def __init__(self, cfg: ModelConfig, params: Any, ctx: QuantContext, *,
                 n_slots: int = 4, block_size: int = 16,
                 max_model_len: int = 128,
                 num_blocks: Optional[int] = None, chunk: int = 16,
                 prefill_token_budget: Optional[int] = None,
                 num_slabs: Optional[int] = None,
                 top_k: int = 0, mesh=None, seed: int = 0,
                 prefix_cache: Optional[bool] = None, spec_k: int = 0,
                 drafter="ngram", ragged: bool = True,
                 trace: bool = False, trace_capacity: int = 65536,
                 profile_dir: Optional[str] = None,
                 profile_cost: bool = False,
                 record: bool = False, virtual_dt: float = 1e-3,
                 slo=None):
        self.cfg = cfg
        from repro.core.qmodel import QuantizedParams
        if isinstance(params, QuantizedParams):
            # W8A8 deploy container: the engine only ever runs the code
            # tree; the exponents already live in ctx.table
            params = params.tree
        self.params = params
        self.ctx = ctx
        self.n_slots = n_slots
        self.max_model_len = max_model_len
        # substrate routing (DESIGN §16): the config's layer mix decides
        # which pools back this engine's sequences.  Attention sequences
        # grow block tables from the BlockPool; recurrent (RWKV6 / Mamba2)
        # sequences keep ONE fixed-size quantized state slab from the
        # StateSlabPool; a hybrid (zamba2) holds both at once.
        self.substrate = sub = substrate_for(cfg)
        if spec_k > 0 and not sub.supports_spec:
            raise ValueError(
                f"spec_k={spec_k} is unsupported on the {sub.kind} "
                "substrate: speculative decoding must retract rejected "
                "draft tokens, but fixed-size recurrent state cannot be "
                "rolled back (use spec_k=0 for recurrent/hybrid models)")
        if prefix_cache and not sub.supports_prefix_cache:
            raise ValueError(
                f"prefix_cache=True is unsupported on the {sub.kind} "
                "substrate: recurrent state is a running summary, not an "
                "addressable token range, so there is no prefix to share "
                "(leave prefix_cache unset for auto, or pass False)")
        if prefix_cache is None:
            prefix_cache = sub.supports_prefix_cache
        ragged = ragged and sub.supports_ragged
        nbmax = -(-max_model_len // block_size)
        if sub.grows:
            if num_blocks is None:
                # full residency: every slot can reach max_model_len
                # (+ trash).  Callers undersize this deliberately to
                # exercise preemption.
                num_blocks = 1 + n_slots * nbmax
            scale_exp = cfg.kv_cache_frac_bits if cfg.kv_cache_bits == 8 \
                else 0
            self.pool: Optional[BlockPool] = BlockPool(
                num_blocks, block_size, scale_exp=scale_exp,
                prefix_cache=prefix_cache)
        else:
            self.pool = None
        if sub.fixed_state:
            if num_slabs is None:
                num_slabs = 1 + n_slots      # one per slot + trash
            st_exp = cfg.state_frac_bits if cfg.state_bits == 8 else 0
            self.state_pool: Optional[StateSlabPool] = StateSlabPool(
                num_slabs, scale_exp=st_exp)
        else:
            self.state_pool = None
        self.sched = Scheduler(self.pool, n_slots=n_slots, chunk=chunk,
                               max_model_len=max_model_len,
                               prefill_token_budget=prefill_token_budget,
                               state_pool=self.state_pool, substrate=sub)
        # observability (DESIGN §14): one tracer threaded through every
        # serving-path module.  Ring events are off unless ``trace=True``;
        # per-request timelines (a few floats each) are always on — they
        # are the source of the report's trace-derived latency section.
        self.tracer = Tracer(capacity=trace_capacity, clock=self._now,
                             enabled=trace)
        if self.pool is not None:
            self.pool.tracer = self.tracer
            if self.pool.cache is not None:
                self.pool.cache.tracer = self.tracer
        if self.state_pool is not None:
            self.state_pool.tracer = self.tracer
        self.sched.tracer = self.tracer
        if sub.snapshot_preempt:
            # pure-recurrent preemption snapshots the sequence's whole
            # state slab to the host (no token range to recompute from the
            # pool) — the scheduler calls this hook, admit restores it
            self.sched.snapshot_fn = self._snapshot_slab
        # flight recorder (DESIGN §15): record mode switches run() onto a
        # deterministic VIRTUAL clock (virtual_dt seconds per step, idle
        # gaps jump to the next arrival) and tees the scheduler-decision
        # events into an unbounded sink — the capture run is then exactly
        # reproducible, which is the whole replay contract.  Tracing is
        # forced on (the decision event call sites are ring-gated).
        if virtual_dt <= 0.0:
            raise ValueError(f"virtual_dt must be > 0, got {virtual_dt}")
        self.record = record
        self.virtual_dt = virtual_dt
        self._virtual_time: Optional[float] = 0.0 if record else None
        if record:
            self.tracer.enabled = True
            self.tracer.decision_sink = []
        # SLO burn-rate monitor (DESIGN §15): evaluated once per step on
        # the engine clock (virtual under record mode, so SLO evaluation
        # replays deterministically too).  ``slo=True`` takes the stock
        # objective set; a list of SLObjective customizes it.
        if slo is None:
            self.slo: Optional[SLOMonitor] = None
        else:
            objectives = default_slos() if slo is True else slo
            self.slo = SLOMonitor(objectives, tracer=self.tracer,
                                  value_fn=self._metric_value)
        self.profiler = Profiler(profile_dir=profile_dir, cost=profile_cost,
                                 tracer=self.tracer)
        # live Table-5 energy proxy, split prefill / decode / spec_wasted;
        # reconciles exactly with the requant counters below (tested)
        self.energy = EnergyAccount("bit_shifting")
        if sub.kind == "attention":
            self.cache = M.init_paged_cache(cfg, num_blocks, block_size)
        elif sub.kind == "recurrent":
            self.cache = M.init_paged_state(cfg, self.state_pool.num_slabs)
        else:                                # hybrid: slabs + block tables
            self.cache = M.init_paged_state(cfg, self.state_pool.num_slabs,
                                            num_blocks=num_blocks,
                                            block_size=block_size)
        # sampling is FUSED into the jitted step: one dispatch + one host
        # sync per engine step, and only the (B,) sampled tokens ever leave
        # the device — logits never cross to the host.  The rng key derives
        # from a per-call counter via fold_in inside the jit, so the host
        # does zero PRNG work per step and runs stay seed-reproducible.
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.spec_k = spec_k
        self.drafter = resolve_drafter(drafter)
        self.ragged = ragged
        self.seed = seed
        base_key = jax.random.PRNGKey(seed)
        kp1 = spec_k + 1
        self._step_fn = self._spec_fn = self._ragged_fn = None
        self._rec_fn = None
        if sub.kind == "attention":
            base_step = S.build_paged_step(cfg, ctx, mesh=mesh)

            def sampled_step(params, tokens, cache, positions, bt, temps,
                             topks, last_idx, step_idx, k_cap):
                logits, cache = base_step(params, tokens, cache, positions,
                                          bt)
                row = jax.lax.dynamic_index_in_dim(logits, last_idx, axis=1,
                                                   keepdims=False)  # (B, V)
                key = jax.random.fold_in(base_key, step_idx)
                return sample_tokens(row, key, temps, topks,
                                     k_cap=k_cap), cache

            # donate the pool: the per-token scatter then updates the
            # arena in place — without donation XLA copies the whole
            # multi-MB pool every step, which is exactly the
            # write-amplification the paged design exists to avoid.
            # k_cap is static (the host-known max top-k of the batch):
            # one extra executable per distinct cap, and the sampler's
            # cutoff stays an O(V log k) partial sort.
            self._step_fn = jax.jit(sampled_step, donate_argnums=(2,),
                                    static_argnums=(9,))

            # speculative verify step (DESIGN §11): score the (B, K+1)
            # chunk and resolve draft acceptance in ONE dispatch —
            # rejection sampling is fused into the jit, and only
            # (out tokens, accepted counts) ever cross to the host
            def spec_verify_step(params, tokens, cache, positions, bt,
                                 temps, topks, n_drafts, step_idx, k_cap):
                logits, cache = base_step(params, tokens, cache, positions,
                                          bt)
                key = jax.random.fold_in(base_key, step_idx)
                out, n_acc = verify_tokens(logits, tokens, n_drafts, key,
                                           temps, topks, k_cap=k_cap)
                return out, n_acc, cache

            self._spec_fn = jax.jit(spec_verify_step, donate_argnums=(2,),
                                    static_argnums=(9,))

            # UNIFIED ragged step (DESIGN §12): the whole mixed work-list
            # — prefill chunks, decode rows, speculative tails —
            # flattened to one (T,) stream with per-sequence descriptors,
            # served by ONE dispatch.  Sampling and draft verification
            # share one fused sampler: every sequence gathers K+1 logit
            # rows starting at its ``sample_start`` and runs
            # Leviathan/Chen verification — a prefill/decode row rides
            # with n_drafts=0, which reduces verify_tokens to plain
            # sampling of row 0, so one executable covers every traffic
            # class.
            base_ragged = S.build_ragged_step(cfg, ctx, mesh=mesh)

            def ragged_sampled_step(params, tokens, cache, positions, rb,
                                    temps, topks, sample_start, n_drafts,
                                    step_idx, k_cap):
                logits, cache = base_ragged(params, tokens, cache,
                                            positions, rb)
                with jax.named_scope("sample"):
                    t = logits.shape[0]
                    idx = jnp.clip(
                        sample_start[:, None]
                        + jnp.arange(kp1, dtype=jnp.int32)[None, :],
                        0, t - 1)
                    rows = jnp.take(logits, idx, axis=0)    # (S, K+1, V)
                    toks = jnp.take(tokens, idx, axis=0)    # (S, K+1)
                    key = jax.random.fold_in(base_key, step_idx)
                    out, n_acc = verify_tokens(rows, toks, n_drafts, key,
                                               temps, topks, k_cap=k_cap)
                return out, n_acc, cache

            self._ragged_fn = jax.jit(ragged_sampled_step,
                                      donate_argnums=(2,),
                                      static_argnums=(10,))
        else:
            # batched recurrent step (DESIGN §16): ONE fixed-shape
            # executable per (n_slots, chunk) serves the whole mixed
            # work-list — prefill chunks feed q_len=c tokens, decode rows
            # q_len=1, idle lanes q_len=0 against the trash slab — so the
            # recurrent substrate needs no ragged flattening at all.  The
            # step gathers each row's slab, dequantizes to the compute
            # dtype, runs every layer, and re-quantizes the WHOLE state
            # back to its slab exactly once (the context-free requant the
            # report's ops/token gauge quantifies).  Sampling is fused
            # like the attention paths; logits are already (B, V).
            base_rec = S.build_recurrent_step(cfg, ctx, mesh=mesh)

            def recurrent_sampled_step(params, tokens, cache, slab_ids,
                                       q_len, positions, bt, temps, topks,
                                       step_idx, k_cap):
                logits, cache = base_rec(params, tokens, cache, slab_ids,
                                         q_len, positions, bt)
                key = jax.random.fold_in(base_key, step_idx)
                return sample_tokens(logits, key, temps, topks,
                                     k_cap=k_cap), cache

            self._rec_fn = jax.jit(recurrent_sampled_step,
                                   donate_argnums=(2,),
                                   static_argnums=(10,))
        # padded-stream buckets: pow2 from 8 up to the step's worst case
        # (full prefill budget + every slot verifying a K-token tail), so
        # jit sees O(log) distinct ragged executables
        budget = self.sched.prefill_token_budget
        self._t_max = max(8, -(-(budget + n_slots * kp1) // 8) * 8)
        # no sequence feeds more than one chunk or one verify tail per
        # step: the static bound that sizes the kernel's q window
        self._tq_max = max(self.sched.chunk, kp1)
        self._mesh = mesh           # the attention kernel's head shards

        # COW device copy (DESIGN §10): duplicate one pool block's rows
        # (all layers, K and V) into a fresh private block before a write
        # would land in a shared/published block.  Donated for the same
        # reason as the step: copy block_size rows, not the whole arena.
        def cow_copy(cache, src, dst):
            return jax.tree.map(
                lambda a: a.at[:, dst].set(a[:, src]), cache)

        self._cow_fn = jax.jit(cow_copy, donate_argnums=(0,))
        self._step_counter = 0
        # engine-level default top-k, applied to requests that don't set
        # their own (Request.top_k > 0 wins per slot)
        self.default_top_k = top_k
        # one requant op per KV element (paper's unit of Table 5).  Only
        # layers that WRITE per-token KV count: every layer on attention,
        # the shared attention blocks (one per attn_every stride) on
        # hybrid, none on pure recurrent.
        if sub.kind == "hybrid":
            n_kv_layers = cfg.n_layers // cfg.hybrid.attn_every
        elif sub.kind == "recurrent":
            n_kv_layers = 0
        else:
            n_kv_layers = cfg.n_layers
        self._elems_per_token = (n_kv_layers * cfg.n_kv_heads
                                 * cfg.resolved_head_dim * 2)
        # fixed-slab counterpart: ops to requantize one sequence's WHOLE
        # recurrent state, paid once per step regardless of context
        # (DESIGN §16) — 'performed' when slabs are int8, the
        # counterfactual 'avoided' bucket when they stay fp32
        self._state_elems_per_step = hwcost.state_quant_ops_per_step(cfg) \
            if sub.fixed_state else 0
        # running total of the state ops above — kept SEPARATE from the
        # merged performed/avoided buckets so a hybrid run can report the
        # recurrent substrate's share of the per-token gauge on its own
        self.requant_ops_state = 0
        self.requant_ops_performed = 0
        self.requant_ops_avoided = 0
        # quant ops the PREFIX CACHE deleted outright: cached-prefix tokens
        # are never quantized at all for the hitting request (Table 5)
        self.requant_ops_avoided_cache = 0
        # quant ops SPENT on rejected drafts: performed, then rolled back —
        # exactly the waste the paper's write-once scheme minimizes
        # elsewhere, reported honestly instead of hidden (Table 5)
        self.requant_ops_wasted_spec = 0
        # true-W8A8 forward accounting (DESIGN §13): per-token dynamic
        # quant ops of the projection/MLP/head dataflow — activation quant
        # at every module boundary + the fused output requant.  Zero on the
        # dense path, so the forward counters below only move under
        # matmul_kernel='int8'.  Kept SEPARATE from the KV counters above
        # so the KV-only Table-5 accounting stays comparable across runs.
        self._fwd_elems_per_token = (
            hwcost.forward_quant_ops_per_token(cfg)
            if cfg.matmul_kernel == "int8" else 0)
        self.requant_ops_forward = 0
        self.requant_ops_forward_avoided_cache = 0
        self.requant_ops_forward_wasted_spec = 0
        self.cache_hit_prefill_tokens = 0
        self.decode_steps = 0
        self.prefill_chunks = 0
        self.spec_steps = 0
        self.spec_slot_steps = 0    # (live slot, verify step) pairs
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.ragged_steps = 0
        self.recurrent_steps = 0
        # padding honesty (satellite): every dispatched token that carried
        # no real work — pow2 bucket rounding, empty decode slots, unused
        # draft columns — counted at dispatch time on BOTH paths
        self.dispatched_tokens = 0
        self.padded_tokens = 0
        # the ragged attention kernel's walk, one layer's call per ragged
        # dispatch: grid steps issued, live pool blocks copied
        self.attn_grid_steps = 0
        self.attn_kv_blocks_copied = 0
        self._step_times: dict[tuple, list] = {}    # shape key -> wall s
        self._t0 = time.perf_counter()
        self._skip = 0.0
        # the engine clock (seconds): whoever drives the engine may hand
        # it their own, so arrivals, timeline marks and ring spans all
        # lie on the caller's clock; ``run`` uses this default
        self.clock: Callable[[], float] = self._run_clock
        self._wall_s = 0.0
        # the registry is the single source of report naming/typing:
        # report() is a nested view of it (DESIGN §14)
        self.metrics = MetricsRegistry()
        self._register_metrics()

    # -- clock ------------------------------------------------------------

    def _now(self) -> float:
        """Engine clock, seconds: ``self.clock`` normally; the
        deterministic VIRTUAL clock under ``record=True`` — every
        timeline mark, trace timestamp and SLO window then replays
        bit-identically (DESIGN §15)."""
        if self._virtual_time is not None:
            return self._virtual_time
        return self.clock()

    def _run_clock(self) -> float:
        """The default clock: monotonic minus the idle gaps ``run``
        fast-forwarded."""
        return time.perf_counter() - self._t0 + self._skip

    def _metric_value(self, name: str):
        """Registry read for the SLO monitor's gauge objectives."""
        return self.metrics.get_value(name)

    # -- public API -------------------------------------------------------

    def submit(self, req: Request) -> None:
        self.tracer.req_submit(req.rid, req.arrival)
        self.sched.submit(req)

    def workload_record(self, requests: list[Request]):
        """Freeze the last ``record=True`` run into a portable
        :class:`repro.obs.replay.WorkloadRecord` (DESIGN §15)."""
        from repro.obs.replay import capture_workload
        return capture_workload(self, requests)

    def reset_metrics(self, *, flush_cache: bool = True) -> None:
        """Clear accounting between runs (e.g. after a warm-up workload
        that populated the jit caches) — engine must be drained first.
        The sampling step counter resets too, so a reused engine replays
        the same rng stream (seed-reproducible across passes); note that
        post-reset ``first_s`` per shape reflects a WARM first call, not
        compilation.  By default the PREFIX CACHE is flushed too, so every
        pass starts cold — inter-pass hits would make pass N incomparable
        to pass 1; pass ``flush_cache=False`` to measure the warm-cache
        steady state (e.g. after priming a shared system prompt)."""
        assert self.sched.idle \
            and (self.pool is None or self.pool.n_live == 0) \
            and (self.state_pool is None or self.state_pool.n_live == 0), \
            "reset_metrics on a non-drained engine"
        from repro.serving.kv_pool import PoolStats
        from repro.serving.prefix_cache import CacheStats
        self._step_counter = 0
        self.sched.done.clear()
        self.sched.admission_log.clear()
        if self.pool is not None:
            if flush_cache:
                self.pool.flush_cache()
            self.pool.reset_free_order()
            self.pool.stats = PoolStats()
            if self.pool.cache is not None:
                self.pool.cache.stats = CacheStats()
        if self.state_pool is not None:
            self.state_pool.reset_free_order()
            self.state_pool.stats = PoolStats()
        self.requant_ops_performed = 0
        self.requant_ops_avoided = 0
        self.requant_ops_avoided_cache = 0
        self.requant_ops_wasted_spec = 0
        self.requant_ops_forward = 0
        self.requant_ops_forward_avoided_cache = 0
        self.requant_ops_forward_wasted_spec = 0
        self.cache_hit_prefill_tokens = 0
        self.decode_steps = 0
        self.prefill_chunks = 0
        self.spec_steps = 0
        self.spec_slot_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.ragged_steps = 0
        self.recurrent_steps = 0
        self.dispatched_tokens = 0
        self.padded_tokens = 0
        self.attn_grid_steps = 0
        self.attn_kv_blocks_copied = 0
        self._step_times.clear()
        self._wall_s = 0.0
        if self.record:
            self._virtual_time = 0.0
        self.energy.reset()
        self.tracer.reset()          # clears the decision sink too
        if self.slo is not None:
            self.slo.reset()
        self.profiler.reset()
        self.metrics.reset()        # owned metrics only; bound ones follow
        stats = getattr(self.drafter, "stats", None)
        if stats is not None:
            stats.reset()

    def run(self, requests: list[Request]) -> dict:
        """Serve ``requests`` (arrival-stamped) to completion; idle gaps
        between arrivals are fast-forwarded on the engine clock, so the
        report's latencies are arrival-relative without real sleeps.
        Under ``record=True`` the loop runs on the virtual clock instead
        (``virtual_dt`` per step): arrival→admission composition then
        depends only on the workload, never the host, so the run is
        exactly replayable (obs/replay.py)."""
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        if self.record:
            return self._run_virtual(pending)
        self._t0, self._skip = time.perf_counter(), 0.0
        while pending or not self.sched.idle:
            now = self._now()
            if self.sched.idle and pending and pending[0].arrival > now:
                self._skip += pending[0].arrival - now
                now = self._now()
            while pending and pending[0].arrival <= now:
                self.submit(pending.pop(0))
            self.step()
        self._wall_s = self._now()
        return self.report()

    def _run_virtual(self, pending: list[Request]) -> dict:
        """The record-mode run loop: same structure as ``run`` but the
        clock advances ``virtual_dt`` per step and jumps straight to the
        next arrival when idle."""
        self._virtual_time = 0.0
        while pending or not self.sched.idle:
            now = self._virtual_time
            if self.sched.idle and pending and pending[0].arrival > now:
                self._virtual_time = now = pending[0].arrival
            while pending and pending[0].arrival <= now:
                self.submit(pending.pop(0))
            self.step()
            self._virtual_time += self.virtual_dt
        self._wall_s = self._virtual_time
        return self.report()

    def step(self) -> None:
        """One engine iteration.  Ragged (default): admit → ONE unified
        dispatch over the mixed work-list.  Legacy (``ragged=False``):
        admit → chunked prefill → decode (a speculative verify step when
        drafting is on and produced drafts, the plain (B, 1) decode
        otherwise).  The whole call is one ``engine.step`` phase; its
        children name where the host time goes (``engine.admit``,
        ``engine.plan``, ``engine.pack``, ``engine.transfer``,
        ``engine.launch``, ``engine.sync``, ``engine.commit``)."""
        tr = self.tracer
        with tr.phase("engine.step", "engine", step=self._step_counter):
            with tr.phase("engine.admit", "engine"):
                self._admit()
            if self.substrate.fixed_state:
                self._run_recurrent_step()
            elif self.ragged:
                self._run_ragged_step()
            else:
                self._run_prefills()
                if not (self.spec_k and self._run_spec_decode()):
                    self._run_decode()
            if self.slo is not None:
                self.slo.evaluate(self._now())

    def _admit(self) -> None:
        for req in self.sched.admit(self._now()):
            if self.substrate.fixed_state:
                if req.snapshot is not None:
                    # preemption snapshot resume: the saved state codes
                    # drop back into the fresh slab — these tokens were
                    # PAID for before eviction, not prefix-cache hits
                    self._restore_snapshot(req)
                else:
                    # slabs are recycled LIFO: a fresh sequence must not
                    # inherit the previous owner's final state
                    self._reset_slab(req)
                continue
            # cached-prefix hit: those tokens' KV is already resident, so
            # their quantization ops simply never happen for this request
            self.cache_hit_prefill_tokens += req.n_prefilled
            self.requant_ops_avoided_cache += \
                req.n_prefilled * self._elems_per_token
            # under W8A8 the hit also skips the whole forward for those
            # tokens — none of their matmul-boundary quant ops ever run
            self.requant_ops_forward_avoided_cache += \
                req.n_prefilled * self._fwd_elems_per_token

    # -- unified ragged step (DESIGN §12) ---------------------------------

    def _t_bucket(self, n: int) -> int:
        """Padded stream length for ``n`` real tokens: smallest power of
        two >= n (floored at the sublane size 8), capped at the step's
        static worst case — O(log) distinct jitted stream lengths."""
        b = 8
        while b < n:
            b <<= 1
        return min(b, self._t_max)

    def _run_ragged_step(self) -> None:
        """Plan the mixed work-list, then serve it in ONE dispatch.

        Planning mirrors the legacy phases: every PREFILL job contributes
        one chunk under the shared token budget (CoW-protected), every
        DECODE request contributes its fed token plus a speculative tail
        when drafting is on (pool growth degrades the tail before
        preempting peers, exactly like the per-shape verify step).
        Growth/CoW for a later request may preempt an earlier one, so
        planned items are re-validated against slot residency before the
        arrays are built — a preempted request's chunk simply drops out
        of this step, the same outcome the phase-ordered path reaches by
        dispatching before planning the next phase."""
        tr = self.tracer
        with tr.phase("engine.plan", "engine"):
            now = self._now()
            budget = self.sched.prefill_token_budget
            prefill_items = []                  # (req, start, c_real)
            for req in self.sched.prefill_jobs():
                if budget <= 0:
                    break
                start = req.n_prefilled
                c_real = min(self.sched.chunk, len(req.feed) - start, budget)
                # copy-on-write (DESIGN §10): any block this chunk writes
                # into must be private (returns False iff req was preempted)
                if not self._cow_for_range(req, start, start + c_real):
                    continue
                budget -= c_real
                prefill_items.append((req, start, c_real))

            proposals = {}
            if self.spec_k:
                for req in self.sched.decode_reqs():
                    b = self._spec_budget(req)
                    if b > 0:
                        d = np.asarray(self.drafter.draft(
                            np.concatenate([req.prompt, np.asarray(
                                req.generated, np.int32)]), b), np.int32)
                        proposals[req.rid] = d[:b]
            has_spec = any(len(d) for d in proposals.values())
            plans: dict[int, np.ndarray] = {}
            for req in list(self.sched.decode_reqs()):
                if req.slot is None or req.state is not RequestState.DECODE:
                    continue
                drafts = proposals.get(req.rid, np.empty(0, np.int32))
                if has_spec:
                    granted = self.sched.grow_for_spec(req, now, len(drafts))
                    if granted is None:
                        continue                # req itself was preempted
                    drafts = drafts[:granted]
                    # the speculative tail must only write private blocks
                    if not self._cow_for_range(req, req.n_ctx,
                                               req.n_ctx + 1 + len(drafts)):
                        continue                # req itself was preempted
                elif not self.sched.grow_for_decode(req, now):
                    continue                    # req itself was preempted
                plans[req.rid] = drafts

            # re-validate: growth/CoW above may have preempted planned items
            prefill_items = [
                (r, s, c) for (r, s, c) in prefill_items
                if r.slot is not None and r.state is RequestState.PREFILL
                and r.n_prefilled == s]
            decode_items = [(r, plans[r.rid]) for r in self.sched.decode_reqs()
                            if r.rid in plans]
            if not prefill_items and not decode_items:
                return

        with tr.phase("engine.pack", "engine"):
            # -- build the flattened stream + descriptors -----------------
            bs = self.pool.block_size
            nbmax = self.sched.nbmax
            s_pad = self.n_slots
            q_lens = [c for (_, _, c) in prefill_items] \
                + [1 + len(d) for (_, d) in decode_items]
            t_real = sum(q_lens)
            t_pad = self._t_bucket(t_real)
            tokens = np.zeros(t_pad, np.int32)
            positions = np.zeros(t_pad, np.int32)
            dest = np.zeros(t_pad, np.int32)  # padding rows scatter to trash
            q_start = np.full(s_pad, t_pad, np.int32)
            q_len = np.zeros(s_pad, np.int32)
            kv_len = np.zeros(s_pad, np.int32)
            bt = np.full((s_pad, nbmax), TRASH_BLOCK, np.int32)
            temps = np.zeros(s_pad, np.float32)
            topks = np.zeros(s_pad, np.int32)
            sample_start = np.zeros(s_pad, np.int32)
            n_drafts = np.zeros(s_pad, np.int32)
            fed: list[np.ndarray] = []
            off = 0
            for i, (req, item) in enumerate(
                    [(r, (s, c)) for (r, s, c) in prefill_items]
                    + [(r, d) for (r, d) in decode_items]):
                if i < len(prefill_items):
                    start, c_real = item
                    toks_i = np.asarray(req.feed[start:start + c_real],
                                        np.int32)
                    pos_i = start + np.arange(c_real, dtype=np.int32)
                    sample_start[i] = off + c_real - 1     # last real row
                else:
                    d = item
                    toks_i = np.concatenate(
                        [[req.generated[-1]], d]).astype(np.int32)
                    pos_i = req.n_ctx + np.arange(1 + len(d),
                                                  dtype=np.int32)
                    sample_start[i] = off                  # fed-token row
                    n_drafts[i] = len(d)
                n = len(toks_i)
                row = self.pool.table_row(req.rid, nbmax)
                tokens[off:off + n] = toks_i
                positions[off:off + n] = pos_i
                dest[off:off + n] = row[pos_i // bs] * bs + pos_i % bs
                q_start[i] = off
                q_len[i] = n
                kv_len[i] = int(pos_i[-1]) + 1
                bt[i] = row
                temps[i] = req.temperature
                topks[i] = self._req_top_k(req)
                fed.append(toks_i)
                off += n
            # the walk of the attention kernel each layer's call runs,
            # at the shapes ops.ragged_attention builds it for
            hd = self.cfg.resolved_head_dim
            shape = kops.ragged_call_shape(
                t_pad, self.cfg.n_heads, self.cfg.n_kv_heads, nbmax, bs, hd,
                hd, tq_max=self._tq_max, mesh=self._mesh,
                shard_axis=self.cfg.attn_shard_axis)
            if shape is not None:
                steps, blocks = ragged_walk_counts(kv_len, **shape)
                self.attn_grid_steps += steps
                self.attn_kv_blocks_copied += blocks
        out, n_acc = self._dispatch_ragged(tokens, positions, dest, bt,
                                           q_start, q_len, kv_len, temps,
                                           topks, sample_start, n_drafts,
                                           t_real=t_real)
        with tr.phase("engine.commit", "engine"):
            self.ragged_steps += 1
            self.dispatched_tokens += t_pad
            self.padded_tokens += t_pad - t_real
            now = self._now()
            ept = self._elems_per_token + self._fwd_elems_per_token

            # -- post-process: prefill items (mirrors _prefill_chunk) ---------
            for i, (req, start, c_real) in enumerate(prefill_items):
                req.n_prefilled += c_real
                req.n_ctx = req.n_prefilled
                self.pool.commit(req.rid, start,
                                 req.feed[start:start + c_real])
                self.prefill_chunks += 1
                self.requant_ops_performed += c_real * self._elems_per_token
                self.requant_ops_forward += c_real * self._fwd_elems_per_token
                self.energy.charge("prefill", c_real * ept, c_real)
                if tr.enabled:
                    # chunk boundary: part of the scheduler-decision stream
                    # the flight recorder diffs between runs (DESIGN §15)
                    tr.event("sched.prefill_chunk", "sched", ts=now, args={
                        "rid": req.rid, "start": start, "tokens": c_real})
                tr.req_mark(req.rid, "first_chunk", now)
                if req.n_prefilled == len(req.feed):
                    tok = int(out[i, 0])
                    if req.t_first is None:
                        req.t_first = now
                    tr.req_mark(req.rid, "first_token", now)
                    tr.req_token(req.rid, now)
                    done = req.finished_by(tok, self.max_model_len)
                    req.generated.append(tok)
                    if done:
                        self.sched.finish(req, now)
                    else:
                        req.state = RequestState.DECODE

            # -- post-process: decode items (mirrors _run_decode / spec) ------
            if decode_items:
                if has_spec:
                    self.spec_steps += 1
                    self.spec_slot_steps += len(decode_items)
                else:
                    self.decode_steps += 1
            for j, (req, d) in enumerate(decode_items):
                i = len(prefill_items) + j
                fed_tok = int(fed[i][0])
                if has_spec:
                    acc = int(n_acc[i])
                    emitted = out[i, :acc + 1].tolist()
                    kept_drafts = 0
                    n_out = 0
                    done = False
                    for k, tok in enumerate(emitted):
                        done = req.finished_by(int(tok), self.max_model_len)
                        req.generated.append(int(tok))
                        tr.req_token(req.rid, now)
                        self.spec_emitted += 1
                        n_out += 1
                        if k < acc:
                            kept_drafts += 1   # its KV row is resident
                        if done:
                            break
                    self.pool.commit(req.rid, req.n_ctx,
                                     [fed_tok] + d[:kept_drafts].tolist())
                    self.requant_ops_performed += \
                        (1 + len(d)) * self._elems_per_token
                    self.requant_ops_wasted_spec += \
                        (len(d) - kept_drafts) * self._elems_per_token
                    # every fed row (real token + all drafts) ran the W8A8
                    # forward; rejected drafts' forward ops are pure waste
                    self.requant_ops_forward += \
                        (1 + len(d)) * self._fwd_elems_per_token
                    self.requant_ops_forward_wasted_spec += \
                        (len(d) - kept_drafts) * self._fwd_elems_per_token
                    self.energy.charge("decode", (1 + kept_drafts) * ept,
                                       n_out)
                    self.energy.charge("spec_wasted",
                                       (len(d) - kept_drafts) * ept,
                                       len(d) - kept_drafts)
                    self.spec_drafted += len(d)
                    self.spec_accepted += acc
                    req.n_ctx += 1 + kept_drafts
                    if done:
                        self.sched.finish(req, now)
                    else:
                        self.pool.retract(req.rid, req.n_ctx)
                    self.requant_ops_avoided += \
                        req.n_ctx * self._elems_per_token
                else:
                    self.pool.commit(req.rid, req.n_ctx, [fed_tok])
                    self.requant_ops_performed += self._elems_per_token
                    self.requant_ops_forward += self._fwd_elems_per_token
                    self.energy.charge("decode", ept, 1)
                    req.n_ctx += 1
                    self.requant_ops_avoided += \
                        req.n_ctx * self._elems_per_token
                    tok = int(out[i, 0])
                    done = req.finished_by(tok, self.max_model_len)
                    req.generated.append(tok)
                    tr.req_token(req.rid, now)
                    if done:
                        self.sched.finish(req, now)

    def _dispatch_ragged(self, tokens, positions, dest, bt, q_start, q_len,
                         kv_len, temps, topks, sample_start, n_drafts,
                         t_real: int = 0):
        """One unified dispatch + host sync; timed under the work-list
        shape key ``("ragged", T_pad, S_pad)`` so compile-vs-steady is
        attributed to what actually ran (satellite: summarize_step_times
        keyed by dispatched shape).  Emits one ``dispatch`` span per call
        when tracing is on (stream shape, real vs padded tokens,
        compile-vs-steady flag) and — with cost analysis enabled — runs
        the AOT ``cost_analysis`` once per new shape BEFORE the donating
        call consumes the cache buffer."""
        t_start = self._now()
        t0 = time.perf_counter()
        self._step_counter += 1
        topks = np.asarray(topks)
        cap = int(topks.max()) if topks.any() else None
        shape_key = ("ragged", len(tokens), len(temps))
        first_call = shape_key not in self._step_times
        tr = self.tracer
        with tr.phase("engine.transfer", "engine"):
            topks_arg = jnp.asarray(topks) if topks.any() else None
            rb = RaggedBatch(
                dest=jnp.asarray(dest), block_tables=jnp.asarray(bt),
                q_start=jnp.asarray(q_start), q_len=jnp.asarray(q_len),
                kv_len=jnp.asarray(kv_len), tq_max=self._tq_max)
            args = (self.params, jnp.asarray(tokens), self.cache,
                    jnp.asarray(positions), rb, jnp.asarray(temps),
                    topks_arg, jnp.asarray(sample_start),
                    jnp.asarray(n_drafts),
                    jnp.asarray(self._step_counter, jnp.uint32), cap)
        if self.profiler.cost:
            self.profiler.cost_for(shape_key, self._ragged_fn, *args)
        with tr.phase("engine.launch", "engine"):
            out, n_acc, self.cache = self._ragged_fn(*args)
        with tr.phase("engine.sync", "engine"):
            out, n_acc = np.asarray(out), np.asarray(n_acc)
        dt = time.perf_counter() - t0
        self._step_times.setdefault(shape_key, []).append(dt)
        if tr.enabled:
            tr.span("ragged_step", "dispatch", t_start, dt, {
                "shape": f"T{len(tokens)}xS{len(temps)}",
                "real_tokens": t_real,
                "padded_tokens": len(tokens) - t_real,
                "compile": first_call})
        return out, n_acc

    # -- prefill ----------------------------------------------------------

    def _run_prefills(self) -> None:
        # one shared token budget per engine step: admitting a long prompt
        # costs the decode batch at most `budget` tokens of extra latency
        budget = self.sched.prefill_token_budget
        for req in self.sched.prefill_jobs():
            zero_streak = 0
            while budget > 0 and req.state is RequestState.PREFILL:
                fed = self._prefill_chunk(req, budget)
                budget -= fed
                # progress guard: the only legitimate zero-token return is
                # the CoW-failure path, whose preemption side-effect flips
                # req.state and exits this loop.  If the state is STILL
                # PREFILL after two consecutive zero-token iterations,
                # something broke that contract — fail fast instead of
                # spinning the engine forever.
                zero_streak = zero_streak + 1 if fed == 0 else 0
                if zero_streak >= 2:
                    raise RuntimeError(
                        f"prefill of request {req.rid} made no progress "
                        f"twice in a row (state {req.state}, "
                        f"{req.n_prefilled}/{len(req.feed)} fed, budget "
                        f"{budget}) — zero-progress CoW retry without "
                        f"preemption")

    def _prefill_chunk(self, req: Request, budget: int) -> int:
        start = req.n_prefilled
        c_real = min(self.sched.chunk, len(req.feed) - start, budget)
        # copy-on-write (DESIGN §10): any block this chunk writes into
        # must be private.  Only the fully-cached-feed re-feed ever lands
        # in a shared block (partial hits start at a block boundary), but
        # the check is general: preemption retry mirrors decode growth.
        if not self._cow_for_range(req, start, start + c_real):
            return 0                        # req itself was preempted
        c_pad = chunk_bucket(c_real, self.sched.chunk)
        cap = self.max_model_len - start
        if c_pad > cap:
            # near the end of the table the padded tail could land past
            # max_model_len (clamped block-table lookups would then alias
            # LIVE rows of the last block).  Shrink to the largest power
            # of two that fits — still pow2, so at most 2 widths below
            # the bucket floor (1 and 2) join the executable set; at
            # worst the boundary chunk feeds fewer real tokens.
            c_pad = 1 << (cap.bit_length() - 1)
            c_real = min(c_real, c_pad)
        tokens = np.zeros((1, c_pad), np.int32)
        tokens[0, :c_real] = req.feed[start:start + c_real]
        positions = (start + np.arange(c_pad, dtype=np.int32))[None]
        bt = self.pool.table_row(req.rid, self.sched.nbmax)[None]
        toks = self._timed_step(tokens, positions, bt,
                                np.asarray([req.temperature], np.float32),
                                np.asarray([self._req_top_k(req)], np.int32),
                                c_real - 1, name="prefill", n_real=c_real)
        self.dispatched_tokens += c_pad
        self.padded_tokens += c_pad - c_real
        req.n_prefilled += c_real
        req.n_ctx = req.n_prefilled
        # the chunk's KV rows are device-resident now: full blocks this
        # completes become content-addressable (publish is a no-op when
        # the prefix cache is off)
        self.pool.commit(req.rid, start, req.feed[start:start + c_real])
        self.prefill_chunks += 1
        self.requant_ops_performed += c_real * self._elems_per_token
        self.requant_ops_forward += c_real * self._fwd_elems_per_token
        self.energy.charge(
            "prefill",
            c_real * (self._elems_per_token + self._fwd_elems_per_token),
            c_real)
        tr = self.tracer
        if tr.enabled:
            # chunk boundary: part of the scheduler-decision stream the
            # flight recorder diffs between runs (DESIGN §15)
            tr.event("sched.prefill_chunk", "sched", ts=self._now(),
                     args={"rid": req.rid, "start": start,
                           "tokens": c_real})
        tr.req_mark(req.rid, "first_chunk", self._now())
        if req.n_prefilled == len(req.feed):
            # prompt fully resident: the token sampled from the last real
            # row IS the first generated token (for preemption resumes it
            # just continues the sequence)
            tok = int(toks[0])
            now = self._now()
            if req.t_first is None:
                req.t_first = now
            tr.req_mark(req.rid, "first_token", now)
            tr.req_token(req.rid, now)
            done = req.finished_by(tok, self.max_model_len)
            req.generated.append(tok)
            if done:
                self.sched.finish(req, now)
            else:
                req.state = RequestState.DECODE
        return c_real

    # -- decode -----------------------------------------------------------

    def _run_decode(self) -> None:
        now = self._now()
        for req in list(self.sched.decode_reqs()):
            if req.slot is not None and req.state is RequestState.DECODE:
                self.sched.grow_for_decode(req, now)
        reqs = self.sched.decode_reqs()
        if not reqs:
            return
        tokens = np.zeros((self.n_slots, 1), np.int32)
        positions = np.zeros((self.n_slots, 1), np.int32)
        bt = np.full((self.n_slots, self.sched.nbmax), TRASH_BLOCK, np.int32)
        temps = np.zeros((self.n_slots,), np.float32)
        topks = np.zeros((self.n_slots,), np.int32)
        for req in reqs:
            s = req.slot
            tokens[s, 0] = req.generated[-1]
            positions[s, 0] = req.n_ctx
            bt[s] = self.pool.table_row(req.rid, self.sched.nbmax)
            temps[s] = req.temperature
            topks[s] = self._req_top_k(req)
        toks = self._timed_step(tokens, positions, bt, temps, topks, 0,
                                name="decode", n_real=len(reqs))
        self.dispatched_tokens += self.n_slots
        self.padded_tokens += self.n_slots - len(reqs)
        self.decode_steps += 1
        self.requant_ops_performed += len(reqs) * self._elems_per_token
        self.requant_ops_forward += len(reqs) * self._fwd_elems_per_token
        self.energy.charge(
            "decode",
            len(reqs) * (self._elems_per_token + self._fwd_elems_per_token),
            len(reqs))
        now = self._now()
        tr = self.tracer
        for req in reqs:
            # the fed token's KV row is resident: blocks that fill during
            # decode publish too, so a preempted resume (or a later request
            # sharing prompt+generation) can re-attach them
            self.pool.commit(req.rid, req.n_ctx, [req.generated[-1]])
            req.n_ctx += 1
            # the dataflow the int8-resident pool deletes: dequantizing the
            # slot's whole live cache before attending, EVERY step
            self.requant_ops_avoided += req.n_ctx * self._elems_per_token
            tok = int(toks[req.slot])
            done = req.finished_by(tok, self.max_model_len)
            req.generated.append(tok)
            tr.req_token(req.rid, now)
            if done:
                self.sched.finish(req, now)

    # -- speculative decode (DESIGN §11) ---------------------------------

    def _spec_budget(self, req: Request) -> int:
        """How many tokens are worth drafting for ``req`` this step: each
        verify step emits at least one token, so drafting past the
        request's remaining generation (or the model length) only burns
        quantization ops on rows that can never be kept."""
        return max(0, min(self.spec_k,
                          req.max_new_tokens - req.n_generated - 1,
                          self.max_model_len - 1 - req.n_ctx))

    def _run_spec_decode(self) -> bool:
        """One speculative verify step at (n_slots, K+1): draft, grow the
        pool for the speculative tail (degrading the tail under pressure
        before preempting peers), COW any shared block the tail would
        land in, verify all slots in one fused dispatch, then COMMIT only
        accepted tokens and RETRACT the rejected tail's blocks.  Returns
        False when no slot produced a draft — the caller then runs the
        plain (B, 1) decode step instead of paying for a K+1-wide one."""
        now = self._now()
        proposals = {}
        for req in self.sched.decode_reqs():
            budget = self._spec_budget(req)
            if budget > 0:
                d = np.asarray(self.drafter.draft(
                    np.concatenate([req.prompt, np.asarray(
                        req.generated, np.int32)]), budget), np.int32)
                proposals[req.rid] = d[:budget]
        if not any(len(d) for d in proposals.values()):
            return False

        plans: dict[int, np.ndarray] = {}
        for req in list(self.sched.decode_reqs()):
            if req.slot is None or req.state is not RequestState.DECODE:
                continue
            drafts = proposals.get(req.rid, np.empty(0, np.int32))
            granted = self.sched.grow_for_spec(req, now, len(drafts))
            if granted is None:
                continue                    # req itself was preempted
            drafts = drafts[:granted]
            # the speculative tail must only write private blocks: COW
            # any shared/published block overlapping [n_ctx, n_ctx + k]
            if not self._cow_for_range(req, req.n_ctx,
                                       req.n_ctx + 1 + len(drafts)):
                continue                    # req itself was preempted
            plans[req.rid] = drafts
        # growth/COW for a later slot may have preempted an earlier one —
        # only requests still resident in a slot join the verify batch
        reqs = [r for r in self.sched.decode_reqs() if r.rid in plans]
        if not reqs:
            return bool(plans)

        kp1 = self.spec_k + 1
        bs = self.pool.block_size
        # one guaranteed-TRASH table column past nbmax: padded draft
        # positions point there, so their scatter lands in the trash
        # block even for a full-length sequence (a clamped lookup would
        # alias its last LIVE block)
        width = self.sched.nbmax + 1
        pad_pos = self.sched.nbmax * bs
        tokens = np.zeros((self.n_slots, kp1), np.int32)
        positions = np.full((self.n_slots, kp1), pad_pos, np.int32)
        bt = np.full((self.n_slots, width), TRASH_BLOCK, np.int32)
        temps = np.zeros((self.n_slots,), np.float32)
        topks = np.zeros((self.n_slots,), np.int32)
        n_drafts = np.zeros((self.n_slots,), np.int32)
        for req in reqs:
            s, d = req.slot, plans[req.rid]
            tokens[s, 0] = req.generated[-1]
            tokens[s, 1:1 + len(d)] = d
            positions[s, :1 + len(d)] = req.n_ctx + np.arange(
                1 + len(d), dtype=np.int32)
            bt[s, :self.sched.nbmax] = self.pool.table_row(
                req.rid, self.sched.nbmax)
            temps[s] = req.temperature
            topks[s] = self._req_top_k(req)
            n_drafts[s] = len(d)
        n_real = sum(1 + len(plans[r.rid]) for r in reqs)
        out, n_acc = self._timed_spec_step(tokens, positions, bt, temps,
                                           topks, n_drafts, n_real=n_real)
        self.dispatched_tokens += self.n_slots * kp1
        self.padded_tokens += self.n_slots * kp1 - n_real
        self.spec_steps += 1
        self.spec_slot_steps += len(reqs)
        now = self._now()
        tr = self.tracer
        for req in reqs:
            d = plans[req.rid]
            acc = int(n_acc[req.slot])
            emitted = out[req.slot, :acc + 1].tolist()
            kept_drafts = 0
            n_out = 0
            done = False
            for i, tok in enumerate(emitted):
                done = req.finished_by(int(tok), self.max_model_len)
                req.generated.append(int(tok))
                tr.req_token(req.rid, now)
                self.spec_emitted += 1
                n_out += 1
                if i < acc:
                    kept_drafts += 1    # this draft's KV row is resident
                if done:
                    break
            # publish ONLY accepted tokens (the fed token + the kept
            # draft prefix); the rejected tail's rows never reach the
            # prefix cache, and retract frees any block they alone held
            self.pool.commit(req.rid, req.n_ctx,
                             [int(tokens[req.slot, 0])]
                             + d[:kept_drafts].tolist())
            self.requant_ops_performed += \
                (1 + len(d)) * self._elems_per_token
            self.requant_ops_wasted_spec += \
                (len(d) - kept_drafts) * self._elems_per_token
            self.requant_ops_forward += \
                (1 + len(d)) * self._fwd_elems_per_token
            self.requant_ops_forward_wasted_spec += \
                (len(d) - kept_drafts) * self._fwd_elems_per_token
            ept = self._elems_per_token + self._fwd_elems_per_token
            self.energy.charge("decode", (1 + kept_drafts) * ept, n_out)
            self.energy.charge("spec_wasted", (len(d) - kept_drafts) * ept,
                               len(d) - kept_drafts)
            self.spec_drafted += len(d)
            self.spec_accepted += acc
            req.n_ctx += 1 + kept_drafts
            if done:
                self.sched.finish(req, now)
            else:
                self.pool.retract(req.rid, req.n_ctx)
            # the counterfactual a dequantize-per-step dataflow pays: the
            # slot's whole live cache re-requantized once per VERIFY step
            # (speculation amortizes it over up to K+1 emitted tokens)
            self.requant_ops_avoided += req.n_ctx * self._elems_per_token
        return True

    # -- fixed-slab recurrent step (DESIGN §16) ---------------------------

    def _snapshot_slab(self, req: Request) -> dict:
        """Scheduler preemption hook (pure-recurrent substrate): copy the
        sequence's whole state slab to the host.  O(state) bytes instead
        of the attention substrate's recompute-the-prefix, because the
        slab IS the entire sequence state.  Codes are copied as codes
        (int8 mode) or raw fp32, so the resume is bit-exact."""
        slab = self.state_pool.slab_of(req.rid)
        state = {k: np.asarray(v[:, slab])
                 for k, v in self.cache["state"].items()}
        return {"n_ctx": req.n_ctx, "state": state}

    def _restore_snapshot(self, req: Request) -> None:
        """Drop a preemption snapshot back into the freshly allocated
        slab.  Admission already resumed the token bookkeeping from
        ``snapshot['n_ctx']``; the slab's scale exponent is the engine's
        fixed per-run grid, so the codes reinterpret identically."""
        slab = self.state_pool.slab_of(req.rid)
        st = self.cache["state"]
        for k, v in req.snapshot["state"].items():
            st[k] = st[k].at[:, slab].set(jnp.asarray(v))
        req.snapshot = None

    def _reset_slab(self, req: Request) -> None:
        """Zero a freshly allocated slab.  Slabs recycle LIFO off the free
        stack still holding their previous owner's FINAL state — a new
        sequence must integrate from zero (the stale-state bug shows up
        as token divergence only several tokens in, after the decay has
        had time to amplify the inherited state's contribution)."""
        slab = self.state_pool.slab_of(req.rid)
        if "state" in self.cache:               # pure recurrent
            st = self.cache["state"]
            for k, v in st.items():
                st[k] = v.at[:, slab].set(0)
        else:                                   # hybrid Mamba slabs
            self.cache["ssm"] = jax.tree.map(
                lambda a: a.at[:, :, slab].set(0), self.cache["ssm"])

    def _charge_recurrent(self, phase: str, n_tok: int,
                          int8_state: bool) -> None:
        """Table-5 accounting for one sequence's share of a recurrent
        step: ``n_tok`` per-token KV appends (the hybrid's shared
        attention blocks; zero on pure recurrent) plus ONE whole-slab
        state requant — context-free, the §16 headline.  int8 slabs
        PERFORM the state ops; fp32 slabs book the identical count as
        the dequantize-per-step counterfactual ``avoided``, so the
        ops/token gauge compares across storage modes."""
        kv = n_tok * self._elems_per_token
        st = self._state_elems_per_step
        fwd = n_tok * self._fwd_elems_per_token
        self.requant_ops_state += st
        if int8_state:
            self.requant_ops_performed += kv + st
            self.energy.charge(phase, kv + st + fwd, n_tok)
        else:
            self.requant_ops_performed += kv
            self.requant_ops_avoided += st
            self.energy.charge(phase, kv + fwd, n_tok)
        self.requant_ops_forward += fwd

    def _run_recurrent_step(self) -> None:
        """Serve the whole mixed work-list in ONE fixed-shape dispatch
        (n_slots, chunk): prefill rows feed their next chunk (q_len = c),
        decode rows feed their last sampled token (q_len = 1), idle lanes
        ride along inert (q_len = 0 against the trash slab).  There is no
        ragged flattening and no per-shape phase trio — the recurrent
        batch is already shape-stable, so jit sees exactly one
        executable.  On the hybrid substrate the same dispatch carries
        per-row positions and block tables: Mamba layers consume the
        slabs while the shared attention blocks scatter/gather the paged
        KV pool, in the same jitted step."""
        sub = self.substrate
        tr = self.tracer
        with tr.phase("engine.plan", "engine"):
            now = self._now()
            if sub.grows:
                # hybrid KV half: decode rows append one KV row per step, so
                # block tables may need to grow — growth can preempt a peer,
                # exactly like the attention decode path
                for req in list(self.sched.decode_reqs()):
                    if req.slot is not None \
                            and req.state is RequestState.DECODE:
                        self.sched.grow_for_decode(req, now)
            prefills = []
            for req in self.sched.prefill_jobs():
                start = req.n_prefilled
                c_real = min(self.sched.chunk, len(req.feed) - start)
                prefills.append((req, start, c_real))
            decodes = self.sched.decode_reqs()
            if not prefills and not decodes:
                return
        with tr.phase("engine.pack", "engine"):
            b, c = self.n_slots, self.sched.chunk
            tokens = np.zeros((b, c), np.int32)
            q_len = np.zeros((b,), np.int32)
            slab_ids = np.full((b,), TRASH_SLAB, np.int32)   # idle lanes
            temps = np.zeros((b,), np.float32)
            topks = np.zeros((b,), np.int32)
            if sub.grows:
                # one guaranteed-TRASH table column past nbmax: idle/padded
                # positions point there, so their KV scatter lands in the
                # trash block even for a full-length sequence
                width = self.sched.nbmax + 1
                pad_pos = self.sched.nbmax * self.pool.block_size
                positions = np.full((b, c), pad_pos, np.int32)
                bt = np.full((b, width), TRASH_BLOCK, np.int32)
            else:
                positions = bt = None
            for req, start, c_real in prefills:
                s = req.slot
                tokens[s, :c_real] = req.feed[start:start + c_real]
                q_len[s] = c_real
                slab_ids[s] = self.state_pool.slab_of(req.rid)
                temps[s] = req.temperature
                topks[s] = self._req_top_k(req)
                if sub.grows:
                    positions[s, :c_real] = start + np.arange(c_real,
                                                              dtype=np.int32)
                    bt[s, :self.sched.nbmax] = self.pool.table_row(
                        req.rid, self.sched.nbmax)
            for req in decodes:
                s = req.slot
                tokens[s, 0] = req.generated[-1]
                q_len[s] = 1
                slab_ids[s] = self.state_pool.slab_of(req.rid)
                temps[s] = req.temperature
                topks[s] = self._req_top_k(req)
                if sub.grows:
                    positions[s, 0] = req.n_ctx
                    bt[s, :self.sched.nbmax] = self.pool.table_row(
                        req.rid, self.sched.nbmax)
        n_real = int(q_len.sum())
        toks = self._dispatch_recurrent(tokens, slab_ids, q_len,
                                        positions, bt, temps, topks,
                                        n_real)
        with tr.phase("engine.commit", "engine"):
            self.recurrent_steps += 1
            self.dispatched_tokens += b * c
            self.padded_tokens += b * c - n_real
            int8_state = self.cfg.state_bits == 8
            now = self._now()

            # -- post-process: prefill rows (mirrors _prefill_chunk) ----------
            for req, start, c_real in prefills:
                req.n_prefilled += c_real
                req.n_ctx = req.n_prefilled
                if sub.grows:
                    self.pool.commit(req.rid, start,
                                     req.feed[start:start + c_real])
                self.prefill_chunks += 1
                self._charge_recurrent("prefill", c_real, int8_state)
                if tr.enabled:
                    # chunk boundary: part of the scheduler-decision stream
                    # the flight recorder diffs between runs (DESIGN §15)
                    tr.event("sched.prefill_chunk", "sched", ts=now, args={
                        "rid": req.rid, "start": start, "tokens": c_real})
                tr.req_mark(req.rid, "first_chunk", now)
                if req.n_prefilled == len(req.feed):
                    tok = int(toks[req.slot])
                    if req.t_first is None:
                        req.t_first = now
                    tr.req_mark(req.rid, "first_token", now)
                    tr.req_token(req.rid, now)
                    done = req.finished_by(tok, self.max_model_len)
                    req.generated.append(tok)
                    if done:
                        self.sched.finish(req, now)
                    else:
                        req.state = RequestState.DECODE

            # -- post-process: decode rows (mirrors _run_decode) --------------
            for req in decodes:
                if sub.grows:
                    self.pool.commit(req.rid, req.n_ctx, [req.generated[-1]])
                req.n_ctx += 1
                if sub.grows:
                    # the hybrid's KV half still avoids the dequantize-the-
                    # whole-cache-per-step counterfactual, same as attention
                    self.requant_ops_avoided += \
                        req.n_ctx * self._elems_per_token
                self._charge_recurrent("decode", 1, int8_state)
                tok = int(toks[req.slot])
                done = req.finished_by(tok, self.max_model_len)
                req.generated.append(tok)
                tr.req_token(req.rid, now)
                if done:
                    self.sched.finish(req, now)

    def _dispatch_recurrent(self, tokens, slab_ids, q_len, positions, bt,
                            temps, topks, n_real):
        """Recurrent counterpart of ``_dispatch``: same step counter,
        top-k fast path, timing and host sync, but the descriptor set is
        (slab_ids, q_len) plus the hybrid's (positions, block tables) —
        ``None`` on the pure-recurrent substrate, where jit simply sees
        an empty pytree leaf."""
        t_start = self._now()
        t0 = time.perf_counter()
        self._step_counter += 1
        topks = np.asarray(topks)
        cap = int(topks.max()) if topks.any() else None
        shape_key = ("recurrent",) + tuple(tokens.shape)
        first_call = shape_key not in self._step_times
        tr = self.tracer
        with tr.phase("engine.transfer", "engine"):
            topks_arg = jnp.asarray(topks) if topks.any() else None
            args = (self.params, jnp.asarray(tokens), self.cache,
                    jnp.asarray(slab_ids), jnp.asarray(q_len),
                    None if positions is None else jnp.asarray(positions),
                    None if bt is None else jnp.asarray(bt),
                    jnp.asarray(temps), topks_arg,
                    jnp.asarray(self._step_counter, jnp.uint32), cap)
        if self.profiler.cost:
            self.profiler.cost_for(shape_key, self._rec_fn, *args)
        with tr.phase("engine.launch", "engine"):
            toks, self.cache = self._rec_fn(*args)
        with tr.phase("engine.sync", "engine"):
            toks = np.asarray(toks)
        dt = time.perf_counter() - t0
        self._step_times.setdefault(shape_key, []).append(dt)
        if tr.enabled:
            n_disp = int(np.prod(tokens.shape))
            tr.span("recurrent", "dispatch", t_start, dt, {
                "shape": "x".join(map(str, tokens.shape)),
                "real_tokens": n_real,
                "padded_tokens": n_disp - n_real,
                "compile": first_call})
        return toks

    # -- shared step plumbing --------------------------------------------

    def _cow_for_range(self, req: Request, start: int, end: int) -> bool:
        """Copy-on-write every SHARED block overlapping feed positions
        [start, end) so the chunk's KV scatter only touches private
        blocks.  The pool moves the map; the device rows are duplicated
        here (one jitted block copy, donated — block_size rows per layer,
        never the whole arena).  Returns False iff ``req`` itself was
        preempted while finding a block for the copy."""
        if self.substrate.fixed_state:
            raise BlockPoolError(
                f"copy-on-write on the {self.substrate.kind} substrate: "
                f"sequence {req.rid} keeps fixed-size recurrent state and "
                f"never shares a block (no prefix cache to COW from)")
        bs = self.pool.block_size
        for idx in range(start // bs, -(-end // bs)):
            if idx >= self.pool.n_blocks_of(req.rid):
                break                       # rows beyond the table: extend
            if self.pool.block_writable(req.rid, idx):
                continue
            pair = self.sched.cow_for_prefill(req, idx, self._now())
            if pair is None:
                return False
            src, dst = pair
            self.cache = self._cow_fn(self.cache, jnp.asarray(src),
                                      jnp.asarray(dst))
        return True

    def _req_top_k(self, req: Request) -> int:
        return req.top_k if req.top_k > 0 else self.default_top_k

    def _dispatch(self, step_fn, tokens, positions, bt, temps, topks,
                  mode_arg, name: str = "step",
                  n_real: Optional[int] = None):
        """Shared plumbing for the jitted decode/prefill and verify
        steps: step counter, the top-k fast path, timing, host sync.

        all-zero top-k (the greedy/full-vocab default) drops to the
        sampler's None fast path: no top-k cutoff ever enters the hot
        executable.  Otherwise the batch's max top-k rides along as the
        STATIC k_cap (an O(V log k) lax.top_k, one extra jit variant per
        distinct cap — bounded by the workload's top-k settings).
        ``mode_arg`` is the per-step int payload: the last real row index
        for sampled steps, the per-slot draft counts for verify steps.
        ``name``/``n_real`` feed the trace span (dispatch kind + padded
        vs real token count) when tracing is on.
        """
        t_start = self._now()
        t0 = time.perf_counter()
        self._step_counter += 1
        topks = np.asarray(topks)
        cap = int(topks.max()) if topks.any() else None
        shape_key = tuple(tokens.shape)
        first_call = shape_key not in self._step_times
        tr = self.tracer
        with tr.phase("engine.transfer", "engine"):
            topks_arg = jnp.asarray(topks) if topks.any() else None
            args = (self.params, jnp.asarray(tokens), self.cache,
                    jnp.asarray(positions), jnp.asarray(bt),
                    jnp.asarray(temps), topks_arg,
                    jnp.asarray(mode_arg, jnp.int32),
                    jnp.asarray(self._step_counter, jnp.uint32), cap)
        if self.profiler.cost:
            self.profiler.cost_for(shape_key, step_fn, *args)
        with tr.phase("engine.launch", "engine"):
            *out, self.cache = step_fn(*args)
        with tr.phase("engine.sync", "engine"):
            out = [np.asarray(o) for o in out]
        dt = time.perf_counter() - t0
        self._step_times.setdefault(shape_key, []).append(dt)
        if tr.enabled:
            n_disp = int(np.prod(tokens.shape))
            tr.span(name, "dispatch", t_start, dt, {
                "shape": "x".join(map(str, tokens.shape)),
                "real_tokens": n_disp if n_real is None else n_real,
                "padded_tokens": 0 if n_real is None else n_disp - n_real,
                "compile": first_call})
        return out

    def _timed_step(self, tokens, positions, bt, temps, topks, last_idx,
                    name: str = "step", n_real: Optional[int] = None):
        toks, = self._dispatch(self._step_fn, tokens, positions, bt,
                               temps, topks, last_idx, name=name,
                               n_real=n_real)
        return toks

    def _timed_spec_step(self, tokens, positions, bt, temps, topks,
                         n_drafts, n_real: Optional[int] = None):
        out, n_acc = self._dispatch(self._spec_fn, tokens, positions, bt,
                                    temps, topks, n_drafts,
                                    name="spec_verify", n_real=n_real)
        return out, n_acc

    # -- report -----------------------------------------------------------

    def outputs(self) -> dict[int, np.ndarray]:
        return {r.rid: np.asarray(r.generated, np.int32)
                for r in self.sched.done}

    def _wall(self) -> float:
        return self._wall_s or self._now()

    def _latency_samples(self) -> dict[str, list]:
        """Legacy latency sample lists from the finished requests'
        timestamps (the pre-§14 source; the trace timelines must
        reproduce these exactly — cross-checked in tests/test_obs.py)."""
        done = self.sched.done
        return {
            "ttft": [r.t_first - r.arrival for r in done
                     if r.t_first is not None],
            "e2e": [r.t_done - r.arrival for r in done
                    if r.t_done is not None],
            "tpot": [(r.t_done - r.t_first) / (r.n_generated - 1)
                     for r in done if r.n_generated > 1],
        }

    def _register_metrics(self) -> None:
        """Declare every report field on the metrics registry, in report
        order (DESIGN §14).  All metrics are BOUND (FuncMetric): the
        engine's plain counters, ``PoolStats``/``CacheStats`` and the
        request lists stay the single source of truth (the property
        tests drive them directly); the registry owns naming, typing,
        help text and exposition.  ``engine.report()`` is
        ``metrics.nested()`` — a renamed or undocumented field now fails
        the golden-schema test instead of silently breaking a downstream
        bench gate."""
        m = self.metrics
        sched, pool = self.sched, self.pool
        f = m.func

        def n_requests():
            return len(sched.done) + len(sched.waiting) \
                + len(sched.active())

        def tokens_per_s():
            wall = self._wall()
            gen = sum(r.n_generated for r in sched.done)
            return round(gen / wall, 2) if wall else None

        f("n_requests", "requests seen (done + waiting + active)",
          n_requests, kind="counter", typ=int)
        f("completed", "requests served to completion",
          lambda: len(sched.done), kind="counter", typ=int)
        f("preemptions", "recompute preemptions among completed requests",
          lambda: sum(r.preemptions for r in sched.done),
          kind="counter", typ=int)
        f("gen_tokens", "tokens generated across completed requests",
          lambda: sum(r.n_generated for r in sched.done),
          kind="counter", typ=int)
        f("prompt_tokens", "prompt tokens across completed requests",
          lambda: sum(len(r.prompt) for r in sched.done),
          kind="counter", typ=int)
        f("wall_s", "run wall-clock on the engine clock (fast-forwarded "
          "arrival gaps excluded from real time)",
          lambda: round(self._wall(), 4), unit="s", typ=float)
        f("tokens_per_s", "generated-token throughput over wall_s",
          tokens_per_s, typ=float, optional=True)
        f("decode_steps", "plain (non-speculative) decode dispatches",
          lambda: self.decode_steps, kind="counter", typ=int)
        f("spec_steps", "speculative verify dispatches",
          lambda: self.spec_steps, kind="counter", typ=int)
        f("prefill_chunks", "chunked-prefill pieces dispatched",
          lambda: self.prefill_chunks, kind="counter", typ=int)
        f("ragged", "unified ragged dispatch path enabled (DESIGN §12)",
          lambda: self.ragged, typ=bool)
        f("ragged_steps", "unified ragged dispatches",
          lambda: self.ragged_steps, kind="counter", typ=int)
        f("substrate", "sequence-state substrate serving this model — "
          "attention block tables, recurrent state slabs, or the hybrid "
          "of both (DESIGN §16)",
          lambda: self.substrate.kind, typ=str)
        f("recurrent_steps", "fixed-shape recurrent dispatches "
          "(DESIGN §16)",
          lambda: self.recurrent_steps, kind="counter", typ=int)
        # padding honesty: tokens dispatched vs tokens that carried real
        # work — pow2 bucket rounding, empty decode slots, unused draft
        # columns — invisible in the Table-5 accounting before PR 6
        f("dispatched_tokens", "token rows dispatched incl. padding",
          lambda: self.dispatched_tokens, kind="counter", typ=int)
        f("padded_tokens", "dispatched token rows that carried no work",
          lambda: self.padded_tokens, kind="counter", typ=int)
        f("padding_frac", "padded_tokens / dispatched_tokens",
          lambda: round(self.padded_tokens / self.dispatched_tokens, 4)
          if self.dispatched_tokens else None, typ=float, optional=True)
        f("attn_grid_steps", "grid steps one layer's ragged attention call "
          "issues, live or dead, summed over ragged dispatches",
          lambda: self.attn_grid_steps, kind="counter", typ=int)
        f("attn_kv_blocks_copied", "pool blocks one layer's ragged "
          "attention call copies (the live blocks of each slot, per head "
          "step), summed over ragged dispatches",
          lambda: self.attn_kv_blocks_copied, kind="counter", typ=int)
        if self.spec_k:
            self._register_spec_metrics()
        for name, q in (("ttft_s", "time to first token"),
                        ("tpot_s", "per-output-token time"),
                        ("e2e_s", "request end-to-end latency")):
            key = name.split("_")[0] if name != "e2e_s" else "e2e"
            for p in (50, 99):
                f(f"{name}.p{p}", f"{q} p{p} (legacy request-timestamp "
                  f"source), seconds",
                  (lambda key=key, p=p:
                   _pct(self._latency_samples()[key], p)),
                  unit="s", typ=float, optional=True)
        f("step_shapes", "per-dispatched-shape compile-vs-steady step-time"
          " summary (dynamic keys: one per jitted shape)",
          lambda: summarize_step_times(self._step_times), typ=dict)
        if pool is not None:
            self._register_pool_metrics()
        if self.state_pool is not None:
            self._register_state_pool_metrics()
        if pool is not None and pool.cache is not None:
            self._register_cache_metrics()
        self._register_hwcost_metrics()
        self._register_energy_metrics()
        self._register_timeline_metrics()
        f("obs.trace_enabled", "ring-event tracing active",
          lambda: self.tracer.enabled, typ=bool)
        f("obs.trace_events", "events currently held in the trace ring",
          lambda: len(self.tracer.events), typ=int)
        f("obs.trace_emitted", "events emitted since start/reset",
          lambda: self.tracer.n_emitted, kind="counter", typ=int)
        f("obs.trace_dropped", "events evicted from the bounded ring",
          lambda: self.tracer.dropped, kind="counter", typ=int)
        f("obs.trace_capacity", "trace ring capacity (hard bound)",
          lambda: self.tracer.capacity, typ=int)
        # silent-span-loss visibility (DESIGN §15): the prometheus-
        # conventional _total alias of the drop counter plus the ring
        # occupancy fraction — a scrape can alert on drops BEFORE a
        # truncated trace surprises someone in Perfetto
        f("obs.trace_dropped_total",
          "events evicted from the bounded ring (prometheus-"
          "conventional view of obs.trace_dropped)",
          lambda: self.tracer.dropped, kind="counter", typ=int,
          alias_of="obs.trace_dropped")
        f("obs.trace_ring_used",
          "trace ring occupancy fraction (held / capacity); 1.0 means "
          "the next event evicts the oldest",
          lambda: round(len(self.tracer.events) / self.tracer.capacity,
                        6), typ=float)
        if self.slo is not None:
            self._register_slo_metrics()
        if self.profiler.enabled:
            f("profile", "jax-profiler/cost-analysis attribution "
              "(dynamic keys; present only when profiling is on)",
              lambda: self.profiler.report(), typ=dict, optional=True)
        m.check_aliases()

    def _register_slo_metrics(self) -> None:
        f = self.metrics.func
        f("slo.objectives", "number of configured SLO objectives",
          lambda: len(self.slo.objectives), typ=int)
        f("slo.evaluations", "monitoring ticks since start/reset "
          "(one per engine step)",
          lambda: self.slo.evaluations, kind="counter", typ=int)
        f("slo.alerts_fired", "burn-rate alert firings since "
          "start/reset",
          lambda: self.slo.alerts_fired, kind="counter", typ=int)
        f("slo.alerts_active", "objectives currently in alert",
          lambda: self.slo.alerts_active, typ=int)
        f("slo.worst_burn_rate", "max burn rate across objectives at "
          "the last evaluation (1.0 = violations exactly exhaust the "
          "error budget)",
          lambda: self.slo.worst_burn_rate(), typ=float, optional=True)
        f("slo.status", "per-objective window/burn/firing state "
          "(dynamic keys: one per objective)",
          lambda: self.slo.status(), typ=dict)

    def _register_spec_metrics(self) -> None:
        f = self.metrics.func
        f("speculative.spec_k", "max draft tokens per verify step",
          lambda: self.spec_k, typ=int)
        f("speculative.drafter", "drafter implementation",
          lambda: type(self.drafter).__name__, typ=str)
        f("speculative.verify_steps", "speculative verify dispatches",
          lambda: self.spec_steps, kind="counter", typ=int)
        f("speculative.fallback_decode_steps",
          "plain decode dispatches (no slot produced a draft)",
          lambda: self.decode_steps, kind="counter", typ=int)
        f("speculative.drafted_tokens", "tokens proposed by the drafter",
          lambda: self.spec_drafted, kind="counter", typ=int)
        f("speculative.accepted_tokens", "drafted tokens that verified",
          lambda: self.spec_accepted, kind="counter", typ=int)
        f("speculative.acceptance_rate", "accepted / drafted",
          lambda: round(self.spec_accepted / self.spec_drafted, 4)
          if self.spec_drafted else None, typ=float, optional=True)
        f("speculative.emitted_tokens",
          "tokens emitted by verify steps (accepted + correction/bonus)",
          lambda: self.spec_emitted, kind="counter", typ=int)
        # emitted per (slot, verify step) pair — the amortization
        # speculation buys a sequence (1.0 == plain decode; K+1 == every
        # draft accepted).  Normalized per SLOT so batching can't
        # inflate it past K+1.
        f("speculative.tokens_per_step",
          "emitted tokens per (slot, verify step) pair",
          lambda: round(self.spec_emitted / self.spec_slot_steps, 4)
          if self.spec_slot_steps else None, typ=float, optional=True)
        f("speculative.retracts", "speculative rollbacks that freed "
          "blocks (view of pool.retracts — single source of truth)",
          lambda: self.pool.stats.retracts, kind="counter", typ=int,
          alias_of="pool.retracts")
        f("speculative.retracted_blocks", "blocks freed by rollback "
          "(view of pool.retracted_blocks)",
          lambda: self.pool.stats.retracted_blocks, kind="counter",
          typ=int, alias_of="pool.retracted_blocks")
        f("speculative.requant_ops_wasted",
          "KV quant ops spent on rejected drafts (performed, rolled back)",
          lambda: self.requant_ops_wasted_spec, kind="counter", typ=int)
        f("speculative.drafter_calls", "draft() invocations",
          lambda: getattr(self.drafter, "stats").calls
          if hasattr(self.drafter, "stats") else 0,
          kind="counter", typ=int)
        f("speculative.drafter_proposed", "tokens the drafter proposed "
          "(before the engine's per-request budget truncation)",
          lambda: getattr(self.drafter, "stats").proposed
          if hasattr(self.drafter, "stats") else 0,
          kind="counter", typ=int)
        f("speculative.drafter_empty", "draft() calls that proposed "
          "nothing (request decodes at the plain per-token rate)",
          lambda: getattr(self.drafter, "stats").empty
          if hasattr(self.drafter, "stats") else 0,
          kind="counter", typ=int)

    def _register_pool_metrics(self) -> None:
        f, pool = self.metrics.func, self.pool
        f("pool.num_blocks", "pool capacity in blocks (incl. trash)",
          lambda: pool.num_blocks, typ=int)
        f("pool.block_size", "tokens per KV block",
          lambda: pool.block_size, typ=int)
        f("pool.peak_live_blocks", "max simultaneously-live blocks",
          lambda: pool.stats.peak_live, typ=int)
        f("pool.peak_utilization", "peak_live / allocatable blocks",
          lambda: round(pool.stats.peak_live
                        / max(pool.num_blocks - 1, 1), 3), typ=float)
        f("pool.utilization", "live blocks / allocatable blocks now",
          lambda: round(pool.utilization, 3), typ=float)
        f("pool.residency", "(live + cached) / allocatable blocks now",
          lambda: round(pool.residency, 3), typ=float)
        f("pool.allocs", "blocks handed out fresh (not cache hits)",
          lambda: pool.stats.allocs, kind="counter", typ=int)
        f("pool.frees", "block references released",
          lambda: pool.stats.frees, kind="counter", typ=int)
        f("pool.evictions", "blocks released by preemption",
          lambda: pool.stats.evictions, kind="counter", typ=int)
        f("pool.seq_evictions", "sequences preempted",
          lambda: pool.stats.seq_evictions, kind="counter", typ=int)
        f("pool.cache_evictions", "idle cached blocks reclaimed (LRU)",
          lambda: pool.stats.cache_evictions, kind="counter", typ=int)
        f("pool.retracts", "speculative rollbacks that freed blocks "
          "(canonical; speculative.retracts is a view of this)",
          lambda: pool.stats.retracts, kind="counter", typ=int)
        f("pool.retracted_blocks", "blocks freed by rollback (canonical)",
          lambda: pool.stats.retracted_blocks, kind="counter", typ=int)
        f("pool.alloc_failures", "alloc/extend requests refused",
          lambda: pool.stats.alloc_failures, kind="counter", typ=int)

    def _register_state_pool_metrics(self) -> None:
        """Fixed-slab substrate accounting (DESIGN §16) — the recurrent
        counterpart of the ``pool.*`` section."""
        f, sp = self.metrics.func, self.state_pool
        f("state_pool.num_slabs", "slab capacity (incl. trash slab 0)",
          lambda: sp.num_slabs, typ=int)
        f("state_pool.scale_exp", "fixed Eq.-1 scale exponent slabs are "
          "allocated with (0 when slabs store fp32 state)",
          lambda: sp.default_scale_exp, typ=int)
        f("state_pool.state_quant_ops_per_step", "ops to requantize one "
          "sequence's WHOLE state once — paid per step, context-free",
          lambda: self._state_elems_per_step, typ=int)
        f("state_pool.requant_ops_state", "whole-slab state requant ops "
          "booked so far (performed when slabs are int8, counterfactual "
          "otherwise) — the recurrent share of hwcost totals",
          lambda: self.requant_ops_state, kind="counter", typ=int)

        def state_ops_per_token():
            tok = self.energy.tokens["prefill"] + self.energy.tokens[
                "decode"]
            return round(self.requant_ops_state / tok, 2) if tok else None

        f("state_pool.state_ops_per_token", "recurrent-substrate share "
          "of hwcost.requant_ops_per_token — context-free by "
          "construction, the number the §16 bench gate compares against "
          "the attention baseline",
          state_ops_per_token, typ=float, optional=True)
        f("state_pool.peak_live_slabs", "max simultaneously-live slabs",
          lambda: sp.stats.peak_live, typ=int)
        f("state_pool.utilization", "live slabs / allocatable slabs now",
          lambda: round(sp.utilization, 3), typ=float)
        f("state_pool.allocs", "slabs handed out",
          lambda: sp.stats.allocs, kind="counter", typ=int)
        f("state_pool.frees", "slab references released",
          lambda: sp.stats.frees, kind="counter", typ=int)
        f("state_pool.seq_evictions", "sequences preempted off slabs",
          lambda: sp.stats.seq_evictions, kind="counter", typ=int)
        f("state_pool.alloc_failures", "slab allocations refused",
          lambda: sp.stats.alloc_failures, kind="counter", typ=int)

    def _register_cache_metrics(self) -> None:
        f, pool = self.metrics.func, self.pool
        f("prefix_cache.hits", "full-block lookups served from cache",
          lambda: pool.cache.stats.hits, kind="counter", typ=int)
        f("prefix_cache.misses", "full-block lookups that missed",
          lambda: pool.cache.stats.misses, kind="counter", typ=int)
        f("prefix_cache.hit_rate", "hits / (hits + misses)",
          lambda: round(pool.cache.stats.hit_rate, 4), typ=float)
        f("prefix_cache.hit_tokens", "tokens covered by block hits",
          lambda: pool.cache.stats.hit_tokens, kind="counter", typ=int)
        f("prefix_cache.lookup_tokens", "tokens covered by lookups",
          lambda: pool.cache.stats.lookup_tokens, kind="counter", typ=int)
        f("prefix_cache.token_hit_rate", "hit_tokens / lookup_tokens",
          lambda: round(pool.cache.stats.token_hit_rate, 4), typ=float)
        f("prefix_cache.cached_prefill_tokens",
          "prefill tokens served from resident KV (never re-quantized)",
          lambda: self.cache_hit_prefill_tokens, kind="counter", typ=int)
        f("prefix_cache.cow_copies", "shared blocks copied before a write",
          lambda: pool.cache.stats.cow_copies, kind="counter", typ=int)
        f("prefix_cache.published_blocks",
          "blocks registered under a content key",
          lambda: pool.cache.stats.published, kind="counter", typ=int)
        f("prefix_cache.cache_evictions",
          "idle cached blocks reclaimed (LRU)",
          lambda: pool.cache.stats.evictions, kind="counter", typ=int)
        f("prefix_cache.resident_cached_blocks",
          "idle cached blocks resident now",
          lambda: pool.n_cached, typ=int)
        f("prefix_cache.quant_ops_avoided",
          "KV quant ops deleted outright by cache hits",
          lambda: self.requant_ops_avoided_cache, kind="counter", typ=int)

    def _register_hwcost_metrics(self) -> None:
        f = self.metrics.func
        f("hwcost.requant_ops_performed",
          "KV requant ops executed (paper Table 5 unit)",
          lambda: self.requant_ops_performed, kind="counter", typ=int)
        f("hwcost.requant_ops_avoided", "ops a dequantize-the-cache-every-"
          "step dataflow would have executed on top",
          lambda: self.requant_ops_avoided, kind="counter", typ=int)
        # ops a cache-less engine would have PERFORMED for the tokens the
        # prefix cache served from resident blocks (Table 5's strongest
        # case: quantized zero times instead of once)
        f("hwcost.requant_ops_avoided_prefix_cache",
          "ops deleted outright by prefix-cache hits",
          lambda: self.requant_ops_avoided_cache, kind="counter", typ=int)
        # ops spent quantizing speculative rows that were REJECTED —
        # performed (inside requant_ops_performed), then rolled back
        # before they could publish: the price of per-step amortization,
        # reported instead of hidden
        f("hwcost.requant_ops_wasted_speculation",
          "ops spent on rejected speculative rows",
          lambda: self.requant_ops_wasted_spec, kind="counter", typ=int)

        # substrate-comparable headline gauge (DESIGN §16): what a
        # requant-per-step dataflow pays per useful token — performed +
        # the avoided counterfactual, over prefill + decode tokens.  On
        # attention this GROWS with context (the avoided bucket is
        # n_ctx * elems per step); on the fixed-slab substrate it is
        # CONTEXT-FREE (one whole-slab requant per step), which is the
        # paper's dataflow thesis at its strongest — the recurrent bench
        # gate asserts this number sits strictly below the equal-length
        # attention baseline.
        def requant_ops_per_token():
            tok = self.energy.tokens["prefill"] + self.energy.tokens[
                "decode"]
            ops = self.requant_ops_performed + self.requant_ops_avoided
            return round(ops / tok, 2) if tok else None

        f("hwcost.requant_ops_per_token",
          "KV+state requant ops (performed + avoided counterfactual) "
          "per useful token",
          requant_ops_per_token, typ=float, optional=True)
        f("hwcost.energy_uj_bit_shift",
          "Table-5 bit-shift energy of the ops performed",
          lambda: hwcost.estimate(
              "bit_shifting", self.requant_ops_performed).energy_uj,
          unit="uJ", typ=float)
        f("hwcost.energy_uj_if_requant_per_step",
          "counterfactual energy of a requant-per-step dataflow",
          lambda: hwcost.estimate(
              "bit_shifting", self.requant_ops_performed
              + self.requant_ops_avoided).energy_uj, unit="uJ", typ=float)
        f("hwcost.energy_uj_if_no_prefix_cache",
          "counterfactual energy without the prefix cache",
          lambda: hwcost.estimate(
              "bit_shifting", self.requant_ops_performed
              + self.requant_ops_avoided_cache).energy_uj,
          unit="uJ", typ=float)
        f("hwcost.energy_uj_if_scaling_factor",
          "counterfactual energy with a scaling-factor requant unit",
          lambda: hwcost.estimate(
              "scaling_factor", self.requant_ops_performed
              + self.requant_ops_avoided).energy_uj, unit="uJ", typ=float)
        # full-forward W8A8 accounting (DESIGN §13): separate keys so the
        # KV-only Table-5 numbers stay comparable across W8A8-on/off runs
        # (forward keys are all zero on the dense path)
        f("hwcost.w8a8", "int8 weight+activation matmul path active",
          lambda: self.cfg.matmul_kernel == "int8", typ=bool)
        f("hwcost.forward_quant_ops_per_token",
          "per-token dynamic quant ops of the W8A8 forward dataflow",
          lambda: self._fwd_elems_per_token, typ=int)
        f("hwcost.requant_ops_forward",
          "W8A8 forward boundary quant ops executed",
          lambda: self.requant_ops_forward, kind="counter", typ=int)
        f("hwcost.requant_ops_forward_avoided_prefix_cache",
          "forward ops skipped for cache-hit prefill tokens",
          lambda: self.requant_ops_forward_avoided_cache,
          kind="counter", typ=int)
        f("hwcost.requant_ops_forward_wasted_speculation",
          "forward ops spent on rejected speculative rows",
          lambda: self.requant_ops_forward_wasted_spec,
          kind="counter", typ=int)
        f("hwcost.energy_uj_forward_bit_shift",
          "Table-5 bit-shift energy of the forward ops",
          lambda: hwcost.estimate(
              "bit_shifting", self.requant_ops_forward).energy_uj,
          unit="uJ", typ=float)
        f("hwcost.energy_uj_forward_if_scaling_factor",
          "counterfactual forward energy with a scaling-factor unit",
          lambda: hwcost.estimate(
              "scaling_factor", self.requant_ops_forward).energy_uj,
          unit="uJ", typ=float)

    def _register_energy_metrics(self) -> None:
        """Live Table-5 energy proxy split by phase (DESIGN §14): the
        requant ops (KV + W8A8 forward) attributed to prefill / decode /
        spec_wasted at each commit point, priced at the Table-5
        bit-shifting unit.  ``sum(phase quant_ops) ==
        requant_ops_performed + requant_ops_forward`` ALWAYS (the
        reconciliation test + bench gate assert it)."""
        f, en = self.metrics.func, self.energy
        f("energy.unit", "Table-5 requant unit pricing the proxy",
          lambda: en.kind, typ=str)
        for p in ("prefill", "decode", "spec_wasted"):
            f(f"energy.{p}.quant_ops",
              f"requant ops (KV + forward) attributed to {p}",
              lambda p=p: en.quant_ops[p], kind="counter", typ=int)
            f(f"energy.{p}.tokens",
              "rejected draft rows" if p == "spec_wasted" else
              f"useful tokens processed in {p}",
              lambda p=p: en.tokens[p], kind="counter", typ=int)
            f(f"energy.{p}.energy_uj",
              f"Table-5 energy of the {p} ops",
              lambda p=p: round(en.energy_uj(p), 6), unit="uJ", typ=float)
            f(f"energy.{p}.uj_per_token",
              "wasted energy amortized over EMITTED decode tokens"
              if p == "spec_wasted" else
              f"energy per useful {p} token",
              lambda p=p: (lambda v: None if v is None else round(v, 9))(
                  en.uj_per_token(p)),
              unit="uJ", typ=float, optional=True)
        f("energy.total_quant_ops", "sum of phase quant ops (== "
          "hwcost.requant_ops_performed + hwcost.requant_ops_forward)",
          lambda: en.total_quant_ops, kind="counter", typ=int)
        f("energy.total_energy_uj", "Table-5 energy of all requant ops",
          lambda: round(hwcost.estimate(
              en.kind, en.total_quant_ops).energy_uj, 6),
          unit="uJ", typ=float)
        f("energy.proxy_uj_per_token", "LIVE headline gauge: total requant"
          " energy over useful (prefill + decode) tokens",
          lambda: (lambda v: None if v is None else round(v, 9))(
              en.proxy_uj_per_token()),
          unit="uJ", typ=float, optional=True)

    def _register_timeline_metrics(self) -> None:
        """Latency percentiles DERIVED FROM THE TRACE (per-request
        timelines), the §14 source of truth going forward; the legacy
        ttft_s/tpot_s/e2e_s sections stay as the cross-check."""
        f, tr = self.metrics.func, self.tracer
        f("timeline.source", "where these latencies come from",
          lambda: "trace", typ=str)
        f("timeline.requests", "requests with a timeline",
          lambda: len(tr.timelines), typ=int)
        f("timeline.completed", "timelines with a done mark",
          lambda: sum(1 for t in tr.timelines.values()
                      if t.done is not None), typ=int)
        for name, key in (("ttft_s", "ttft"), ("tpot_s", "tpot"),
                          ("e2e_s", "e2e")):
            for p in (50, 99):
                f(f"timeline.{name}.p{p}",
                  f"trace-derived {key} p{p}, seconds",
                  (lambda key=key, p=p:
                   _pct(tr.derive_latencies()[key], p)),
                  unit="s", typ=float, optional=True)

    # -- report -----------------------------------------------------------

    def report(self) -> dict:
        """Schema-stable snapshot of the metrics registry, nested into
        the report shape the benches consume (DESIGN §14).  Disabled
        sections surface as explicit ``None`` (their metrics are never
        registered), preserving the pre-§14 contract."""
        rep = self.metrics.nested()
        rep.setdefault("speculative", None)
        rep.setdefault("prefix_cache", None)
        rep.setdefault("pool", None)
        rep.setdefault("state_pool", None)
        return rep
