"""Serving-engine end-to-end coverage (DESIGN §9) — the CI `serving`
smoke: a small Poisson trace on CPU must COMPLETE every request and the
continuous-batching paged tokens must MATCH the static-batch dense-cache
oracle exactly at fp32 (greedy).  Plus: preemption round-trip parity,
sampling hooks, report integrity, and the serve() warm-up split.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.qmodel import QuantContext, QuantMode
from repro.models import model as M
from repro.serving import Request, RequestState, ServingEngine

CTX = QuantContext(mode=QuantMode.FP)


def _cfg(**kw):
    cfg = get_smoke_config("qwen3_1_7b").scaled(dtype="float32")
    return dataclasses.replace(cfg, kv_cache_bits=8, **kw)


def _dense_oracle(cfg, params, prompt: np.ndarray, gen: int) -> list:
    """Static-batch oracle: one request, dense cache, greedy decode."""
    p_len = len(prompt)
    logits, cache = M.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                              cfg, CTX, max_seq=p_len + gen)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [int(tok[0, 0])]
    for i in range(gen - 1):
        l, cache = M.decode_step(params, tok, cache,
                                 jnp.asarray(p_len + i, jnp.int32), cfg, CTX)
        tok = jnp.argmax(l, -1)[:, None].astype(jnp.int32)
        out.append(int(tok[0, 0]))
    return out


def _check_vs_oracle(cfg, params, reqs, outputs):
    for r in reqs:
        oracle = _dense_oracle(cfg, params, r.prompt, r.max_new_tokens)
        got = outputs[r.rid].tolist()
        # stop-token-free requests emit exactly max_new_tokens
        assert got == oracle[:len(got)] and len(got) == r.max_new_tokens, \
            f"req {r.rid}: engine {got} vs oracle {oracle}"


def _workload(rng, n, vocab, *, p_lo=5, p_hi=20, g_lo=3, g_hi=9,
              arrivals=False):
    t = 0.0
    reqs = []
    for i in range(n):
        t += float(rng.exponential(0.02)) if arrivals else 0.0
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, vocab, size=int(
                rng.integers(p_lo, p_hi))).astype(np.int32),
            max_new_tokens=int(rng.integers(g_lo, g_hi)), arrival=t))
    return reqs


def test_poisson_smoke_completes_and_matches_oracle():
    """The CI `serving` smoke: small Poisson trace, every request
    completes, tokens are exactly the static-batch fp32 oracle's."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = _workload(np.random.default_rng(0), 6, cfg.vocab_size,
                     arrivals=True)
    eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                        max_model_len=32, chunk=8)
    rep = eng.run(reqs)
    assert rep["completed"] == len(reqs)
    eng.pool.check_invariants()
    assert eng.pool.n_live == 0                    # all blocks returned
    _check_vs_oracle(cfg, params, reqs, eng.outputs())
    # report integrity
    assert rep["gen_tokens"] == sum(r.max_new_tokens for r in reqs)
    assert rep["tokens_per_s"] > 0
    assert rep["ttft_s"]["p50"] is not None
    assert rep["tpot_s"]["p50"] is not None
    # decode steps batched slots: fewer steps than total generated tokens
    assert rep["decode_steps"] < rep["gen_tokens"]
    # compile/steady split is keyed by the DISPATCHED ragged work-list
    # shape (DESIGN §12) — one unified executable serves the whole run
    assert rep["ragged"] and rep["ragged_steps"] > 0
    ragged_keys = [k for k in rep["step_shapes"] if k.startswith("ragged_")]
    assert ragged_keys and "legacy_shapes" not in rep["step_shapes"]
    dec = rep["step_shapes"][ragged_keys[0]]
    assert dec["first_s"] > dec["steady_s"] > 0
    # padding honesty (satellite): the report quantifies bucket waste
    assert rep["dispatched_tokens"] > 0
    assert rep["padding_frac"] == round(
        rep["padded_tokens"] / rep["dispatched_tokens"], 4)


def test_attention_walk_counters_match_dispatches(monkeypatch):
    """``attn_grid_steps`` / ``attn_kv_blocks_copied`` are the sums, over
    the run's ragged dispatches, of the walk of the kernel each dispatch
    ran: the steps of its grid, and per head step the ceil(kv_len / bs)
    live blocks of each slot.  Block size and head_dim 128, so the
    kernel (interpret mode) serves every step; padding slots and table
    tails add steps, never blocks."""
    from jax.experimental import pallas as pl
    cfg = _cfg(head_dim=128)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = _workload(np.random.default_rng(4), 5, cfg.vocab_size,
                     arrivals=True)
    grids = {}          # stream rows -> grid of the kernel built for them
    pallas_call = pl.pallas_call

    def spy_call(kernel, **k):
        if k.get("name") == "ragged_paged_flash":
            grids[k["out_shape"].shape[0]] = tuple(k["grid_spec"].grid)
        return pallas_call(kernel, **k)

    monkeypatch.setattr(pl, "pallas_call", spy_call)
    eng = ServingEngine(cfg, params, CTX, n_slots=3, block_size=128,
                        max_model_len=384, chunk=8)
    seen = []
    dispatch = eng._dispatch_ragged

    def spy(tokens, positions, dest, bt, q_start, q_len, kv_len, *a, **k):
        seen.append((len(tokens), kv_len.copy()))
        return dispatch(tokens, positions, dest, bt, q_start, q_len, kv_len,
                        *a, **k)

    eng._dispatch_ragged = spy
    rep = eng.run(reqs)
    assert rep["completed"] == len(reqs) and len(seen) == rep["ragged_steps"]
    # 3-block tables fit one step with every head: a dispatch walks its 3
    # slots in 3 grid steps
    assert all(grids[t] == (1, 3, 1) for t, _ in seen)
    assert rep["attn_grid_steps"] == sum(
        int(np.prod(grids[t])) for t, _ in seen) > 0
    assert rep["attn_kv_blocks_copied"] == sum(
        grids[t][0] * int((-(-kl // 128)).sum()) for t, kl in seen) > 0


def test_preemption_roundtrip_matches_oracle():
    """Undersized pool: decode growth must evict and resume (recompute),
    and the resumed requests still emit the oracle's exact tokens."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=14).astype(np.int32), max_new_tokens=12)
        for i in range(4)]
    # 5 usable blocks x 8 = 40 rows < 2 slots x 26 rows each
    eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                        max_model_len=32, num_blocks=6, chunk=8)
    rep = eng.run(reqs)
    assert rep["completed"] == 4
    assert rep["preemptions"] > 0 and rep["pool"]["evictions"] > 0
    eng.pool.check_invariants()
    assert eng.pool.n_live == 0
    _check_vs_oracle(cfg, params, reqs, eng.outputs())


def test_shared_prefix_blocks_physically_shared_and_token_exact():
    """CI `serving` gate for the prefix cache (DESIGN §10): requests
    sharing a prefix must physically share pool blocks (asserted on block
    ids mid-run), a repeated prompt must take the COW path, the report
    must show hit-rate > 0 and >= 1 COW event, and every request decodes
    token-exactly vs the dense-cache oracle through the divergence."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(21)
    shared = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
    tail = rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
    r0 = Request(rid=0, prompt=shared.copy(), max_new_tokens=8)
    r1 = Request(rid=1, prompt=np.concatenate([shared, tail]),
                 max_new_tokens=6)
    r2 = Request(rid=2, prompt=shared.copy(), max_new_tokens=4)  # repeat
    eng = ServingEngine(cfg, params, CTX, n_slots=3, block_size=8,
                        max_model_len=40, chunk=8)
    eng.submit(r0)
    for _ in range(30):
        eng.step()
        if r0.state is RequestState.DECODE:
            break
    assert r0.state is RequestState.DECODE         # prefix published
    eng.submit(r1)
    eng.submit(r2)
    eng.step()
    b0 = eng.pool.seq_blocks(0)
    b1 = eng.pool.seq_blocks(1)
    b2 = eng.pool.seq_blocks(2)
    # ACCEPTANCE: the same physical pool blocks back the shared prefix
    assert b1[:2] == b0[:2]
    assert (eng.pool.refcount[b0[:2]] >= 2).all()
    # the exact repeat is a FULL-feed hit: first block shared, last block
    # copy-on-written so the re-fed token's write stays private
    assert b2[0] == b0[0] and b2[1] != b0[1]
    while not eng.sched.idle:
        eng.step()
    rep = eng.report()
    assert rep["completed"] == 3
    pc = rep["prefix_cache"]
    assert pc["hit_rate"] > 0 and pc["hits"] >= 4
    assert pc["cow_copies"] >= 1
    # r1 attached 16 prefix tokens, r2 skipped 15 (full hit, one re-fed)
    assert pc["cached_prefill_tokens"] == 31
    assert rep["hwcost"]["requant_ops_avoided_prefix_cache"] > 0
    eng.pool.check_invariants()
    assert eng.pool.n_live == 0 and eng.pool.n_cached > 0
    # token-exactness through sharing + COW divergence
    _check_vs_oracle(cfg, params, [r0, r1, r2], eng.outputs())


def test_shared_prefix_preemption_roundtrip_matches_oracle():
    """Cache + pressure: an undersized pool forces recompute preemption
    while requests share a prefix (and one repeats it exactly).  Resumes
    re-attach whatever published blocks survived, and every request still
    decodes token-exactly vs the dense oracle."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(13)
    shared = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
    reqs = []
    for i in range(4):
        tail = rng.integers(0, cfg.vocab_size, size=4).astype(np.int32)
        reqs.append(Request(rid=i, prompt=np.concatenate([shared, tail]),
                            max_new_tokens=10))
    reqs[2].prompt = reqs[0].prompt.copy()         # exact duplicate
    # 5 usable blocks x 8 = 40 rows < 2 slots x 26 rows each
    eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                        max_model_len=32, num_blocks=6, chunk=8)
    rep = eng.run(reqs)
    assert rep["completed"] == 4
    assert rep["preemptions"] > 0 and rep["pool"]["evictions"] > 0
    eng.pool.check_invariants()
    assert eng.pool.n_live == 0
    _check_vs_oracle(cfg, params, reqs, eng.outputs())


def test_prefix_cache_off_matches_cached_engine_greedy():
    """A/B at equal pool size: the cache changes WHAT work runs, never
    the tokens — prefix_cache=False produces identical greedy outputs."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(17)
    shared = rng.integers(0, cfg.vocab_size, size=10).astype(np.int32)

    def workload():
        return [Request(rid=i, prompt=np.concatenate(
            [shared, rng2.integers(0, cfg.vocab_size, size=3 + i)
             .astype(np.int32)]), max_new_tokens=5) for i in range(3)]

    rng2 = np.random.default_rng(19)
    reqs_a = workload()
    rng2 = np.random.default_rng(19)
    reqs_b = workload()
    on = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                       max_model_len=32, chunk=8, prefix_cache=True)
    on.run(reqs_a)
    off = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                        max_model_len=32, chunk=8, prefix_cache=False)
    off.run(reqs_b)
    assert off.pool.cache is None
    for rid in range(3):
        assert on.outputs()[rid].tolist() == off.outputs()[rid].tolist()
    assert on.pool.cache.stats.hits > 0            # the cache did engage


def test_stop_token_and_max_len():
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    # find what greedy emits first, then use it as the stop token
    first = _dense_oracle(cfg, params, prompt, 1)[0]
    reqs = [
        Request(rid=0, prompt=prompt, max_new_tokens=10, stop_token=first),
        # prompt 28 + max_new 4 == max_model_len 32: must clamp, not hang
        Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, size=28)
                .astype(np.int32), max_new_tokens=4),
    ]
    eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                        max_model_len=32, chunk=8)
    rep = eng.run(reqs)
    assert rep["completed"] == 2
    outs = eng.outputs()
    assert outs[0].tolist() == [first]             # stopped immediately
    assert len(outs[1]) == 4


def test_sampling_hooks_deterministic():
    """temperature/top-k sampling: tokens stay in-vocab and the whole run
    is reproducible from the engine seed."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    def run():
        rng = np.random.default_rng(7)
        reqs = _workload(rng, 4, cfg.vocab_size)
        for r in reqs:
            r.temperature = 0.8
        eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                            max_model_len=32, chunk=8, top_k=5, seed=42)
        eng.run(reqs)
        return eng.outputs()

    a, b = run(), run()
    for rid in a:
        assert a[rid].tolist() == b[rid].tolist()
        assert (a[rid] >= 0).all() and (a[rid] < cfg.vocab_size).all()


def test_per_request_top_k_honored():
    """Request.top_k is applied per slot: top_k=1 with temperature > 0
    degenerates to greedy (the only survivor of the k-filter is the
    argmax), so its tokens must equal the greedy oracle's while riding in
    the same batch as full-vocab sampled requests."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    reqs = _workload(rng, 3, cfg.vocab_size, g_lo=4, g_hi=7)
    reqs[0].temperature = 1.0
    reqs[0].top_k = 1                              # == greedy
    reqs[1].temperature = 1.0                      # full-vocab sampling
    eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                        max_model_len=32, chunk=8, seed=3)
    eng.run(reqs)
    outs = eng.outputs()
    oracle = _dense_oracle(cfg, params, reqs[0].prompt,
                           reqs[0].max_new_tokens)
    assert outs[0].tolist() == oracle
    assert outs[2].tolist() == _dense_oracle(cfg, params, reqs[2].prompt,
                                             reqs[2].max_new_tokens)


def test_mixed_greedy_and_sampled_slots():
    """temperature=0 rows in a sampled batch stay EXACTLY greedy: the
    fixed-shape sampler must not perturb greedy requests."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    reqs = _workload(rng, 4, cfg.vocab_size, g_lo=4, g_hi=7)
    reqs[1].temperature = 1.0
    reqs[3].temperature = 1.0
    eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                        max_model_len=32, chunk=8, seed=1)
    eng.run(reqs)
    outs = eng.outputs()
    for r in (reqs[0], reqs[2]):                   # the greedy ones
        oracle = _dense_oracle(cfg, params, r.prompt, r.max_new_tokens)
        assert outs[r.rid].tolist() == oracle


def test_hwcost_requant_accounting():
    """Write-once accounting: performed ops == KV elements written once
    per (real) token; avoided ops grow with live context per decode step —
    and the Table 5 energies order accordingly."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    req = Request(rid=0, prompt=np.arange(10, dtype=np.int32) % cfg.vocab_size,
                  max_new_tokens=6)
    eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                        max_model_len=32, chunk=8)
    rep = eng.run([req])
    per_tok = (cfg.n_layers * cfg.n_kv_heads * cfg.resolved_head_dim * 2)
    hw = rep["hwcost"]
    # 10 prompt + 5 decode-fed tokens, each quantized exactly once
    assert hw["requant_ops_performed"] == 15 * per_tok
    # dequant-per-step counterfactual: sum of live context over 5 steps
    assert hw["requant_ops_avoided"] == sum(
        11 + i for i in range(5)) * per_tok
    assert (hw["energy_uj_bit_shift"]
            < hw["energy_uj_if_requant_per_step"]
            < hw["energy_uj_if_scaling_factor"])


@pytest.mark.parametrize("spec_k", [0, 3])
def test_ragged_engine_token_identical_to_legacy(spec_k):
    """ACCEPTANCE (DESIGN §12): the unified ragged step is a pure
    dataflow refactor — same workload, same params, greedy outputs are
    token-for-token IDENTICAL to the retired per-shape engine, with
    speculation off and on."""
    from repro.serving.spec import CallableDrafter
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    def run(ragged):
        reqs = _workload(np.random.default_rng(23), 5, cfg.vocab_size,
                         arrivals=True)
        # deterministic always-proposing drafter so BOTH engines hit the
        # verify path (ngram rarely fires on short random prompts)
        drafter = CallableDrafter(lambda h, k: [int(h[-1])] * k)
        eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                            max_model_len=32, chunk=8, spec_k=spec_k,
                            drafter=drafter, ragged=ragged)
        rep = eng.run(reqs)
        assert rep["completed"] == len(reqs)
        eng.pool.check_invariants()
        assert eng.pool.n_live == 0
        return eng.outputs(), rep

    got_r, rep_r = run(True)
    got_l, rep_l = run(False)
    assert rep_r["ragged"] and not rep_l["ragged"]
    assert rep_r["ragged_steps"] > 0 and rep_l["ragged_steps"] == 0
    for rid in got_l:
        assert got_r[rid].tolist() == got_l[rid].tolist(), f"req {rid}"
    if spec_k:
        assert rep_r["spec_steps"] > 0 and rep_l["spec_steps"] > 0
        assert (rep_r["speculative"]["drafted_tokens"]
                == rep_l["speculative"]["drafted_tokens"] > 0)


def test_ragged_engine_token_identical_through_preemption_and_sharing():
    """The hard path: an undersized pool forces eviction/recompute while
    requests share (and one exactly repeats) a prefix — the ragged
    scheduler makes DIFFERENT step-level choices than the legacy phase
    loop, but greedy per-request token streams must not change."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(29)
    shared = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)

    def workload():
        r2 = np.random.default_rng(31)
        reqs = [Request(rid=i, prompt=np.concatenate(
            [shared, r2.integers(0, cfg.vocab_size, size=4)
             .astype(np.int32)]), max_new_tokens=10) for i in range(4)]
        reqs[2].prompt = reqs[0].prompt.copy()     # exact duplicate
        return reqs

    outs = {}
    for ragged in (True, False):
        eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                            max_model_len=32, num_blocks=6, chunk=8,
                            ragged=ragged)
        rep = eng.run(workload())
        assert rep["completed"] == 4
        assert rep["preemptions"] > 0
        assert rep["prefix_cache"]["hits"] > 0
        eng.pool.check_invariants()
        assert eng.pool.n_live == 0
        outs[ragged] = eng.outputs()
    for rid in outs[False]:
        assert outs[True][rid].tolist() == outs[False][rid].tolist()
    _check_vs_oracle(cfg, params, workload(), outs[True])


def test_ragged_padding_strictly_less_than_bucketed():
    """Satellite regression: on a mixed prefill+decode workload at
    serving scale, the ragged work-list dispatches strictly fewer padded
    tokens than the per-shape bucketed engine — the perf claim the
    tentpole exists for, held token-identical at the same time."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    def run(ragged):
        # staggered arrivals keep prefill chunks and decode rows live in
        # the same steps — the mix the bucketed engine pads worst
        reqs = _workload(np.random.default_rng(37), 10, cfg.vocab_size,
                         p_lo=6, p_hi=24, g_lo=4, g_hi=10, arrivals=True)
        eng = ServingEngine(cfg, params, CTX, n_slots=8, block_size=8,
                            max_model_len=64, chunk=16,
                            prefill_token_budget=32, ragged=ragged)
        rep = eng.run(reqs)
        assert rep["completed"] == len(reqs)
        return eng.outputs(), rep

    got_r, rep_r = run(True)
    got_l, rep_l = run(False)
    for rid in got_l:
        assert got_r[rid].tolist() == got_l[rid].tolist(), f"req {rid}"
    assert rep_r["padded_tokens"] < rep_l["padded_tokens"], (
        rep_r["padded_tokens"], rep_l["padded_tokens"])
    assert rep_r["padding_frac"] < rep_l["padding_frac"]


def test_serve_warmup_reports_compile_separately():
    """Satellite: serve() AOT-compiles, so prefill_s / decode_s_per_tok
    are steady-state and compile time is its own field."""
    from repro.launch.serve import serve
    out = serve("qwen3_1_7b", batch=2, prompt_len=8, gen=4, mode="fp",
                calibrate=False)
    assert out["compile_prefill_s"] > 0 and out["compile_decode_s"] > 0
    assert out["prefill_s"] > 0 and out["decode_s_per_tok"] > 0
    # steady per-token decode must not contain a multi-second jit compile
    assert out["decode_s_per_tok"] < out["compile_decode_s"]


PHASES = ["engine.admit", "engine.plan", "engine.pack", "engine.transfer",
          "engine.launch", "engine.sync", "engine.commit"]


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    while not eng.sched.idle:
        eng.step()


def test_ring_shows_every_steps_phases_in_order():
    """With the ring on, each ``step()`` is one ``engine.step`` span whose
    children are the seven phases in order, and the dispatch's
    ``ragged_step`` span (its args unchanged) lies between transfer and
    sync."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    reqs = _workload(np.random.default_rng(3), 4, cfg.vocab_size)
    eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                        max_model_len=32, chunk=8, trace=True)
    _drain(eng, reqs)
    spans = [e for e in eng.tracer.events if e[0] == "X"]
    steps = [e for e in spans if e[1] == "engine.step"]
    assert len(steps) == eng.ragged_steps > 0
    for _, _, _, t0, dur, _ in steps:
        inner = sorted((e for e in spans if e[1] != "engine.step"
                        and t0 <= e[3] and e[3] + e[4] <= t0 + dur),
                       key=lambda e: e[3])
        names = [e[1] for e in inner if e[1].startswith("engine.")]
        assert names == PHASES
        disp, = [e for e in inner if e[1] == "ragged_step"]
        assert disp[2] == "dispatch"
        assert set(disp[5]) == {"shape", "real_tokens", "padded_tokens",
                                "compile"}
        at = {e[1]: e for e in inner}
        assert at["engine.transfer"][3] >= disp[3]
        assert at["engine.sync"][3] + at["engine.sync"][4] \
            <= disp[3] + disp[4]


def test_caller_clock_changes_no_token_or_decision():
    """An engine driven on its caller's clock (here a counter far from
    the default clock's zero) serves the same tokens through the same
    scheduler decisions, and stamps its timelines on that clock."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    def serve(clock):
        reqs = _workload(np.random.default_rng(4), 5, cfg.vocab_size)
        eng = ServingEngine(cfg, params, CTX, n_slots=2, block_size=8,
                            max_model_len=32, chunk=8, trace=True,
                            num_blocks=7)
        if clock is not None:
            eng.clock = clock
            for r in reqs:
                r.arrival = clock()
        _drain(eng, reqs)
        decisions = [(e[1], e[5]) for e in eng.tracer.events
                     if e[2] in ("sched", "pool", "cache")]
        return eng, decisions

    base, base_dec = serve(None)
    t = [1000.0]

    def clock():
        t[0] += 0.25
        return t[0]
    mine, mine_dec = serve(clock)
    assert {k: v.tolist() for k, v in mine.outputs().items()} == \
        {k: v.tolist() for k, v in base.outputs().items()}
    assert mine.sched.admission_log == base.sched.admission_log
    assert mine_dec == base_dec and mine_dec
    tls = mine.tracer.timelines.values()
    assert all(1000.0 < tl.arrival <= tl.admit < tl.first_token <= tl.done
               <= t[0] for tl in tls)
