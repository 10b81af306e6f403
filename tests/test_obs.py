"""Observability layer (DESIGN §14): metrics registry, trace ring,
energy accounting, and the golden report schema.

The engine-integration half runs ONE small mixed workload (speculation +
prefix cache + tracing all on) through a module-scoped engine and then
asserts every §14 contract against that single run: the report is a
nested view of the registry and matches the committed GOLDEN_SCHEMA;
trace-derived TTFT/TPOT/e2e percentiles equal the legacy
request-timestamp percentiles EXACTLY (the marks reuse the same clock
reads); the phase-split energy proxy reconciles exactly with the
Table-5 requant counters; the exported trace validates against the
Chrome trace-event schema; and the duplicated ``retracts`` fields are
declared aliases that cannot diverge.
"""
import dataclasses
import json
import math

import numpy as np
import pytest

from repro.core import hwcost
from repro.obs.metrics import (Counter, FuncMetric, Gauge, Histogram,
                               MetricsRegistry, prom_name)
from repro.obs.profile import ENERGY_PHASES, EnergyAccount, Profiler
from repro.obs.schema import GOLDEN_SCHEMA, diff_schema, schema_of
from repro.obs.trace import Tracer, validate_chrome_trace
from repro.serving.engine import _pct, summarize_step_times


# ---------------------------------------------------------------------------
# metrics registry (pure python)
# ---------------------------------------------------------------------------

def test_counter_unlabeled_and_labeled():
    c = Counter("x.ops", "ops", label_names=("phase",))
    c.inc(3, phase="prefill")
    c.inc(2, phase="decode")
    c.inc(1, phase="prefill")
    assert c.get() == 6
    assert c.get(phase="prefill") == 4
    assert c.value() == {"phase=decode": 2, "phase=prefill": 4}
    c.reset()
    assert c.get() == 0 and c.value() == {}
    u = Counter("y", "plain")
    u.inc()
    u.inc(4)
    assert u.value() == 5


def test_gauge_and_func_metric():
    g = Gauge("g", "a gauge")
    g.set(2.5)
    g.add(0.5)
    assert g.value() == 3.0
    src = {"v": 7}
    f = FuncMetric("f", "bound", lambda: src["v"], kind="counter")
    assert f.value() == 7
    src["v"] = 9
    assert f.value() == 9          # read at snapshot time, not bind time
    f.reset()                      # bound metrics follow their source
    assert f.value() == 9
    with pytest.raises(ValueError):
        FuncMetric("f", "bad kind", lambda: 0, kind="summary")


def test_histogram_percentile_upper_bound_never_interpolates():
    h = Histogram("h", "lat", buckets=[0.001, 0.01, 0.1])
    assert h.percentile(50) is None
    for v in (0.0005, 0.002, 0.003, 0.05):
        h.observe(v)
    assert h.n == 4
    # p50 sample is 0.002/0.003 -> bucket upper bound 0.01, not a blend
    assert h.percentile(50) == 0.01
    assert h.percentile(99) == 0.1
    h.observe(5.0)                 # lands in +Inf
    assert h.percentile(99) == math.inf
    val = h.value()
    assert val["count"] == 5 and val["buckets"]["+Inf"] == 1


def test_registry_rejects_duplicates_and_undocumented():
    m = MetricsRegistry()
    m.counter("a", "doc")
    with pytest.raises(ValueError):
        m.counter("a", "again")
    with pytest.raises(ValueError):
        m.counter("b", "")
    assert "a" in m and len(m) == 1


def test_registry_alias_check_is_deferred():
    m = MetricsRegistry()
    # alias registered BEFORE its canonical target (report order allows
    # speculative.* to precede pool.*) — only check_aliases enforces it
    m.func("view.n", "view", lambda: 0, alias_of="canon.n")
    with pytest.raises(ValueError):
        m.check_aliases()
    m.func("canon.n", "canonical", lambda: 0)
    m.check_aliases()


def test_registry_nested_and_reset_owned_only():
    m = MetricsRegistry()
    m.counter("top", "t")
    m.counter("sec.a", "a")
    m.gauge("sec.deep.b", "b")
    src = {"v": 3}
    m.func("sec.bound", "bound", lambda: src["v"])
    m.get("top").inc(2)
    m.get("sec.a").inc(1)
    m.get("sec.deep.b").set(1.5)
    assert m.nested() == {"top": 2,
                          "sec": {"a": 1, "deep": {"b": 1.5}, "bound": 3}}
    assert list(m.snapshot()) == ["top", "sec.a", "sec.deep.b",
                                  "sec.bound"]
    m.reset()
    assert m.get("top").value() == 0
    assert m.get("sec.bound").value() == 3      # bound follows its source


def test_prometheus_exposition():
    m = MetricsRegistry()
    m.counter("pool.allocs", "blocks allocated").inc(4)
    m.gauge("engine.util", "utilization").set(0.5)
    m.func("engine.mode", "serving mode", lambda: "ragged")
    m.func("engine.maybe", "optional value", lambda: None, optional=True)
    h = m.histogram("step.time", "step seconds", buckets=[0.01, 0.1])
    h.observe(0.005)
    h.observe(0.05)
    text = m.to_prometheus()
    assert "# TYPE pool_allocs counter\npool_allocs 4" in text
    assert "engine_util 0.5" in text
    assert 'engine_mode_info{value="ragged"} 1' in text
    assert 'engine_maybe_info{value="none"} 1' in text
    assert 'step_time_bucket{le="0.01"} 1' in text
    assert 'step_time_bucket{le="+Inf"} 2' in text
    assert "step_time_count 2" in text
    assert prom_name("a.b-c d") == "a_b_c_d"


# ---------------------------------------------------------------------------
# tracer (pure python)
# ---------------------------------------------------------------------------

def test_ring_is_bounded_and_counts_drops():
    tr = Tracer(capacity=8, clock=lambda: 0.0, enabled=True)
    for i in range(30):
        tr.event(f"e{i}", "pool")
    assert len(tr.events) == 8
    assert tr.n_emitted == 30
    assert tr.dropped == 22
    # oldest dropped first: the ring holds the most recent 8
    assert [e[1] for e in tr.events] == [f"e{i}" for i in range(22, 30)]
    tr.reset()
    assert len(tr.events) == 0 and tr.dropped == 0


def test_disabled_tracer_records_nothing_but_timelines_stay_on():
    tr = Tracer(capacity=8, clock=lambda: 0.0, enabled=False)
    tr.event("e", "pool")
    tr.span("s", "dispatch", 0.0, 1.0)
    assert tr.n_emitted == 0 and not tr.events
    tr.req_submit(1, arrival=0.5)
    tr.req_mark(1, "first_token", 1.5)
    tr.req_token(1, 1.5)                       # ring-gated: dropped
    tr.req_done(1, 2.5, n_generated=3)
    tl = tr.timelines[1]
    assert tl.ttft == 1.0 and tl.e2e == 2.0
    assert tl.tpot == pytest.approx(0.5)
    assert tl.tokens == []


def test_timeline_marks_are_first_occurrence_wins():
    tr = Tracer(capacity=8, enabled=False)
    tr.req_submit(7, arrival=1.0)
    tr.req_submit(7, arrival=99.0)             # re-queue keeps original
    tr.req_mark(7, "admit", 2.0)
    tr.req_mark(7, "admit", 50.0)              # resume is not admission
    tr.req_preempt(7)
    tr.req_done(7, 5.0, n_generated=1)
    tr.req_done(7, 90.0, n_generated=9)
    tl = tr.timelines[7]
    assert (tl.arrival, tl.admit, tl.done) == (1.0, 2.0, 5.0)
    assert tl.n_generated == 1 and tl.preemptions == 1
    assert tl.tpot is None                     # needs n_generated >= 2


def test_derive_latencies_skips_unfinished():
    tr = Tracer(capacity=8, enabled=False)
    tr.req_submit(0, 0.0)
    tr.req_mark(0, "first_token", 1.0)
    tr.req_done(0, 3.0, n_generated=5)
    tr.req_submit(1, 0.0)                      # never finished
    lat = tr.derive_latencies()
    assert lat["ttft"] == [1.0] and lat["e2e"] == [3.0]
    assert lat["tpot"] == [pytest.approx(0.5)]


def test_phases_nest_under_engine_step_in_the_ring():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]
    tr = Tracer(capacity=16, clock=clock, enabled=True)
    with tr.phase("engine.step", "engine", step=3):
        with tr.phase("engine.admit", "engine"):
            pass
        with tr.phase("engine.launch", "engine", args={"k": 1}):
            pass
    # spans land as they close: children first, each inside its parent
    ev = list(tr.events)
    assert [e[1] for e in ev] == ["engine.admit", "engine.launch",
                                  "engine.step"]
    assert all(e[0] == "X" and e[2] == "engine" for e in ev)
    (_, _, _, s0, d0, _), (_, _, _, s1, d1, a1), (_, _, _, s, d, _) = ev
    assert s < s0 < s0 + d0 <= s1 < s1 + d1 < s + d
    assert a1 == {"k": 1}


def test_phase_with_both_sinks_off_reads_no_clock():
    calls = []
    tr = Tracer(capacity=8, clock=lambda: calls.append(1) or 0.0,
                enabled=False)
    a = tr.phase("engine.step", "engine", step=0)
    b = tr.phase("engine.plan", "engine")
    assert a is b                    # one shared no-op context
    with a:
        with b:
            pass
    assert calls == [] and tr.n_emitted == 0 and not tr.events


def test_phase_annotates_only_while_capturing(monkeypatch):
    import jax
    entered = []

    class Ann:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            entered.append((self.name, self.kw))

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Ann)
    tr = Tracer(capacity=8, clock=lambda: 0.0, enabled=False)
    with tr.phase("engine.plan", "engine"):
        pass
    assert entered == []
    tr.capturing = True
    with tr.phase("engine.step", "engine", step=7):
        with tr.phase("engine.plan", "engine"):
            pass
    assert entered == [("engine.step", {"step_num": 7}),
                       ("engine.plan", {})]
    assert tr.n_emitted == 0         # the ring stays off


def test_chrome_export_schema():
    tr = Tracer(capacity=16, clock=lambda: 0.0, enabled=True)
    tr.event("pool.alloc", "pool", ts=0.001, args={"seq": 1})
    tr.span("ragged_step", "dispatch", 0.002, 0.003,
            {"shape": "T8xS2", "compile": True})
    tr.req_submit(0, 0.0)
    tr.req_mark(0, "admit", 0.001)
    tr.req_mark(0, "first_token", 0.004)
    tr.req_done(0, 0.01, n_generated=4)
    obj = tr.to_chrome()
    assert validate_chrome_trace(obj) == []
    ev = obj["traceEvents"]
    spans = [e for e in ev if e["ph"] == "X"]
    names = {e["name"] for e in ev}
    assert "ragged_step" in names and "req 0" in names
    assert "first_token rid=0" in names
    step = next(e for e in spans if e["name"] == "ragged_step")
    assert step["ts"] == 2000.0 and step["dur"] == 3000.0   # seconds->us
    req = next(e for e in spans if e["name"] == "req 0")
    assert req["args"]["ttft_s"] == pytest.approx(0.004)
    assert obj["otherData"]["dropped_events"] == 0
    json.dumps(obj)                            # file-writable


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"foo": 1}) != []
    bad_phase = {"traceEvents": [
        {"name": "e", "ph": "Z", "ts": 0, "pid": 0, "tid": 0}]}
    assert any("phase" in p for p in validate_chrome_trace(bad_phase))
    no_dur = {"traceEvents": [
        {"name": "e", "ph": "X", "ts": 0, "pid": 0, "tid": 0}]}
    assert any("dur" in p for p in validate_chrome_trace(no_dur))
    missing = {"traceEvents": [{"ph": "i", "ts": 0, "pid": 0}]}
    probs = validate_chrome_trace(missing)
    assert any("name" in p for p in probs) and any("tid" in p
                                                   for p in probs)


# ---------------------------------------------------------------------------
# energy account (pure python)
# ---------------------------------------------------------------------------

def test_energy_account_phases_and_per_token_semantics():
    en = EnergyAccount("bit_shifting")
    with pytest.raises(ValueError):
        EnergyAccount("free_lunch")
    assert en.uj_per_token("prefill") is None          # 0 ops / 0 toks
    en.charge("prefill", 1000, 10)
    en.charge("decode", 500, 5)
    en.charge("spec_wasted", 200, 2)
    assert en.total_quant_ops == 1700
    pj = hwcost.energy_per_op_pj("bit_shifting")
    assert en.energy_uj("prefill") == pytest.approx(1000 * pj * 1e-6)
    assert en.uj_per_token("prefill") == pytest.approx(
        en.energy_uj("prefill") / 10)
    # spec_wasted amortizes over EMITTED decode tokens, not wasted rows
    assert en.uj_per_token("spec_wasted") == pytest.approx(
        en.energy_uj("spec_wasted") / 5)
    assert en.proxy_uj_per_token() == pytest.approx(
        hwcost.estimate("bit_shifting", 1700).energy_uj / 15)
    rep = en.report()
    assert rep["unit"] == "bit_shifting"
    assert set(ENERGY_PHASES) <= set(rep)
    assert rep["total_quant_ops"] == 1700
    en.reset()
    assert en.total_quant_ops == 0 and en.proxy_uj_per_token() is None


def test_energy_ops_without_tokens_is_inf_not_crash():
    en = EnergyAccount()
    en.charge("decode", 100, 0)
    assert en.uj_per_token("decode") == float("inf")


def test_profiler_disabled_is_inert():
    tr = Tracer(capacity=8, enabled=False)
    p = Profiler(tracer=tr)
    assert not p.enabled and p.report() is None
    with p.capture():
        pass
    with tr.phase("engine.step", "engine", step=0):
        pass
    assert not tr.capturing and tr.n_emitted == 0
    assert p.cost_for(("ragged", 8, 2), None) is None


def test_profiler_directory_is_the_capture_flag():
    tr = Tracer(capacity=8, enabled=False)
    p = Profiler(tracer=tr, profile_dir="/nonexistent")
    assert tr.capturing
    p.profile_dir = None
    assert not tr.capturing
    p.profile_dir = "/nonexistent"
    assert tr.capturing and p.enabled


# ---------------------------------------------------------------------------
# summarize_step_times edge cases (obs satellite)
# ---------------------------------------------------------------------------

def test_step_times_empty_and_tiny_sample_lists():
    assert summarize_step_times({}) == {}
    out = summarize_step_times({("ragged", 8, 2): []})
    assert out["ragged_8xS2"] == {"calls": 0, "first_s": None,
                                  "steady_s": None, "p99_s": None}
    out = summarize_step_times({("ragged", 8, 2): [0.5]})
    assert out["ragged_8xS2"] == {"calls": 1, "first_s": 0.5,
                                  "steady_s": None, "p99_s": None}
    # one steady sample: a median exists, a p99 tail bound does not
    out = summarize_step_times({("ragged", 8, 2): [0.5, 0.1]})
    assert out["ragged_8xS2"] == {"calls": 2, "first_s": 0.5,
                                  "steady_s": 0.1, "p99_s": None}
    out = summarize_step_times({("ragged", 8, 2): [0.5, 0.1, 0.3]})
    e = out["ragged_8xS2"]
    assert e["calls"] == 3 and e["steady_s"] == 0.2
    assert e["p99_s"] == round(_pct([0.1, 0.3], 99), 4)


def test_step_times_never_index_errors_across_key_kinds():
    out = summarize_step_times({
        ("ragged", 8, 2): [],
        ("decode", 4): [0.2],
        "prefill_1x32": [],
    })
    assert out["legacy_shapes"]["decodex4"]["calls"] == 1
    assert out["prefill_1x32"]["calls"] == 0


# ---------------------------------------------------------------------------
# engine integration: one small traced run, every §14 contract
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_run():
    import jax
    from repro.configs import get_smoke_config
    from repro.core.qmodel import QuantContext, QuantMode
    from repro.models import model as M
    from repro.serving import Request, ServingEngine

    cfg = dataclasses.replace(
        get_smoke_config("qwen3_1_7b").scaled(dtype="float32"),
        kv_cache_bits=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, QuantContext(mode=QuantMode.FP),
                        n_slots=2, block_size=8, max_model_len=64,
                        spec_k=3, prefix_cache=True, trace=True)
    rng = np.random.default_rng(0)
    reqs, t = [], 0.0
    for i in range(4):
        t += float(rng.exponential(0.02))
        # one repetitive prompt so the ngram drafter proposes something
        prompt = (np.tile(rng.integers(0, cfg.vocab_size, size=3), 5)
                  if i == 1 else
                  rng.integers(0, cfg.vocab_size,
                               size=int(rng.integers(5, 20))))
        reqs.append(Request(rid=i, prompt=prompt.astype(np.int32),
                            max_new_tokens=int(rng.integers(3, 9)),
                            arrival=t))
    rep = eng.run(reqs)
    return eng, rep


def test_golden_schema_matches_registry(traced_run):
    eng, _ = traced_run
    errs = diff_schema(schema_of(eng.metrics), spec=True, cache=True)
    assert errs == [], "\n".join(errs)
    eng.metrics.check_aliases()
    for name, m in ((n, eng.metrics.get(n)) for n in eng.metrics.names()):
        assert m.help.strip(), f"{name} has no help text"


def test_report_is_nested_registry_view(traced_run):
    eng, rep = traced_run
    nested = eng.metrics.nested()
    # all attention-substrate sections enabled; the slab section is
    # substrate-exclusive and surfaces as an explicit None (§16)
    nested.setdefault("state_pool", None)
    assert rep == nested
    # every snapshot value is JSON-serializable with documented type
    snap = eng.metrics.snapshot()
    json.dumps(snap)
    for name, val in snap.items():
        d = eng.metrics.get(name)
        if val is None:
            assert d.optional, f"{name} is None but not declared optional"
        else:
            assert isinstance(val, d.typ) or (
                d.typ is float and isinstance(val, int)), \
                f"{name}: {type(val).__name__} is not declared {d.typ}"


def test_timeline_percentiles_match_legacy_exactly(traced_run):
    _, rep = traced_run
    for sec in ("ttft_s", "tpot_s", "e2e_s"):
        assert rep["timeline"][sec] == rep[sec], sec
    assert rep["timeline"]["completed"] == rep["completed"]


def test_energy_reconciles_exactly_with_hwcost(traced_run):
    _, rep = traced_run
    en, hw = rep["energy"], rep["hwcost"]
    assert en["total_quant_ops"] == (hw["requant_ops_performed"]
                                     + hw["requant_ops_forward"])
    assert en["total_quant_ops"] == sum(
        en[p]["quant_ops"] for p in ENERGY_PHASES)
    assert en["spec_wasted"]["quant_ops"] == \
        hw["requant_ops_wasted_speculation"]
    # useful-token accounting: prefill fed every prompt token, decode
    # emitted everything past each request's first token
    assert en["prefill"]["tokens"] == rep["prompt_tokens"]
    assert en["decode"]["tokens"] == rep["gen_tokens"] - rep["completed"]
    assert en["total_energy_uj"] == pytest.approx(
        hw["energy_uj_bit_shift"], abs=1e-6)


def test_retract_fields_are_aliases_and_never_diverge(traced_run):
    eng, rep = traced_run
    assert rep["speculative"]["retracts"] == rep["pool"]["retracts"]
    assert rep["speculative"]["retracted_blocks"] == \
        rep["pool"]["retracted_blocks"]
    assert eng.metrics.get("speculative.retracts").alias_of == \
        "pool.retracts"
    # same source by construction: bump the canonical counter and both
    # views move together
    eng.pool.stats.retracts += 1
    try:
        assert eng.metrics.get("speculative.retracts").value() == \
            eng.metrics.get("pool.retracts").value()
    finally:
        eng.pool.stats.retracts -= 1


def test_trace_exports_valid_chrome_json(tmp_path, traced_run):
    eng, rep = traced_run
    path = tmp_path / "trace.json"
    obj = eng.tracer.export(str(path))
    assert validate_chrome_trace(obj) == []
    assert validate_chrome_trace(json.loads(path.read_text())) == []
    names = {e["name"] for e in obj["traceEvents"]}
    # span taxonomy: dispatches, scheduler, pool and cache all present
    assert "ragged_step" in names
    assert "sched.admit" in names and "sched.finish" in names
    assert "pool.alloc" in names and "pool.free" in names
    assert "cache.lookup" in names
    assert {f"req {i}" for i in range(4)} <= names
    steps = [e for e in obj["traceEvents"] if e["name"] == "ragged_step"]
    assert len(steps) == rep["ragged_steps"]
    assert sum(e["args"]["compile"] for e in steps) == \
        len([k for k in rep["step_shapes"] if k.startswith("ragged_")])
    for e in steps:
        assert e["args"]["real_tokens"] + e["args"]["padded_tokens"] > 0
    assert sum(e["args"]["real_tokens"] for e in steps) == \
        rep["dispatched_tokens"] - rep["padded_tokens"]


def test_drafter_stats_surface_in_report(traced_run):
    eng, rep = traced_run
    sp = rep["speculative"]
    assert sp["drafter_calls"] == eng.drafter.stats.calls > 0
    assert sp["drafter_proposed"] == eng.drafter.stats.proposed
    assert sp["drafter_empty"] == eng.drafter.stats.empty
    assert sp["drafter_calls"] >= sp["drafter_empty"]
    # every proposed token was either truncated by the engine's budget
    # or drafted into a verify step
    assert sp["drafted_tokens"] <= sp["drafter_proposed"]


def test_prometheus_exposition_from_engine(traced_run):
    eng, rep = traced_run
    text = eng.metrics.to_prometheus()
    assert f"\ngen_tokens {rep['gen_tokens']}\n" in text
    assert "# TYPE pool_allocs counter" in text
    assert "energy_total_quant_ops" in text
    assert 'energy_unit_info{value="bit_shifting"} 1' in text


def test_reset_metrics_clears_obs_state(traced_run):
    eng, _ = traced_run
    assert eng.tracer.n_emitted > 0
    assert eng.energy.total_quant_ops > 0
    eng.reset_metrics()
    assert eng.tracer.n_emitted == 0 and not eng.tracer.timelines
    assert eng.energy.total_quant_ops == 0
    assert eng.drafter.stats.calls == 0
    rep = eng.report()                 # fresh report stays well-defined
    assert rep["completed"] == 0
    assert rep["ttft_s"]["p50"] is None
    assert rep["energy"]["proxy_uj_per_token"] is None
    assert rep["timeline"]["requests"] == 0


def test_disabled_sections_surface_as_none():
    # engine construction only (no dispatch): report must still be
    # complete, with the off sections explicit None per the legacy shape
    import jax
    from repro.configs import get_smoke_config
    from repro.core.qmodel import QuantContext, QuantMode
    from repro.models import model as M
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(
        get_smoke_config("qwen3_1_7b").scaled(dtype="float32"),
        kv_cache_bits=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, QuantContext(mode=QuantMode.FP),
                        n_slots=2, block_size=8, max_model_len=32,
                        spec_k=0, prefix_cache=False)
    rep = eng.report()
    assert rep["speculative"] is None
    assert rep["prefix_cache"] is None
    assert "speculative.spec_k" not in eng.metrics.names()
    errs = diff_schema(schema_of(eng.metrics), spec=False, cache=False)
    assert errs == [], "\n".join(errs)
    assert rep["obs"]["trace_enabled"] is False
    assert rep["energy"]["total_quant_ops"] == 0


# ---------------------------------------------------------------------------
# satellite coverage (ISSUE 9): ring metrics, escaping, percentile
# contract, chrome-validator edge cases
# ---------------------------------------------------------------------------

def test_ring_overflow_moves_dropped_counter_metrics():
    # a DELIBERATELY tiny ring: the registry-facing counter and the
    # occupancy gauge must track the overflow, not just tracer attrs
    import jax
    from repro.configs import get_smoke_config
    from repro.core.qmodel import QuantContext, QuantMode
    from repro.models import model as M
    from repro.serving import Request, ServingEngine

    cfg = dataclasses.replace(
        get_smoke_config("qwen3_1_7b").scaled(dtype="float32"),
        kv_cache_bits=8)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, QuantContext(mode=QuantMode.FP),
                        n_slots=2, block_size=8, max_model_len=32,
                        trace=True, trace_capacity=8)
    assert eng.metrics.get_value("obs.trace_dropped_total") == 0
    assert eng.metrics.get_value("obs.trace_ring_used") == 0.0
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=6).astype(np.int32),
                    max_new_tokens=4, arrival=0.0) for i in range(3)]
    eng.run(reqs)
    dropped = eng.metrics.get_value("obs.trace_dropped_total")
    assert dropped == eng.tracer.dropped > 0
    assert eng.metrics.get_value("obs.trace_ring_used") == 1.0
    rep = eng.report()
    assert rep["obs"]["trace_dropped_total"] == dropped
    assert rep["obs"]["trace_ring_used"] == 1.0
    eng.reset_metrics()
    assert eng.metrics.get_value("obs.trace_dropped_total") == 0
    assert eng.metrics.get_value("obs.trace_ring_used") == 0.0


def test_prometheus_escaping_round_trips_pathological_strings():
    # prometheus 0.0.4 text format: HELP escapes backslash + newline,
    # label values escape backslash + double-quote + newline.  A parser
    # applying the spec's unescaping must recover the originals.
    nasty_help = 'multi\nline "quoted" back\\slash help'
    nasty_value = 'path\\to\n"thing"'
    m = MetricsRegistry()
    m.counter("nasty.ops", nasty_help, label_names=("k",)).inc(2, k="a\nb")
    m.func("nasty.mode", "mode str", lambda: nasty_value)
    text = m.to_prometheus()
    for line in text.splitlines():
        assert "\r" not in line
    help_line = next(l for l in text.splitlines()
                     if l.startswith("# HELP nasty_ops "))
    escaped = help_line[len("# HELP nasty_ops "):]
    assert "\n" not in escaped
    # spec unescape for HELP: \\ -> \, \n -> newline
    out, i = [], 0
    while i < len(escaped):
        if escaped[i] == "\\" and i + 1 < len(escaped):
            out.append({"n": "\n", "\\": "\\"}[escaped[i + 1]])
            i += 2
        else:
            out.append(escaped[i])
            i += 1
    assert "".join(out) == nasty_help
    series = next(l for l in text.splitlines()
                  if l.startswith("nasty_ops{"))
    assert 'k="a\\nb"' in series and series.endswith(" 2")
    info = next(l for l in text.splitlines()
                if l.startswith("nasty_mode_info{"))
    val = info[info.index('value="') + len('value="'):info.rindex('"')]
    assert (val.replace("\\\\", "\x00").replace('\\"', '"')
            .replace("\\n", "\n").replace("\x00", "\\") == nasty_value)


def test_histogram_percentile_vs_exact_error_bound():
    # the documented contract (Histogram.percentile docstring and
    # Tracer.derive_latencies docstring both cite this test): the
    # bucket-bound percentile is >= the exact rank statistic and
    # overshoots by AT MOST one bucket width; derive_latencies keeps
    # the exact raw samples.
    rng = np.random.default_rng(3)
    samples = rng.uniform(0.0, 0.1, size=97)
    width = 0.01
    edges = [width * k for k in range(1, 11)]      # covers [0, 0.1]
    h = Histogram("h", "lat", buckets=edges)
    for v in samples:
        h.observe(float(v))
    srt = np.sort(samples)
    for q in (1, 10, 25, 50, 75, 90, 99):
        rank = max(1, math.ceil(q / 100.0 * len(srt)))
        exact = float(srt[rank - 1])
        bb = h.percentile(q)
        assert bb >= exact, f"p{q} under-reported: {bb} < {exact}"
        assert bb - exact <= width + 1e-12, \
            f"p{q} error {bb - exact} exceeds one bucket width"
    # exact side of the contract: timelines hand back raw samples
    tr = Tracer(capacity=4, enabled=False)
    tr.req_submit(0, arrival=0.0)
    tr.req_mark(0, "first_token", 0.012)
    tr.req_done(0, 0.05, n_generated=3)
    lat = tr.derive_latencies()
    assert lat["ttft"] == [0.012] and lat["e2e"] == [0.05]


def test_validate_chrome_trace_accepts_edge_cases():
    # empty trace: a capture with zero events is still a valid trace
    assert validate_chrome_trace({"traceEvents": []}) == []
    # events-only object: otherData/displayTimeUnit are optional
    events_only = {"traceEvents": [
        {"name": "e", "ph": "i", "ts": 5.0, "pid": 0, "tid": 1, "s": "t"}]}
    assert validate_chrome_trace(events_only) == []
    # out-of-order timestamps are legal — the chrome loader sorts; the
    # validator must be order-agnostic
    shuffled = {"traceEvents": [
        {"name": "b", "ph": "X", "ts": 900.0, "dur": 1.0,
         "pid": 0, "tid": 0},
        {"name": "a", "ph": "i", "ts": 1.0, "pid": 0, "tid": 0, "s": "t"},
        {"name": "M", "ph": "M", "ts": 0, "pid": 0, "tid": 0,
         "args": {"name": "proc"}}]}
    assert validate_chrome_trace(shuffled) == []
