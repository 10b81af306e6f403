"""The program's spans and scopes read back from a trace: the XSpace
metadata reader, the scope of an HLO op path, the reduction to the five
numbers of the program's spans on a hand-made trace, and the engine on
the window's clock through a tiny run on the CPU."""
import types
from pathlib import Path

import pytest

from bench import program_spans as ps
from bench import run
from bench.tests._tiny import tiny_cell

DATA = Path(__file__).resolve().parent / "data"
STEP = "jit_ragged_sampled_step(1)"
PLANE = "/device:TPU:0"
PHASES = ["engine.admit", "engine.plan", "engine.pack", "engine.transfer",
          "engine.launch", "engine.sync", "engine.commit"]

XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion()"
    stats { metadata_id: 10 str_value:
      "jit(ragged_sampled_step)/layers/while/body/mlp/dot_general" } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.3 = s8[8] copy()"
    stats { metadata_id: 10 ref_value: 12 } } }
  event_metadata { key: 3 value { id: 3
    name: "%copy-done = s8[8] copy-done()" } }
  event_metadata { key: 4 value { id: 4 name: "jit_ragged_sampled_step(1)" } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 12 value { id: 12
    name: "jit(ragged_sampled_step)/layers/while/body/dynamic_update_slice" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 3
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.step" } }
  event_metadata { key: 2 value { id: 2 name: "engine.plan" } }
}
'''


@pytest.fixture(scope="module")
def xspace():
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(XSPACE)


def test_metadata_stats_read_str_and_ref_values(xspace):
    got = ps.metadata_stats(xspace)
    assert list(got) == [PLANE]               # host planes are not read
    ops = got[PLANE]
    assert ops["%fusion.1 = f32[8] fusion()"]["tf_op"].endswith(
        "mlp/dot_general")
    assert ops["%copy.3 = s8[8] copy()"]["tf_op"].endswith(
        "body/dynamic_update_slice")
    assert ops["%copy-done = s8[8] copy-done()"] == {}


@pytest.mark.parametrize("path,scope", [
    ("jit(ragged_sampled_step)/layers/while/body/mlp/dot_general", "mlp"),
    ("jit(ragged_sampled_step)/layers/while/body/closed_call/attention/"
     "ragged_paged_flash/pallas_call", "attention"),
    ("jit(ragged_sampled_step)/layers/while/body/dynamic_slice", "layers"),
    ("jit(ragged_sampled_step)/sample/verify_tokens/jit(_take)/gather",
     "sample"),
    ("jit(ragged_sampled_step)/transpose", ps.UNSCOPED),
    ("", ps.UNSCOPED), (None, ps.UNSCOPED)])
def test_scope_is_the_innermost_program_scope(path, scope):
    assert ps.scope_of(path) == scope


def test_extract_gives_each_op_one_scope(xspace):
    from jax.profiler import ProfileData
    ev = ps.extract(ProfileData.from_serialized_xspace(xspace), xspace)
    ops = ev["devices"][PLANE]["ops"]
    assert [(o[0].split(" = ")[0], o[3]) for o in ops] == [
        ("%fusion.1", "mlp"), ("%copy.3", "layers"),
        ("%copy-done", ps.UNSCOPED)]
    assert [p[0] for p in ev["phases"]] == ["engine.plan"]


def hand_trace():
    """Two step executions; three engine.step spans, one without a
    launch.  Times in seconds."""
    def step(t0, phases):
        out, t = [], t0
        for name, d in phases:
            out.append([name, t, d])
            t += d
        return [["engine.step", t0, t - t0 + 0.001]] + out
    a = [("engine.admit", .001), ("engine.plan", .002), ("engine.pack", .003),
         ("engine.transfer", .004), ("engine.launch", .005),
         ("engine.sync", .4), ("engine.commit", .006)]
    b = [("engine.admit", .002), ("engine.plan", .004), ("engine.pack", .001),
         ("engine.transfer", .002), ("engine.launch", .003),
         ("engine.sync", .5), ("engine.commit", .010)]
    c = [("engine.admit", .001), ("engine.plan", .001)]
    phases = step(-0.2, a) + step(1.0, b) + step(1.6, c)
    return {"devices": {PLANE: {
        "modules": [[STEP, 0.0, 1.0], [STEP, 1.5, 1.0]],
        "ops": [["%while.1 = (...) while(...)", 0.0, 1.0, "layers"],
                ["%ragged_paged_flash.3 = bf16[8,2048] custom-call()", 0.1,
                 0.5, "attention"],
                ["%copy.2 = s8[8] copy()", 0.6, 0.2, "layers"],
                ["%fusion.2 = f32[8] fusion()", 0.8, 0.1, ps.UNSCOPED],
                ["%fusion.9 = f32[8] fusion()", 1.5, 0.25, "mlp"],
                ["%dynamic-slice.1 = s8[8] dynamic-slice()", 1.8, 0.3,
                 "layers"],
                ["%fusion.4 = f32[8] fusion()", 2.2, 0.2, "head"]]}},
        "host": phases, "phases": phases}


def test_reduce_hand_trace_gives_each_reading():
    red = ps.reduce(hand_trace())
    assert red["scope_s"] == pytest.approx(
        {"attention": 0.5, "layers": 0.5, ps.UNSCOPED: 0.1, "mlp": 0.25,
         "head": 0.2})                         # no while
    assert [s["busy_s"] for s in red["steps"]] == pytest.approx([1.0, 0.75])
    # the third engine.step launched nothing and is left out
    assert len(red["engine_steps"]) == 2
    assert ps.host_plan_ms(red) == pytest.approx(4.5)      # 3 and 6 ms
    assert ps.host_prepare_ms(red) == pytest.approx(9.0)   # 12 and 6 ms
    assert ps.host_commit_ms(red) == pytest.approx(8.0)    # 6 and 10 ms
    # copy + fusion.2, then dynamic-slice: 0.3 s of each 1 s step
    assert ps.step_self_ms(red) == pytest.approx(300.0)
    assert red["driver_s"] == pytest.approx([1.0 - 0.222, 1.6 - 1.523])
    # each sync against the execution that starts nearest its launch's
    # end (the first step's launch ends at -0.185, the second's at 1.012)
    assert red["sync_overhang_s"] == pytest.approx([0.4 - 1.0, 0.5 - 1.0])


def test_readings_are_none_without_spans_or_scopes():
    red = ps.reduce({"devices": {}, "host": []})
    assert ps.host_plan_ms(red) is None and ps.host_commit_ms(red) is None
    assert ps.step_self_ms(red) is None


def test_queue_wait_counts_requests_due_in_the_window():
    tl = types.SimpleNamespace
    timelines = [tl(arrival=-1.0, admit=5.0),          # pre-roll
                 tl(arrival=0.0, admit=0.1),
                 tl(arrival=1.0, admit=1.2),
                 tl(arrival=2.0, admit=2.3),
                 tl(arrival=2.5, admit=None),
                 tl(arrival=3.0, admit=3.0)]           # after the window
    assert ps.queue_wait_p90_ms(timelines, 3.0) == pytest.approx(280.0)
    assert ps.queue_wait_p90_ms(timelines[:1], 3.0) is None


def test_engine_runs_on_the_window_clock_on_the_cpu():
    """A tiny open-loop window: the engine stamps admissions on the clock
    that stamps arrivals, so every queue wait is small and not negative."""
    cell = tiny_cell()
    ref = run.load_module(run.BENCH / "references" / "qwen3.py",
                          "bench_reference_qwen3")
    engine = run.build_engine(cell, ref, 7)["engine"]
    vocab = cell.config["vocab_size"]
    run.warm_up(engine, cell, vocab)
    seen = {}
    reset = engine.reset_metrics

    def keep():
        seen["tl"] = list(engine.tracer.timelines.values())
        seen["clock"] = engine.clock
        reset()
    engine.reset_metrics = keep
    out = ps.measure(cell, engine, 7, 2.0, False, vocab)
    assert out["failed"] == 0 and out["metrics"]["itl_p50_s"] > 0
    assert getattr(seen["clock"], "__self__", None).__class__.__name__ \
        == "Window"
    waits = [t.admit - t.arrival for t in seen["tl"] if t.admit is not None]
    assert waits and min(waits) >= 0.0 and max(waits) < 30.0
    assert out["queue_wait_p90_ms"] is not None
    assert engine.sched.idle


def test_scoped_fixture_gives_each_op_one_scope_summing_to_busy():
    """Two ragged steps of the W8A8 cell recorded on a TPU v5e with op
    scopes and the engine's phases (``data/trace_scoped_steps.json.gz``):
    each op of a step has one scope, the scopes' seconds add up to the
    step's busy time, and the kernels lie in the scopes that call them."""
    from bench.trace_reduce import short_name
    ev = ps.read_fixture(DATA / "trace_scoped_steps.json.gz")
    (d,) = ev["devices"].values()
    assert {op[3] for op in d["ops"]} <= set(ps.SCOPES) | {ps.UNSCOPED}
    red = ps.reduce(ev)
    assert len(red["steps"]) == 2
    for st in red["steps"]:
        assert sum(st["scopes"].values()) == pytest.approx(st["busy_s"],
                                                           rel=0.01)
    where, calls = {}, {}
    for name, _, _, scope in d["ops"]:
        k = short_name(name)
        where.setdefault(k, set()).add(scope)
        calls[k] = calls.get(k, 0) + 1
    assert where["ragged_paged_flash"] == {"attention"}
    assert where["int8_matmul"] == {"attn_qkv", "attn_out", "mlp", "head"}
    assert where["constant_dynamic-update-slice_fusion"] == {"layers"}
    # 28 layers: one attention call and seven int8 matmuls per layer per
    # step, and the LM head
    assert calls["ragged_paged_flash"] == 2 * 28
    assert calls["int8_matmul"] == 2 * (28 * 7 + 1)
    # the first step's seven phases; the second's commit ends after the
    # recorded span of host time
    assert sorted(red["engine_steps"][0]) == sorted(PHASES)
    assert ps.step_self_ms(red) > 0.0
