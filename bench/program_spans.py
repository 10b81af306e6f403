#!/usr/bin/env python3
"""The program's own spans and scopes in a profiler trace.

The serving program names its work in two ways:

- host phases: each ``ServingEngine.step`` call is one ``engine.step``
  annotation whose children are ``engine.admit``, ``engine.plan``,
  ``engine.pack``, ``engine.transfer``, ``engine.launch``,
  ``engine.sync`` and ``engine.commit`` (``Tracer.phase``, on the
  profiler's clock while a capture is on);
- model scopes: ``jax.named_scope``s in the ragged step (``embed``,
  ``layers``, and in each layer ``attn_qkv``, ``kv_write``,
  ``attention``, ``attn_out``, ``mlp``; then ``head`` and ``sample``),
  which XLA keeps in each op's HLO ``op_name`` metadata.  A TPU trace
  carries that path in the ``tf_op`` stat of the op's event metadata.

``bench/trace_reduce.py`` reads neither.  This module reads both from
the same trace, and the request timelines from the engine, and reduces
them to the numbers five per-layer metrics would read:

- ``host_plan_ms``: median over traced steps of admit + plan;
- ``host_prepare_ms``: median of pack + transfer + launch;
- ``host_commit_ms``: median of commit;
- ``queue_wait_p90_ms``: 90th percentile of admission minus arrival,
  over the requests due in the window (engine on the window's clock);
- ``step_self_ms``: device ms per step of the step's ops that no child
  scope claims (innermost scope ``layers``, or no program scope).

Run on the chip, it measures one cell as ``bench/run.py`` does, with
the program's spans and scopes besides:

    python3 bench/program_spans.py --workload <cell> --seeds N [N ...] \\
        --modes 0,1 [--seconds 51] [--fixture PATH]

Each seed builds the engine once and runs one window per mode (0: no
trace, 1: a traced window as ``--trace 1``), and prints one JSON line
per window: the end-to-end metrics, the accepted per-layer metrics, the
five above, the device seconds per scope, the time the load loop
spends between steps, and how much longer the sync phase lasts than the
execution it waits for (``sync_overhang_ms``).  ``--fixture`` writes
two traced steps of the last traced window as a small test fixture.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import gzip
import json
import shutil
import sys
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import costs, run, trace_reduce  # noqa: E402

SCOPE_STAT = "tf_op"
SCOPES = ("embed", "layers", "attn_qkv", "kv_write", "attention", "attn_out",
          "mlp", "head", "sample")
UNSCOPED = "unscoped"
SELF = ("layers", UNSCOPED)         # the step's own ops: no child claims them
PHASE = "engine."
STEP = "engine.step"
PLAN = ("engine.admit", "engine.plan")
PREPARE = ("engine.pack", "engine.transfer", "engine.launch")
COMMIT = ("engine.commit",)


# -- reading the trace ----------------------------------------------------

def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of one protobuf message: an int for varint
    and fixed fields, a memoryview for length-delimited ones."""
    i, end = 0, len(b)
    while i < end:
        key, i = _varint(b, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wt == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wt == 5:
            v, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wt} at byte {i}")
        yield key >> 3, v


def metadata_stats(xspace: bytes, planes=trace_reduce.DEVICE_PLANE) -> dict:
    """{plane name: {event name: {stat name: value}}} of the stats the
    profiler keeps on each event's metadata (``XEventMetadata.stats``),
    which ``ProfileData`` does not expose, for the planes whose name
    matches ``planes``.  Reads the ``XSpace`` wire format: XSpace.planes
    = 1; XPlane name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata name = 2, stats = 5; XStat metadata_id = 1, str_value
    = 5, ref_value = 7 (a stat metadata id whose name is the string)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(v)
            elif g == 5:
                for k, meta in _fields(v):
                    if k == 2:
                        d = dict(_fields(meta))
                        stat_names[d.get(1, 0)] = bytes(
                            d.get(2, b"")).decode()
        if not planes.match(name):
            continue
        per = out[name] = {}
        for entry in events:
            meta = dict(_fields(entry)).get(2)
            if meta is None:
                continue
            ev_name, stats = "", {}
            for g, v in _fields(meta):
                if g == 2:
                    ev_name = bytes(v).decode(errors="replace")
                elif g == 5:
                    s = dict(_fields(v))
                    key = stat_names.get(s.get(1), str(s.get(1)))
                    if 5 in s:
                        stats[key] = bytes(s[5]).decode(errors="replace")
                    elif 7 in s:
                        stats[key] = stat_names.get(s[7], "")
                    else:
                        stats[key] = next((s[k] for k in (4, 3, 2, 6)
                                           if k in s), None)
            per[ev_name] = stats
    return out


def scope_of(op_name: str | None) -> str:
    """The innermost program scope in an HLO ``op_name`` path
    (``jit(ragged_sampled_step)/layers/while/body/mlp/dot_general`` ->
    ``mlp``), or ``unscoped``."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def extract(profile, xspace: bytes) -> dict:
    """``trace_reduce.extract``'s events with each device op's scope
    appended, and the host's ``engine.*`` spans under ``phases``."""
    events = trace_reduce.extract(profile)
    stats = metadata_stats(xspace)
    for plane, d in events["devices"].items():
        scopes = {name[:trace_reduce.NAME_CHARS]: scope_of(s.get(SCOPE_STAT))
                  for name, s in stats.get(plane, {}).items()}
        for op in d["ops"]:
            op.append(scopes.get(op[0], UNSCOPED))
    events["phases"] = [e for e in events["host"]
                        if e[0].startswith(PHASE)]
    return events


def load(trace_dir: str) -> dict:
    """``extract`` of the one profile the JAX profiler wrote under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    path = max(Path(trace_dir).rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime)
    raw = path.read_bytes()
    return extract(ProfileData.from_serialized_xspace(raw), raw)


# -- reducing it ----------------------------------------------------------

def reduce(events: dict) -> dict:
    """Per traced step execution its device seconds by scope; the device
    seconds of each scope in the window; per ``engine.step`` span that
    launched a step in the window, the seconds of each of its phases;
    and the load loop's time between consecutive ``engine.step`` spans."""
    scope_s, per_step, windows = {}, [], []
    for d in events["devices"].values():
        ex = sorted([s, dur] for name, s, dur in d["modules"]
                    if trace_reduce.STEP_MODULE in name)
        if not ex:
            continue
        windows.append((ex[0][0], ex[-1][0] + ex[-1][1]))
        for s0, dur0 in ex:
            mine, ivs = {}, []
            for name, s, dur, scope in d["ops"]:
                if s < s0 or s + dur > s0 + dur0:
                    continue
                ivs.append((s, dur))
                if trace_reduce.short_name(name).startswith(
                        trace_reduce.WRAPPERS):
                    continue
                mine[scope] = mine.get(scope, 0.0) + dur
                scope_s[scope] = scope_s.get(scope, 0.0) + dur
            busy = sum(b - a for a, b in trace_reduce.union(ivs))
            per_step.append({"scopes": mine, "busy_s": busy})
    lo = min((a for a, _ in windows), default=0.0)
    hi = max((b for _, b in windows), default=0.0)
    execs = sorted(x for d in events["devices"].values()
                   for x in ([s, dur] for name, s, dur in d["modules"]
                             if trace_reduce.STEP_MODULE in name))
    phases = events.get("phases", [])
    spans = sorted((s, dur) for name, s, dur in phases if name == STEP)
    steps, driver, overhang = [], [], []
    for (s0, d0), nxt in zip(spans, spans[1:] + [None]):
        if s0 > hi or s0 + d0 < lo:
            continue
        inner, at = {}, {}
        for name, s, dur in phases:
            if name != STEP and s0 <= s and s + dur <= s0 + d0:
                inner[name] = inner.get(name, 0.0) + dur
                at[name] = s + dur
        if "engine.launch" not in inner:
            continue
        steps.append(inner)
        if nxt is not None:
            driver.append(nxt[0] - (s0 + d0))
        # the execution this step launched: the one that starts nearest
        # the launch's end (host and device clocks differ by about a ms,
        # so only the two durations are compared)
        end = at["engine.launch"]
        ex = min(execs, key=lambda x: abs(x[0] - end), default=None)
        if ex is not None and "engine.sync" in inner:
            overhang.append(inner["engine.sync"] - ex[1])
    return {"scope_s": scope_s, "steps": per_step, "engine_steps": steps,
            "driver_s": driver, "sync_overhang_s": overhang}


def _median_ms(xs) -> float | None:
    return float(np.median(xs)) * 1e3 if xs else None


def _phases_ms(red: dict, names) -> float | None:
    return _median_ms([sum(st.get(n, 0.0) for n in names)
                       for st in red["engine_steps"]])


def host_plan_ms(red: dict) -> float | None:
    return _phases_ms(red, PLAN)


def host_prepare_ms(red: dict) -> float | None:
    return _phases_ms(red, PREPARE)


def host_commit_ms(red: dict) -> float | None:
    return _phases_ms(red, COMMIT)


def step_self_ms(red: dict) -> float | None:
    steps = red["steps"]
    if not steps or not any(st["scopes"] for st in steps):
        return None
    own = sum(st["scopes"].get(k, 0.0) for st in steps for k in SELF)
    return own / len(steps) * 1e3


def queue_wait_p90_ms(timelines, seconds: float) -> float | None:
    """From the engine's request timelines, on the window's clock (0 is
    when the window opens)."""
    waits = [tl.admit - tl.arrival for tl in timelines
             if 0.0 <= tl.arrival < seconds and tl.admit is not None]
    return float(np.percentile(waits, 90)) * 1e3 if waits else None


# -- one cell on the chip -------------------------------------------------

@contextlib.contextmanager
def engine_on_window_clock():
    """While inside, the benchmark's window hands the engine its clock
    as it opens, so the engine's timelines lie on the clock that stamps
    each request's arrival (its due time)."""
    from unittest import mock

    from bench import drive
    init = drive.Window.__init__

    def on_window_clock(self, engine, *a, **kw):
        init(self, engine, *a, **kw)
        engine.clock = self.now
    with mock.patch.object(drive.Window, "__init__", on_window_clock):
        yield


def measure(cell, engine, seed: int, seconds: float, trace: bool,
            vocab: int, fixture: str | None = None) -> dict:
    """One window of ``cell`` on ``engine``; the engine is drained and
    reset after it."""
    import jax
    with engine_on_window_clock():
        res = run.run_window(cell, engine, seed, seconds, trace, vocab)
    metrics, attempted, failed = run.end_to_end(cell, res, seconds)
    out = {"workload": cell.name, "seed": seed, "trace": int(trace),
           "attempted": attempted, "failed": failed,
           "metrics": {k: v["value"] for k, v in metrics.items()},
           "itl_p50_traced_s": traced_itl(res, seconds)}
    timelines = list(engine.tracer.timelines.values())
    out["queue_wait_p90_ms"] = queue_wait_p90_ms(timelines, seconds)
    if trace:
        red = trace_reduce.reduce_dir(res["trace_dir"])
        events = load(res["trace_dir"])
        shutil.rmtree(res["trace_dir"], ignore_errors=True)
        mine = reduce(events)
        kind = jax.devices()[0].device_kind
        x = run.LayerInputs(
            trace=red, counters={"dispatched_tokens": res["dispatched"],
                                 "padded_tokens": res["padded"]},
            steps=res["steps"], dims=costs.Dims.from_config(cell.config),
            peaks=costs.peaks(kind), w8a8=bool(cell.config["w8a8"]),
            kv_bits=cell.config["kv_bits"])
        out["per_layer"] = {k: v["value"]
                            for k, v in run.per_layer(cell, x).items()}
        out.update(host_plan_ms=host_plan_ms(mine),
                   host_prepare_ms=host_prepare_ms(mine),
                   host_commit_ms=host_commit_ms(mine),
                   step_self_ms=step_self_ms(mine),
                   driver_ms=_median_ms(mine["driver_s"]),
                   sync_overhang_ms=_median_ms(mine["sync_overhang_s"]),
                   n_engine_steps=len(mine["engine_steps"]),
                   busy_s=red["busy_s"], window_s=red["window_s"],
                   scope_s=dict(sorted(mine["scope_s"].items(),
                                       key=lambda kv: -kv[1])),
                   scope_ops=scope_ops(events),
                   idle_gaps=red["idle_gaps"], top_ops=red["top_ops"])
        if fixture:
            write_fixture(events, fixture, f"{kind}, {cell.name}")
    while not engine.sched.idle:
        engine.step()
    engine.reset_metrics()
    return out


def traced_itl(res: dict, seconds: float) -> float | None:
    """Median token gap ending in the part of the window a ``--trace 1``
    run traces, with or without the trace on."""
    t_on = max(0.0, seconds / 2 - trace_reduce.TRACE_SECONDS / 2)
    t_off = t_on + min(trace_reduce.TRACE_SECONDS, seconds)
    gaps = [b - a for tr in res["tracks"]
            for a, b in zip(tr.stamps, tr.stamps[1:]) if t_on <= b < t_off]
    return float(np.median(gaps)) if gaps else None


def scope_ops(events: dict, top: int = 4) -> dict:
    """Per scope, the op names (``trace_reduce.short_name``) that took
    most of its device seconds in the step executions."""
    per = {}
    for d in events["devices"].values():
        ex = [(s, s + dur) for name, s, dur in d["modules"]
              if trace_reduce.STEP_MODULE in name]
        lo, hi = min(a for a, _ in ex), max(b for _, b in ex)
        for name, s, dur, scope in d["ops"]:
            k = trace_reduce.short_name(name)
            if lo <= s and s + dur <= hi \
                    and not k.startswith(trace_reduce.WRAPPERS):
                ops = per.setdefault(scope, {})
                ops[k] = ops.get(k, 0.0) + dur
    return {s: sorted(ops.items(), key=lambda kv: -kv[1])[:top]
            for s, ops in per.items()}


def write_fixture(events: dict, path: str, source: str) -> None:
    """Two consecutive step executions from the middle of the trace:
    their device ops with scopes, the module events, and the host's
    ``engine.*`` spans around them, times in seconds from the first."""
    (plane, d), = [(p, d) for p, d in events["devices"].items()
                   if any(trace_reduce.STEP_MODULE in m[0]
                          for m in d["modules"])]
    ex = sorted(m for m in d["modules"] if trace_reduce.STEP_MODULE in m[0])
    mid = len(ex) // 2 - 1
    a, b = ex[mid], ex[mid + 1]
    lo, hi = a[1], b[1] + b[2]
    t0 = lo
    names, scopes, host_names = {}, {}, {}

    def idx(table, k):
        return table.setdefault(k, len(table))
    ops = [[idx(names, n[:200]), s - t0, dur, idx(scopes, sc)]
           for n, s, dur, sc in d["ops"] if lo <= s and s + dur <= hi]
    mods = [[idx(names, n[:200]), s - t0, dur] for n, s, dur in d["modules"]
            if s < hi and s + dur > lo]
    host = [[idx(host_names, n), s - t0, dur]
            for n, s, dur in events["phases"]
            if s < hi and s + dur > lo - 0.05]
    obj = {"source": f"{source}: two ragged steps of a profiler trace with "
                     f"op scopes and the engine's phases; ops as [name, "
                     f"start s, duration s, scope], times from the first "
                     f"step's start",
           "plane": plane, "names": list(names), "scopes": list(scopes),
           "host_names": list(host_names), "ops": ops, "modules": mods,
           "host": host}
    with gzip.open(path, "wt") as f:
        json.dump(obj, f, separators=(",", ":"))


def read_fixture(path) -> dict:
    """``extract``-shaped events of a fixture ``write_fixture`` wrote."""
    d = json.loads(gzip.open(path).read())
    n, k, h = d["names"], d["scopes"], d["host_names"]
    return {"devices": {d["plane"]: {
        "ops": [[n[i], s, t, k[j]] for i, s, t, j in d["ops"]],
        "modules": [[n[i], s, t] for i, s, t in d["modules"]]}},
        "host": [[h[i], s, t] for i, s, t in d["host"]],
        "phases": [[h[i], s, t] for i, s, t in d["host"]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--modes", default="0,1")
    ap.add_argument("--fixture")
    args = ap.parse_args(argv)
    modes = [bool(int(m)) for m in args.modes.split(",")]
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    from repro.launch import serve
    if jax.devices()[0].platform != "tpu":
        print("program_spans: no TPU", file=sys.stderr)
        return 2
    serve.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = run.find_cell(args.workload)
    ref = run.load_module(
        run.BENCH / "references" / f"{cell.config['reference']}.py",
        "bench_reference")
    vocab = cell.config["vocab_size"]
    for seed in args.seeds:
        engine = run.build_engine(cell, ref, seed)["engine"]
        run.warm_up(engine, cell, vocab)
        for trace in modes:
            out = measure(cell, engine, seed, args.seconds, trace, vocab,
                          args.fixture if trace else None)
            print(json.dumps(out), flush=True)
        del engine
        gc.collect()        # the engine and its window clock hold a cycle
    return 0


if __name__ == "__main__":
    sys.exit(main())
