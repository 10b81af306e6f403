#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <config>.<mix> --seed N --seconds S --trace 0|1

Runs one cell of ``BENCHMARK.json`` on the chip it is started on: builds the
serving engine through the program's normal entry point
(``repro.launch.serve.serve_engine``) with weights the benchmark makes from
the seed, warms every stream shape the cell's traffic uses, drives the
engine on the benchmark's own clock for ``--seconds`` (open or closed loop,
as the mix file says), then checks the served tokens against the plain
float32 reference.  ``setup_s`` runs from process start to the first
request sent; the traffic's lead-in before the window opens (an open
loop's pre-roll, a closed loop's first prefills) is neither set-up nor
measured.  The last stdout line is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks`` (each number compared
beside its limit, also printed as the last lines of stderr).

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name: ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json``, ``bench/metrics/<metric>.py`` and
``bench/references/<reference>.py``.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits nonzero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import costs, drive, trace_reduce  # noqa: E402
from bench.traffic import generate  # noqa: E402


class BenchError(Exception):
    """The run cannot give a result (no chip, a missing file, ...)."""


# -- finding a cell's pieces by name -----------------------------------------

def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file (published keys + ours)
    mix: dict               # the traffic mix file
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def find_cell(name: str, root: Path = ROOT) -> Cell:
    spec_path = root / "BENCHMARK.json"
    if not spec_path.is_file():
        raise BenchError(f"no BENCHMARK.json in {root}")
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / cfgs[w["config"]]["file"]).read_text())
    mix = generate.load_mix(w["traffic"], root / "bench" / "traffic")

    def mine(m):
        return name in m.get("workloads", [name])
    return Cell(name=name, chips=w["chips"], config=config, mix=mix,
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


# -- the system under test ---------------------------------------------------

# The program's own PRNG seed (sampling keys, the W8A8 calibration batch).
# It is fixed, not drawn from ``--seed``: the engine bakes its key into the
# compiled step, so a seed-drawn one would compile every program anew in
# every run.  Decoding is greedy, so the key never changes a token; the
# weights and the traffic still come from ``--seed``.
PROGRAM_SEED = 0


def program_tree(ref, hf: dict, weights: dict, shapes) -> object:
    """The benchmark's weights under the program's leaf names, checked
    leaf by leaf against the program's own parameter shapes and dtypes."""
    import jax
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves, used = [], set()
    for path, sd in flat:
        nm = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path)
        if nm not in ref.PROGRAM_NAMES:
            raise BenchError(f"the program's parameter {nm!r} has no "
                             f"counterpart in the reference")
        w = weights[ref.PROGRAM_NAMES[nm]]
        if tuple(w.shape) != tuple(sd.shape) or w.dtype != sd.dtype:
            raise BenchError(f"{nm}: program wants {sd.shape} {sd.dtype}, "
                             f"reference has {w.shape} {w.dtype}")
        leaves.append(w)
        used.add(ref.PROGRAM_NAMES[nm])
    if used != set(weights):
        raise BenchError(f"reference weights the program does not take: "
                         f"{sorted(set(weights) - used)}")
    return jax.tree_util.tree_unflatten(treedef, leaves)


@contextlib.contextmanager
def benchmark_weights(ref, hf: dict, seed: int):
    """While inside, the program's ``init_params`` returns the benchmark's
    weights for ``seed`` (made on the device in one jitted call) instead of
    its own: the program is loaded with them as with a checkpoint."""
    from unittest import mock
    from repro.models import model as M
    init = M.init_params

    def load(cfg, key):
        bad = ref.check_program_config(hf, cfg)
        if bad:
            raise BenchError("program config departs from the published "
                             "one: " + "; ".join(bad))
        import jax
        shapes = jax.eval_shape(lambda: init(cfg, key))
        return program_tree(ref, hf, ref.make_weights(hf, seed), shapes)

    with mock.patch.object(M, "init_params", load):
        yield


def build_engine(cell: Cell, ref, seed: int) -> dict:
    from repro.launch import serve
    c, mix = cell.config, cell.mix
    with benchmark_weights(ref, c, seed):
        return serve.serve_engine(
            c["arch"], smoke=bool(c.get("smoke", False)), requests=[],
            n_slots=mix["n_slots"], max_model_len=mix["max_model_len"],
            num_blocks=mix.get("num_blocks"),
            block_size=c["block_size"], chunk=c["chunk"],
            kv_bits=c["kv_bits"], w8a8=bool(c["w8a8"]),
            prompt_lens=(c["calibration_tokens"],),
            cfg_overrides=c.get("cfg_overrides"), seed=PROGRAM_SEED)


def stream_buckets(chunk: int, n_slots: int) -> list[int]:
    """The engine's padded stream lengths that this traffic can reach:
    powers of two from 8 up to the chunk, and the step's worst case
    round8(chunk + n_slots) (a full chunk beside decode rows)."""
    t_max = -(-(chunk + n_slots) // 8) * 8
    out, b = [], 8
    while b < t_max:
        out.append(b)
        b <<= 1
    return out + [t_max]


def warm_up(engine, cell: Cell, vocab: int) -> list[int]:
    """Compile (or load from the cache) every stream shape of the cell by
    serving one short request per shape, then reset the engine's counters
    and flush its prefix cache.  Returns the shapes the engine ran."""
    from repro.serving import Request
    rng = np.random.default_rng(0)
    chunk, n_slots = cell.config["chunk"], cell.mix["n_slots"]
    buckets = stream_buckets(chunk, n_slots)
    rid = -1

    def req(n, gen):
        nonlocal rid
        rid -= 1
        return Request(rid=rid, prompt=rng.integers(0, vocab, n)
                       .astype(np.int32), max_new_tokens=gen)

    def drain():
        while not engine.sched.idle:
            engine.step()

    for b in buckets[:-1]:
        engine.submit(req(b if b > 8 else 5, 2))
        drain()
    engine.submit(req(5, 4))                 # a decode row beside ...
    engine.step()
    engine.submit(req(chunk + 8, 2))         # ... a full chunk: t_max
    drain()
    shapes = sorted(k[1] for k in engine._step_times)
    engine.reset_metrics()
    return shapes


# -- the measured window -------------------------------------------------------

class StepLog:
    """Per-step work while the profiler traces: each live sequence's new
    rows [start, end) and the stream length the engine dispatched."""

    def __init__(self, engine):
        self.engine = engine
        self.steps = []
        self.active = False
        self._ctx = {}
        self._disp = 0

    def begin(self, live):
        self.active = True
        self._ctx = {tr.req.rid: tr.req.n_ctx for tr in live}
        self._disp = self.engine.dispatched_tokens

    def after_step(self, live, finished):
        if not self.active:
            return
        rows = []
        for tr in list(live) + list(finished):
            r = tr.req
            a, b = self._ctx.get(r.rid, 0), r.n_ctx
            if b > a:
                rows.append((a, b))
            self._ctx[r.rid] = b
        t_pad = self.engine.dispatched_tokens - self._disp
        self._disp = self.engine.dispatched_tokens
        if t_pad:
            self.steps.append({"t_pad": t_pad, "rows": rows})


# JAX's tracing, lowering and XLA compilation events since the listener
# went on: (perf_counter at the end, event, function name, seconds).  One
# that falls in the measured window is set-up work the warm-up missed; a
# program found in the persistent cache is traced and lowered all the same.
COMPILE_EVENTS: list = []
_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}


def watch_compiles() -> None:
    """Record JAX's compile events from now on; idempotent."""
    import jax
    if getattr(watch_compiles, "on", False):
        return

    def listener(event, duration, **kw):
        if event in _EVENTS:
            COMPILE_EVENTS.append((time.perf_counter(), _EVENTS[event],
                                   str(kw.get("fun_name")), duration))
    jax.monitoring.register_event_duration_secs_listener(listener)
    watch_compiles.on = True


def run_window(cell: Cell, engine, seed: int, seconds: float, trace: bool,
               vocab: int) -> dict:
    from repro.serving import Request
    mix = cell.mix
    log = StepLog(engine)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    t_on = max(0.0, seconds / 2 - trace_reduce.TRACE_SECONDS / 2)
    t_off = t_on + min(trace_reduce.TRACE_SECONDS, seconds)
    state = {"on": False, "off": False}

    def on_step(w, t, finished):
        if not trace:
            return
        import jax
        if state["on"] and not state["off"]:
            log.after_step(w.live, finished)
        if not state["on"] and t >= t_on:
            engine.profiler.profile_dir = tdir
            # host spans only: the Python tracer's per-call events would
            # slow the very host work the traced window measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            log.begin(w.live)
            state["on"] = True
        elif state["on"] and not state["off"] and t >= t_off:
            jax.profiler.stop_trace()
            engine.profiler.profile_dir = None
            log.active = False
            state["off"] = True

    def make_request(p, due):
        return Request(rid=p.rid, prompt=p.prompt,
                       max_new_tokens=p.max_new_tokens, temperature=0.0,
                       arrival=due)

    at_open = {}

    def on_open(w):
        at_open.update(t0=w.t0, dispatched=engine.dispatched_tokens,
                       padded=engine.padded_tokens,
                       usage=resource.getrusage(resource.RUSAGE_SELF))

    watch_compiles()
    n_events = len(COMPILE_EVENTS)
    pauses = []                        # (generation, seconds) in the window
    gc_start = [0.0]

    def gc_watch(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            pauses.append((info["generation"],
                           time.perf_counter() - gc_start[0]))
    gc.callbacks.append(gc_watch)
    try:
        if mix["loop"] == "open":
            planned = generate.open_loop(mix, seconds, seed, vocab)
            t_start = time.perf_counter()
            w, tracks = drive.open_loop(engine, planned, seconds,
                                        make_request, on_step, on_open)
        else:
            queues = generate.closed_loop(mix, seed, vocab)
            t_start = time.perf_counter()
            w, tracks = drive.closed_loop(engine, queues, seconds,
                                          make_request, on_step, on_open)
        usage = resource.getrusage(resource.RUSAGE_SELF)
    finally:
        gc.callbacks.remove(gc_watch)
        if state["on"] and not state["off"]:
            import jax
            jax.profiler.stop_trace()
            engine.profiler.profile_dir = None
    inside = [d for t, d in w.step_s if 0.0 <= t < seconds]
    u0 = at_open["usage"]
    host = {"cpu_user_s": usage.ru_utime - u0.ru_utime,
            "cpu_sys_s": usage.ru_stime - u0.ru_stime,
            "cpus": len(os.sched_getaffinity(0))}
    return {"window": w, "tracks": tracks, "t_start": t_start,
            "t_open": at_open["t0"], "host": host,
            "gc": pauses, "longest_step": max(inside, default=0.0),
            "longest_step_outside": max((d for t, d in w.step_s
                                         if not 0.0 <= t < seconds),
                                        default=0.0),
            "compiles": [(round(t - at_open["t0"], 3), kind, name,
                          round(d, 3))
                         for t, kind, name, d in COMPILE_EVENTS[n_events:]],
            "dispatched": engine.dispatched_tokens - at_open["dispatched"],
            "padded": engine.padded_tokens - at_open["padded"],
            "steps": log.steps, "trace_dir": tdir}


def percentile(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def end_to_end(cell: Cell, res: dict, seconds: float) -> tuple[dict, int, int]:
    """(metrics, attempted, failed) from the driver's stamps."""
    tracks = res["tracks"]
    w = res["window"]
    end = w.now()
    gaps, tokens = [], 0
    for tr in tracks:
        s = tr.stamps
        tokens += sum(1 for t in s if 0.0 <= t < seconds)
        gaps += [b - a for a, b in zip(s, s[1:]) if 0.0 <= b < seconds]
    if cell.mix["loop"] == "open":
        due = [tr for tr in tracks if 0.0 <= tr.due < seconds]
        attempted = len(due)
        failed = sum(1 for tr in due if tr.first is None)
        ttft = [(tr.first if tr.first is not None else end) - tr.due
                for tr in due]
    else:
        attempted = sum(1 for tr in tracks if tr.sent is not None
                        and tr.sent < seconds)
        failed = 0
        ttft = [tr.first - tr.due for tr in tracks
                if tr.first is not None and 0.0 <= tr.due < seconds]
    values = {
        "ttft_p50_s": percentile(ttft, 50) if ttft else None,
        "ttft_p75_s": percentile(ttft, 75) if ttft else None,
        "ttft_p90_s": percentile(ttft, 90) if ttft else None,
        "itl_p50_s": percentile(gaps, 50) if gaps else None,
        "itl_p95_s": percentile(gaps, 95) if gaps else None,
        "output_tokens_per_s": tokens / seconds,
    }
    res["n_gaps"], res["n_ttft"], res["n_tokens"] = len(gaps), len(ttft), tokens
    res["ttft_quartiles"] = ([percentile(ttft, q) for q in (25, 50, 75, 90)]
                             if ttft else [])
    out = {}
    for m in cell.end_to_end:
        if m["name"] in values and values[m["name"]] is not None:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out, attempted, failed


# -- per-layer metrics ---------------------------------------------------------

@dataclasses.dataclass
class LayerInputs:
    """What a per-layer metric reader gets."""
    trace: dict | None          # trace_reduce.reduce(...) of the traced window
    counters: dict              # engine counters over the whole window
    steps: list                 # StepLog.steps of the traced window
    dims: costs.Dims
    peaks: dict
    w8a8: bool
    kv_bits: int


def per_layer(cell: Cell, inputs: LayerInputs,
              metrics_dir: Path = BENCH / "metrics") -> dict:
    """Each of the cell's per-layer metrics, read by its own reader file
    ``<metrics_dir>/<name>.py``; a reader that returns None is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(metrics_dir / f"{m['name']}.py",
                          f"bench_metric_{m['name'].replace('.', '_')}")
        v = mod.read(inputs)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# -- correctness -----------------------------------------------------------------

def sample_requests(tracks, seed: int, target_tokens: int, max_requests: int):
    """Requests to check: the longest finished one (prompt + output), then
    others drawn from the seed, finished ones first, until
    ``target_tokens`` served tokens or ``max_requests`` requests.  Requests
    still decoding when the window closed are drawn only where the
    finished ones hold too few tokens (the longest of them first where none
    finished); their served tokens are checked as far as they go."""
    def longest_first(trs):
        return sorted(trs, key=lambda tr: (len(tr.req.prompt)
                                           + len(tr.req.generated),
                                           tr.req.rid), reverse=True)
    rng = np.random.default_rng([int(seed) % (1 << 63), 11])
    done = longest_first(tr for tr in tracks if tr.done)
    live = longest_first(tr for tr in tracks if not tr.done
                         and tr.req is not None and len(tr.req.generated) > 1)
    order = [done[0]] if done else live[:1]
    done, live = (done[1:], live) if done else (done, live[1:])
    order += [done[i] for i in rng.permutation(len(done))]
    order += [live[i] for i in rng.permutation(len(live))]
    pick, n = [], 0
    for tr in order:
        if n >= target_tokens or len(pick) >= max_requests:
            break
        pick.append(tr)
        n += len(tr.req.generated)
    return pick


def served(tr) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(tokens fed to the reference, rows whose logits chose a served
    token, the served tokens)."""
    p = np.asarray(tr.req.prompt, np.int32)
    g = np.asarray(tr.req.generated, np.int32)
    seq = np.concatenate([p, g[:-1]])
    rows = np.arange(len(p) - 1, len(seq))
    return seq, rows, g


def regrets(ref, hf, weights, sample, precision="fp32", picks=None):
    """Per sampled request, the reference's gap (best logit minus the
    logit of the token, in units of the row's logit standard deviation)
    of each served token, or with ``picks`` of the given tokens."""
    out = []
    for i, tr in enumerate(sample):
        seq, rows, g = served(tr)
        tok = g if picks is None else picks[i]
        r = ref.token_gaps(weights, hf, seq, rows, tok, precision=precision)
        out.append((r["top"] - r["picked"]) / r["std"])
    return out


def gap_stats(gaps) -> dict:
    """The numbers a configuration may compare, over every checked token:
    the widest gap, the mean gap, its 90th percentile, and the share of
    tokens (%) whose gap is over 1, 2 and 3 logit standard deviations."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    names = ("max_token_regret_std", "mean_token_regret_std",
             "p90_token_regret_std", "share_regret_over_1std",
             "share_regret_over_2std", "share_regret_over_3std")
    if not len(g):
        return dict.fromkeys(names, float("inf"))
    return dict(zip(names, (float(g.max()), float(g.mean()),
                            float(np.percentile(g, 90)),
                            *(float(100.0 * np.mean(g > k))
                              for k in (1.0, 2.0, 3.0)))))


def judge(cell: Cell, sample, gaps) -> dict:
    """Each number the configuration's ``correct.limits`` names, over the
    tokens' ``gaps``, beside its limit; the served tokens checked, beside
    ``min_tokens``; the finished requests in ``sample`` whose output has
    another length than asked (traffic keeps prompt + output within
    ``max_model_len``), beside 0.  A configuration without proven limits
    gives no result."""
    c = cell.config["correct"]
    if not c.get("limits"):
        raise BenchError(f"{cell.name}: the configuration states no proven "
                         f"correctness limits")
    stats = gap_stats(gaps)
    short = sum(1 for tr in sample if tr.done
                and len(tr.req.generated) != tr.planned.max_new_tokens)
    out = {k: {"value": stats[k], "limit": lim}
           for k, lim in c["limits"].items()}
    out["sampled_tokens_min"] = {"value": int(sum(len(g) for g in gaps)),
                                 "limit": c["min_tokens"]}
    out["short_outputs"] = {"value": short, "limit": 0}
    return out


def check(cell: Cell, ref, seed: int, tracks) -> dict:
    """The comparison that decides ``correct``: the served tokens of a
    sample drawn from the seed, under the float32 reference, judged by
    ``judge``."""
    c = cell.config["correct"]
    sample = sample_requests(tracks, seed, c["sample_tokens"],
                             c["max_requests"])
    weights = ref.make_weights(cell.config, seed)
    gaps = regrets(ref, cell.config, weights, sample)
    del weights
    return judge(cell, sample, gaps)


def passed(checks: dict) -> bool:
    """Every number at most its limit; ``*_min`` numbers at least it."""
    return all(v["value"] >= v["limit"] if k.endswith("_min")
               else v["value"] <= v["limit"] for k, v in checks.items())


# -- the run -------------------------------------------------------------------------

def device_name(dev: dict) -> str:
    return f"{dev['platform']} {dev['kind']} x{dev['count']}"


def device_info(jax, n: int) -> dict:
    devs = jax.devices()[:n]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n, "memory_peak_bytes": max(peaks) if peaks else None}


def serve_cell(cell: Cell, seed: int, seconds: float, trace: bool,
               chip_check: bool = True, compile_cache: bool = True) -> dict:
    """Everything of a run up to the correctness check: set-up, the
    window, the metrics.  Returns the result line without ``correct`` and
    ``checks``, the driver's tracks and the reference module; the engine is
    freed."""
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import serve
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout ({e})")
    import jax
    devices = jax.devices()
    if chip_check:
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX found {devices[0].platform}")
        if len(devices) < cell.chips:
            raise BenchError(f"{cell.name} needs {cell.chips} chips, "
                             f"found {len(devices)}")
    cache = None
    if compile_cache:
        cache = serve.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    ref = load_module(BENCH / "references" / f"{cell.config['reference']}.py",
                      f"bench_reference_{cell.config['reference']}")
    vocab = cell.config["vocab_size"]
    engine = build_engine(cell, ref, seed)["engine"]
    shapes = warm_up(engine, cell, vocab)
    res = run_window(cell, engine, seed, seconds, trace, vocab)
    setup_s = res["t_start"] - T_START
    dev = device_info(jax, cell.chips)
    metrics, attempted, failed = end_to_end(cell, res, seconds)
    w = res["window"]
    late = [tr.sent - tr.due for tr in res["tracks"]
            if tr.sent is not None and tr.due >= 0]
    print(f"bench: {cell.name} seed {seed} on {device_name(dev)}; "
          f"compile cache {cache}; "
          f"warmed stream shapes {shapes}; {w.steps} steps in the run, "
          f"{len(res['compiles'])} JAX trace/lower/compile events while "
          f"driving (first 20: window time, kind, function, seconds: "
          f"{res['compiles'][:20]}); "
          f"{res['n_tokens']} tokens, {res['n_gaps']} gaps, "
          f"{res['n_ttft']} first tokens in the window (time to first "
          f"token p25/p50/p75/p90 {res['ttft_quartiles']}); generator lateness "
          f"mean {np.mean(late) if late else 0:.6f} s max "
          f"{max(late) if late else 0:.6f} s; padded "
          f"{res['padded']}/{res['dispatched']} dispatched rows; longest "
          f"step {res['longest_step']:.6f} s in the window, "
          f"{res['longest_step_outside']:.6f} s before or after it; "
          f"{len(res['gc'])} garbage "
          f"collections, longest "
          f"{max((d for _, d in res['gc']), default=0.0):.6f} s, "
          f"{sum(1 for g, _ in res['gc'] if g == 2)} of generation 2; "
          f"traffic started {res['t_open'] - res['t_start']:.3f} s before "
          f"the window opened; host over the window {res['host']}",
          file=sys.stderr)
    out = {"attempted": attempted, "failed": failed, "device": dev}
    if trace:
        red = trace_reduce.reduce_dir(res["trace_dir"])
        shutil.rmtree(res["trace_dir"], ignore_errors=True)
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["top_ops"][:10],
                            "idle_gaps": red["idle_gaps"][:10]}
        inputs = LayerInputs(
            trace=red,
            counters={"dispatched_tokens": res["dispatched"],
                      "padded_tokens": res["padded"]},
            steps=res["steps"], dims=costs.Dims.from_config(cell.config),
            peaks=costs.peaks(dev["kind"]) if chip_check else {},
            w8a8=bool(cell.config["w8a8"]), kv_bits=cell.config["kv_bits"])
        out["metrics"] = per_layer(cell, inputs)
        print(f"bench: traced {red['window_s']:.6f} s on {device_name(dev)}, "
              f"device busy "
              f"{red['busy_s']:.6f} s, {red['n_steps']} step executions "
              f"against {len(res['steps'])} steps logged; host lines "
              f"{red['host_lines']}", file=sys.stderr)
    else:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        out["metrics"] = metrics
    tracks = res["tracks"]
    del engine, res, w
    gc.collect()
    return {"out": out, "tracks": tracks, "ref": ref}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             chip_check: bool = True, compile_cache: bool = True) -> dict:
    """One whole run; returns the result line."""
    r = serve_cell(cell, seed, seconds, trace, chip_check, compile_cache)
    t = time.perf_counter()
    checks = check(cell, r["ref"], seed, r["tracks"])
    o = r["out"]
    print(f"bench: reference check took {time.perf_counter() - t:.1f} s on "
          f"{device_name(o['device'])}", file=sys.stderr)
    out = {"correct": passed(checks), "attempted": o["attempted"],
           "failed": o["failed"], "metrics": o["metrics"],
           "device": o["device"]}
    if "breakdown" in o:
        out["breakdown"] = o["breakdown"]
    out["checks"] = checks
    return out


def main(argv=None, *, chip_check: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = find_cell(args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       chip_check=chip_check)
    except BenchError as e:
        print(f"bench: no result: {e}", file=sys.stderr)
        return 2
    on = device_name(out["device"])
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']} on {on}",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
