"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Two stages, so that the second can be checked on a small recorded trace:

- ``extract(profile)`` reads the ``.xplane.pb`` the JAX profiler wrote into
  a plain dict of events (seconds): per device, its operations and its
  module executions; for the host, the spans of the threads that drive
  the engine (those holding the benchmark's ``bench.*`` annotations);
- ``reduce(events)`` computes from that dict: device busy time (the union
  of operation intervals), the window (first to last ragged-step
  execution), the step executions, events and time per operation (keyed by
  its HLO text, which holds its shapes), the operations that took most
  time (control-flow ops that contain others left out), and the longest
  idle gaps labelled by the innermost host span around them.
"""
from __future__ import annotations

import glob
import os
import re

TRACE_SECONDS = 5.0                # length of the traced part of a window
STEP_MODULE = "ragged_sampled_step"  # the engine's jitted step
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MARK = "bench."               # annotations of the benchmark's driver
NAME_CHARS = 400                   # enough of an op's HLO text for shapes
WRAPPERS = ("while", "conditional", "call")   # ops that contain other ops
TOP = 10


def _ev(e) -> list:
    return [e.name[:NAME_CHARS], e.start_ns * 1e-9, e.duration_ns * 1e-9]


def short_name(op: str) -> str:
    """``%ragged_paged_flash.6 = bf16[...] custom-call(...)`` ->
    ``ragged_paged_flash``."""
    head = op.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", head)


def extract(profile) -> dict:
    """Plain events from a ``jax.profiler.ProfileData``."""
    out = {"devices": {}, "host": [], "host_lines": []}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            d = out["devices"][plane.name] = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    d["ops"] += [_ev(e) for e in line.events]
                elif line.name == MODULES_LINE:
                    d["modules"] += [_ev(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host_lines"].append(line.name)
                ev = [_ev(e) for e in line.events]
                if any(e[0].startswith(HOST_MARK) for e in ev):
                    out["host"] += ev
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged [start, end) intervals of (start, duration) pairs."""
    merged = []
    for s, d in sorted(intervals):
        e = s + d
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(a, b) for a, b in merged]


def _host_label(host, t) -> str:
    """What the host was doing at time ``t``: the innermost Python frame
    (``$file:line function`` events, where the Python tracer was on) and
    the two innermost annotated spans around it, innermost first."""
    around = [name for _, name in
              sorted((d, name) for name, s, d in host if s <= t <= s + d)]
    frames = [n for n in around if n.startswith("$")][:1]
    marks = [n for n in around if not n.startswith("$")][:2]
    return " < ".join(frames + marks) or "no host span"


def reduce(events: dict) -> dict:
    busy, window, per_op = 0.0, 0.0, {}
    steps, gaps = [], []
    devs = events["devices"]
    for d in devs.values():
        ex = sorted([s, dur] for name, s, dur in d["modules"]
                    if STEP_MODULE in name)
        if not ex:
            continue
        lo, hi = ex[0][0], ex[-1][0] + ex[-1][1]
        ops = [(s, dur) for _, s, dur in d["ops"] if s >= lo and s + dur <= hi]
        u = union(ops or [(s, dur) for s, dur in ex])
        busy += sum(b - a for a, b in u)
        window += hi - lo
        steps += ex
        for name, s, dur in d["ops"]:
            if s >= lo and s + dur <= hi:
                n_t = per_op.setdefault(name, [0, 0.0])
                n_t[0] += 1
                n_t[1] += dur
        gaps += [(b[0] - a[1], a[1], b[0]) for a, b in zip(u, u[1:])]
    n = max(1, sum(1 for d in devs.values() if d["modules"]))
    gaps.sort(reverse=True)
    idle = [[_host_label(events["host"], (a + b) / 2), g]
            for g, a, b in gaps[:TOP]]
    by_short = {}
    for name, (_, t) in per_op.items():
        k = short_name(name)
        if not k.startswith(WRAPPERS):
            by_short[k] = by_short.get(k, 0.0) + t
    top = sorted(by_short.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy / n, "window_s": window / n,
            "step_exec": sorted(steps), "n_steps": len(steps),
            "op_s": per_op, "top_ops": [[k, v] for k, v in top],
            "idle_gaps": idle, "host_lines": events.get("host_lines", [])}


def kernel_ops(red: dict | None, kernel: str) -> dict:
    """{HLO text: [events, seconds]} of the operations named ``kernel``."""
    if not red:
        return {}
    return {k: v for k, v in red["op_s"].items() if short_name(k) == kernel}


def kernel_seconds(red: dict | None, kernel: str) -> float:
    """Device seconds of the operations named ``kernel``."""
    return sum(t for _, t in kernel_ops(red, kernel).values())


def reduce_dir(path: str) -> dict:
    """Reduce the one profile the JAX profiler wrote under ``path``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return reduce(extract(ProfileData.from_file(max(files,
                                                    key=os.path.getmtime))))
