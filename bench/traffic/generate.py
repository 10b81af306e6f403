"""The one traffic generator: it reads a mix file (``bench/traffic/<mix>.json``)
and makes the requests of one run from ``--seed``.

Every seed gets the same sequence of (inter-arrival gap, prompt length,
output length), drawn at evenly spaced quantiles of the mix's
distributions and shuffled once by a fixed key, in the same order; the
seed draws only the token ids.  Runs with different seeds therefore do the
same work at the same times, and the seed does not move the metrics by
changing which requests fall in the window.

A mix file holds:

- ``loop``: ``"open"`` (independent users, arrivals on a schedule) or
  ``"closed"`` (``clients`` callers, each sending its next request when the
  last one completes);
- ``rate_per_s`` and ``preroll_s`` (open): arrivals start ``preroll_s``
  seconds before the window opens, at least one mean request lifetime, so
  the window sees the load at its steady state; or ``clients`` and
  ``requests_per_client`` (closed), whose first requests' caches are built
  before the window opens;
- ``prompt`` and ``output``: length distributions, each
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``;
- ``n_slots``, ``max_model_len`` and ``num_blocks``: the engine's batch
  and KV-pool sizing that these lengths dictate.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

MIX_DIR = Path(__file__).resolve().parent


@dataclass
class Planned:
    """One request as the traffic defines it: ``due`` is when it is due on
    the window's clock (open loop), ``client`` who sends it (closed loop)."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float = 0.0
    client: int = -1


def load_mix(name: str, mix_dir: Path = MIX_DIR) -> dict:
    path = mix_dir / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r}: {path} is missing")
    mix = json.loads(path.read_text())
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"mix {name!r}: loop must be 'open' or 'closed'")
    return mix


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``spec``, clipped to
    [min, max] and rounded; sorted ascending."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


ORDER_KEY = 20240601       # the fixed key of the one shuffle of every mix


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def _ordered(x: np.ndarray, stream: int) -> np.ndarray:
    """``x`` shuffled by the fixed key, the same for every seed."""
    return _rng(ORDER_KEY, stream).permutation(x)


def _requests(mix: dict, n: int, seed: int, vocab: int) -> list[Planned]:
    prompts = _ordered(quantile_sizes(mix["prompt"], n), 1)
    outputs = _ordered(quantile_sizes(mix["output"], n), 2)
    tok = _rng(seed, 2)
    return [Planned(rid=i,
                    prompt=tok.integers(0, vocab, int(p)).astype(np.int32),
                    max_new_tokens=int(g))
            for i, (p, g) in enumerate(zip(prompts, outputs))]


def open_loop(mix: dict, seconds: float, seed: int, vocab: int
              ) -> list[Planned]:
    """round(rate x (preroll_s + seconds)) requests, all due inside
    [-preroll_s, seconds): Poisson gaps at the exponential's quantiles, in
    the mix's order, scaled so that the last one is due at
    seconds - span / (n + 1).  Sorted by due time."""
    span = mix["preroll_s"] + seconds
    n = max(1, round(mix["rate_per_s"] * span))
    reqs = _requests(mix, n, seed, vocab)
    u = (np.arange(n) + 0.5) / n
    gaps = _ordered(-np.log1p(-u) / mix["rate_per_s"], 3)
    due = np.cumsum(gaps)
    due *= span * n / (n + 1) / due[-1]
    for r, t in zip(reqs, due - mix["preroll_s"]):
        r.due = float(t)
    return reqs


def closed_loop(mix: dict, seed: int, vocab: int) -> list[list[Planned]]:
    """``clients`` queues of ``requests_per_client`` requests each, the
    sizes dealt from the mix's ordered quantile set."""
    c, k = mix["clients"], mix["requests_per_client"]
    reqs = _requests(mix, c * k, seed, vocab)
    queues = [reqs[i::c] for i in range(c)]
    for i, q in enumerate(queues):
        for r in q:
            r.client = i
    return queues
