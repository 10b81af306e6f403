"""Profiling + energy hooks for the serving engine (DESIGN §14).

Three optional, independently-gated capabilities:

* **`jax.profiler` capture windows** — :meth:`Profiler.capture` wraps a
  run of the engine in ``jax.profiler.trace(profile_dir)`` so the XLA
  trace (TensorBoard / Perfetto loadable) lines up with the host-side
  obs trace.  While the profiler has a directory its tracer's
  ``capturing`` flag is on, so every ``Tracer.phase`` of the engine
  (``engine.step`` and its phases) is also a ``TraceAnnotation`` on the
  profiler's clock.  A caller that runs its own ``jax.profiler`` window
  sets ``profile_dir`` for its duration.
* **Per-compiled-shape cost analysis** — :meth:`Profiler.cost_for`
  runs AOT ``lower(...).cost_analysis()`` once per compiled stream
  shape (FLOPs + bytes accessed per dispatch), memoized by the same
  shape keys as the engine's compile cache.  This is the attribution
  table: padded FLOPs per shape × dispatch counts = where compute went.
* **Energy accounting** — :class:`EnergyAccount` turns the engine's
  Table-5 requant counters into a live joules-proxy per token, split by
  phase (prefill / decode / spec-wasted).  The proxy is DEFINED as the
  Table-5 bit-shifting energy of the requant ops attributed to each
  phase (KV-path ops + forward W8A8 boundary ops; the paper's Table 5
  measures the requant unit, so that is what the proxy covers — see
  DESIGN §14 for the formula).  It reconciles *exactly* with the
  engine's hwcost counters: sum over phases of ``quant_ops`` equals
  ``requant_ops_performed + requant_ops_forward`` (asserted in
  tests/test_obs.py and gated in ``serving_bench --check``).

`jax` is imported lazily inside methods: constructing a disabled
Profiler (the default) never touches jax, keeping host-only imports of
`repro.obs` jax-free.
"""
from __future__ import annotations

import contextlib
from typing import Any, Optional

from ..core import hwcost

__all__ = ["Profiler", "EnergyAccount", "ENERGY_PHASES"]

ENERGY_PHASES = ("prefill", "decode", "spec_wasted")


class EnergyAccount:
    """Per-phase requant-op ledger → Table-5 energy proxy.

    The engine calls :meth:`charge` at each commit point with the phase,
    the number of requant ops the step executed, and the number of
    useful tokens it produced (0 for ``spec_wasted`` — wasted draft work
    has energy but no tokens, which is exactly why it gets its own
    bucket)."""

    def __init__(self, kind: str = "bit_shifting"):
        if kind not in hwcost.TABLE5:
            raise ValueError(f"unknown Table-5 unit kind {kind!r}")
        self.kind = kind
        self.quant_ops = {p: 0 for p in ENERGY_PHASES}
        self.tokens = {p: 0 for p in ENERGY_PHASES}

    def charge(self, phase: str, quant_ops: int, tokens: int) -> None:
        self.quant_ops[phase] += quant_ops
        self.tokens[phase] += tokens

    def reset(self) -> None:
        for p in ENERGY_PHASES:
            self.quant_ops[p] = 0
            self.tokens[p] = 0

    @property
    def total_quant_ops(self) -> int:
        return sum(self.quant_ops.values())

    def energy_uj(self, phase: str) -> float:
        return hwcost.energy_uj(self.kind, self.quant_ops[phase])

    def uj_per_token(self, phase: str) -> Optional[float]:
        """Energy proxy per USEFUL token of the phase.  ``spec_wasted``
        divides by the *emitted* decode tokens instead — its meaning is
        'wasted joules amortized over what we actually kept'."""
        ops = self.quant_ops[phase]
        toks = self.tokens["decode"] if phase == "spec_wasted" \
            else self.tokens[phase]
        if toks == 0:
            return None if ops == 0 else float("inf")
        return hwcost.energy_uj(self.kind, ops) / toks

    def proxy_uj_per_token(self) -> Optional[float]:
        """The headline live gauge: total requant energy over total
        useful (prefill-fed + decode-emitted) tokens."""
        toks = self.tokens["prefill"] + self.tokens["decode"]
        if toks == 0:
            return None
        return hwcost.energy_uj(self.kind, self.total_quant_ops) / toks

    def report(self) -> dict:
        out: dict = {"unit": self.kind}
        for p in ENERGY_PHASES:
            uj = self.energy_uj(p)
            upt = self.uj_per_token(p)
            out[p] = {
                "quant_ops": self.quant_ops[p],
                "tokens": self.tokens[p],
                "energy_uj": round(uj, 6),
                "uj_per_token": None if upt is None
                else round(upt, 9),
            }
        total = self.proxy_uj_per_token()
        out["total_quant_ops"] = self.total_quant_ops
        out["total_energy_uj"] = round(
            hwcost.energy_uj(self.kind, self.total_quant_ops), 6)
        out["proxy_uj_per_token"] = None if total is None \
            else round(total, 9)
        return out


class Profiler:
    """Optional jax-profiler + AOT-cost-analysis wrapper.

    ``profile_dir=None`` and ``cost=False`` (the defaults) make every
    method a no-op; the engine constructs one unconditionally so call
    sites stay unconditional too."""

    def __init__(self, *, profile_dir: Optional[str] = None,
                 cost: bool = False, tracer=None):
        self.tracer = tracer
        self.profile_dir = profile_dir
        self.cost = cost
        self.shape_costs: dict[Any, dict] = {}
        # the lowered executable of each costed shape, so callers can
        # compile it and read what ran (``.compile().as_text()``)
        self.lowered: dict[Any, Any] = {}

    @property
    def profile_dir(self) -> Optional[str]:
        return self._profile_dir

    @profile_dir.setter
    def profile_dir(self, path: Optional[str]) -> None:
        """A directory means a capture is on: the tracer's phases then
        annotate the profiler's trace."""
        self._profile_dir = path
        if self.tracer is not None:
            self.tracer.capturing = path is not None

    @property
    def enabled(self) -> bool:
        return self.profile_dir is not None or self.cost

    # -- capture windows --------------------------------------------------

    @contextlib.contextmanager
    def capture(self):
        """Wrap a whole engine run in a profiler trace window."""
        if self.profile_dir is None:
            yield
            return
        import jax
        with jax.profiler.trace(self.profile_dir):
            yield

    # -- per-shape cost analysis ------------------------------------------

    def cost_for(self, shape_key, jitfn, *args) -> Optional[dict]:
        """FLOPs/bytes of one compiled stream shape, memoized.

        Uses AOT ``lower(...).cost_analysis()`` (no compile, no
        execute — safe to call before the real dispatch donates its
        buffers); falls back to ``.compile().cost_analysis()`` on older
        jax.  The ``Lowered`` is kept in ``self.lowered[shape_key]``.
        Returns {flops, bytes_accessed} (floats, -1.0 when the backend
        reports nothing) or None when cost analysis is off."""
        if not self.cost:
            return None
        hit = self.shape_costs.get(shape_key)
        if hit is not None:
            return hit
        entry = {"flops": -1.0, "bytes_accessed": -1.0}
        try:
            lowered = self.lowered[shape_key] = jitfn.lower(*args)
            try:
                ca = lowered.cost_analysis()
            except Exception:
                ca = lowered.compile().cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                if "flops" in ca:
                    entry["flops"] = float(ca["flops"])
                if "bytes accessed" in ca:
                    entry["bytes_accessed"] = float(ca["bytes accessed"])
        except Exception:
            pass        # cost analysis is best-effort attribution only
        self.shape_costs[shape_key] = entry
        return entry

    def report(self) -> Optional[dict]:
        """Per-shape attribution table (None when fully disabled)."""
        if not self.enabled:
            return None
        return {
            "profile_dir": self.profile_dir,
            "cost_analysis": {
                str(k): v for k, v in sorted(self.shape_costs.items(),
                                             key=lambda kv: str(kv[0]))
            },
        }

    def reset(self) -> None:
        self.shape_costs.clear()
        self.lowered.clear()
