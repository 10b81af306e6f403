"""Quantized-execution context — how the paper's scheme enters the models.

Execution modes (selectable per run, identical module structure):

* ``fp``    — bf16/fp32 reference; quant machinery compiled out.
* ``fake``  — Eq. (1) in float arithmetic at every planned quant point.
    Used by calibration and CPU accuracy benches.  (QAT variant adds STE.)
* ``int``   — deploy path: int8 weight codes, activations quantized at
    unified-module boundaries, int8 x int8 -> int32 matmuls, single
    bit-shift requantization per module (Pallas kernel on TPU, jnp
    reference otherwise).

The context carries the calibration table (module name -> fractional bits).
Uncalibrated modules fall back to ``default_n`` bits chosen by the Eq.-6
max-heuristic at conversion time — this keeps the dry-run path static.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from repro.core import integer_ops
from repro.core.calibrate import CalibrationReport
from repro.core.qscheme import QuantParams, fake_quant, quant, dequant

__all__ = ["QuantMode", "ModuleBits", "QuantContext", "qlinear",
           "quantize_weight_tree", "QuantizedParams", "quantize_params",
           "module_name_for_path",
           "DEFAULT_N_W", "DEFAULT_N_X", "DEFAULT_N_O"]

# Static fall-back fractional bits (paper Fig. 2b: chosen shifts cluster
# around 3 and 8 for weights/activations on ResNet-50; transformer weights
# are ~N(0, 0.02) so n_w=8 keeps |w|<0.5 in range; activations post-norm are
# O(1..10) so n_x=4).
DEFAULT_N_W = 8
DEFAULT_N_X = 4
DEFAULT_N_O = 4


class QuantMode(enum.Enum):
    FP = "fp"
    FAKE = "fake"        # paper's bit-shift scheme, float arithmetic
    FAKE_SF = "fake_sf"  # scaling-factor baseline (IOA/TensorRT-style W8A8)
    INT = "int"


@dataclasses.dataclass(frozen=True)
class ModuleBits:
    """Calibrated fractional bits for one unified module."""

    n_x: int = DEFAULT_N_X
    n_w: int = DEFAULT_N_W
    n_b: Optional[int] = None
    n_o: int = DEFAULT_N_O
    out_unsigned: bool = False


@dataclasses.dataclass(frozen=True)
class QuantContext:
    """Static quantization configuration threaded through a model's forward.

    Hashable/static so it can be a jit static argument; the table is a
    frozen mapping of module name -> ModuleBits.
    """

    mode: QuantMode = QuantMode.FP
    bits: int = 8
    table: Mapping[str, ModuleBits] = dataclasses.field(default_factory=dict)

    def __hash__(self):
        return hash((self.mode, self.bits, tuple(sorted(self.table.items(),
                                                        key=lambda kv: kv[0]))))

    def __eq__(self, other):
        return (isinstance(other, QuantContext)
                and (self.mode, self.bits) == (other.mode, other.bits)
                and dict(self.table) == dict(other.table))

    def bits_for(self, name: str) -> ModuleBits:
        return self.table.get(name, ModuleBits())

    @classmethod
    def from_report(cls, mode: QuantMode, report: CalibrationReport,
                    bits: int = 8) -> "QuantContext":
        table = {}
        for name, r in report.results.items():
            table[name] = ModuleBits(
                n_x=DEFAULT_N_X, n_w=r.n_w if r.n_w is not None else DEFAULT_N_W,
                n_b=r.n_b, n_o=r.n_o)
        return cls(mode=mode, bits=bits, table=table)


def _fp_linear(x, w, b):
    y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# activation capture for Algorithm-1 calibration of LM modules
# ---------------------------------------------------------------------------
# Works under scan/jit via io_callback: each qlinear call streams its
# (input, weight, bias) to the host store; the FIRST occurrence per module
# name is kept (scanned layers share a name -> layer-0 calibrates the stack,
# matching the shared-bits scan constraint, DESIGN §3).

import contextlib
import threading

_CAPTURE = threading.local()


@contextlib.contextmanager
def capture_activations():
    store: dict[str, tuple] = {}
    prev = getattr(_CAPTURE, "store", None)
    _CAPTURE.store = store
    try:
        yield store
    finally:
        _CAPTURE.store = prev


def _maybe_capture(name: str, x, w, b):
    store = getattr(_CAPTURE, "store", None)
    if store is None:
        return

    def cb(xv, wv, bv):
        if name not in store:
            store[name] = (xv, wv, bv if b is not None else None)

    import jax.experimental
    jax.experimental.io_callback(
        cb, None, x, w, b if b is not None else jnp.zeros((), x.dtype),
        ordered=True)


def qlinear(ctx: QuantContext, name: str, x: jax.Array, w: jax.Array,
            b: Optional[jax.Array] = None, *, use_kernel: bool = True) -> jax.Array:
    """One unified-module linear op under the active quantization mode.

    ``x`` is a float activation at the module boundary; the return value is a
    float activation on the output grid (``fake``/``int``) or exact (``fp``).
    In ``int`` mode, ``w`` may already be int8 codes (from
    :func:`quantize_weight_tree`); float weights are quantized on the fly
    (dry-run convenience path).
    """
    if w.dtype == jnp.int8 and ctx.mode != QuantMode.INT:
        # pre-quantized codes are meaningless as float values — a fp/fake
        # forward over a quantize_params tree is a wiring bug, not a result.
        raise ValueError(
            f"module {name!r}: int8 weight codes reached the "
            f"{ctx.mode.value!r} path — QuantizedParams trees require INT "
            "mode (cfg.matmul_kernel='int8')")
    if ctx.mode == QuantMode.FP:
        _maybe_capture(name, x, w, b)
        return _fp_linear(x, w, b)

    if ctx.mode == QuantMode.FAKE_SF:
        # competing scheme (Table 1/3 baseline): per-tensor float scales on
        # weights AND activations — accuracy reference, costly requant HW.
        from repro.core.baselines import scale_quant
        return _fp_linear(scale_quant(x, ctx.bits),
                          scale_quant(w, ctx.bits).astype(x.dtype),
                          None if b is None else
                          scale_quant(b, ctx.bits).astype(x.dtype))

    mb = ctx.bits_for(name)
    if ctx.mode == QuantMode.FAKE:
        xq = fake_quant(x, mb.n_x, ctx.bits)
        wq = fake_quant(w, mb.n_w, ctx.bits).astype(x.dtype)
        bq = None if b is None else fake_quant(
            b, mb.n_b if mb.n_b is not None else mb.n_w, ctx.bits).astype(x.dtype)
        return _fp_linear(xq, wq, bq)

    # INT mode — integer-only math between the boundary casts.
    x_int = quant(x, mb.n_x, ctx.bits)
    w_int = w if w.dtype == jnp.int8 else quant(w, mb.n_w, ctx.bits)
    n_b = mb.n_b if mb.n_b is not None else mb.n_w
    b_int = None
    if b is not None:
        b_int = b if b.dtype == jnp.int8 else quant(b, n_b, ctx.bits)
    spec = integer_ops.LinearQuantSpec(
        n_x=mb.n_x, n_w=mb.n_w, n_b=n_b, n_o=mb.n_o, bits=ctx.bits)
    if use_kernel:
        # the fused Pallas kernel (the jnp reference on CPU, see ops)
        from repro.kernels import ops as kops
        o_int = kops.int8_matmul(x_int, w_int, b_int, spec)
    else:
        o_int = integer_ops.int_linear(x_int, w_int, b_int, spec)
    return dequant(o_int, mb.n_o, out_dtype=x.dtype)


def quantize_weight_tree(params: Any, ctx: QuantContext,
                         name_fn=None) -> Any:
    """Convert a pytree of float weights to int8 codes for the deploy path.

    Leaves whose path ends in a matmul weight (2-D+, name containing 'w' by
    default) become int8 codes on the grid from ctx.table (or DEFAULT_N_W).
    Norm gains / embeddings stay float (they are folded or boundary ops).
    """
    flat = jax.tree_util.tree_flatten_with_path(params)
    leaves, treedef = flat

    def path_name(path):
        return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)

    out = []
    for path, leaf in leaves:
        nm = path_name(path)
        is_weight = (isinstance(leaf, jax.Array) and leaf.ndim >= 2
                     and ("norm" not in nm) and ("embed" not in nm))
        if name_fn is not None:
            is_weight = name_fn(nm, leaf)
        if is_weight:
            mb = ctx.bits_for(nm)
            out.append(quant(leaf, mb.n_w, ctx.bits))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# W8A8 deploy containers (DESIGN §13)
# ---------------------------------------------------------------------------

def module_name_for_path(path_name: str, table: Mapping[str, ModuleBits]
                         ) -> Optional[str]:
    """Map a params-tree path to the qlinear module name the forward uses.

    Tree paths carry structural prefixes the calibration table does not
    ('blocks/attn/wq' vs the qlinear name 'attn/wq'); the longest path
    suffix present in the table is the module whose grid the forward will
    read at this weight.  None when no calibrated module matches — such
    leaves stay float and (in INT mode) quantize on the fly at defaults.
    """
    parts = path_name.split("/")
    for i in range(len(parts)):
        cand = "/".join(parts[i:])
        if cand in table:
            return cand
    return None


@dataclasses.dataclass(frozen=True)
class QuantizedParams:
    """Deploy-time weight container for ``cfg.matmul_kernel='int8'``.

    ``tree`` is the params pytree with matmul weights replaced by int8
    codes; the po2 exponents live in ``ctx.table`` (static, hashable —
    they become compile-time shift constants in the fused kernel, which
    is also why the §8 shard_map path needs no changes: codes shard
    exactly like their float counterparts and exponents ride along as
    kernel constants).  ``converted`` records which tree paths were
    quantized, for reporting and tests.
    """

    tree: Any
    ctx: QuantContext
    converted: tuple = ()


def quantize_params(params: Any, ctx: QuantContext) -> QuantizedParams:
    """Pre-quantize calibrated matmul weights to int8 codes (DESIGN §13).

    Codes are bit-identical to qlinear's on-the-fly ``quant(w, mb.n_w)``
    — the INT branch passes int8 weights through untouched, so a forward
    over the returned tree produces exactly the tokens of the float-weight
    INT forward while skipping the per-step weight quantization.  Only
    2-D+ leaves whose path maps onto a calibrated module convert;
    embeddings, norm gains and biases stay float (a tied lm_head reads
    ``embed.T`` and therefore also stays float, quantizing on the fly to
    the same codes).  Scanned stacks quantize the whole leading layer
    axis on the one shared grid, matching the scan constraint (DESIGN §3).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    def path_name(path):
        return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)

    out, converted = [], []
    for path, leaf in flat:
        nm = path_name(path)
        mod = module_name_for_path(nm, ctx.table)
        if (mod is not None and isinstance(leaf, jax.Array)
                and leaf.ndim >= 2 and leaf.dtype != jnp.int8
                and "embed" not in nm):
            mb = ctx.bits_for(mod)
            out.append(quant(leaf, mb.n_w, ctx.bits))
            converted.append(nm)
        else:
            out.append(leaf)
    return QuantizedParams(
        tree=jax.tree_util.tree_unflatten(treedef, out), ctx=ctx,
        converted=tuple(converted))
