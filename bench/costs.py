"""Operations and bytes from shapes: what the algorithm needs, not what the
compiler counted.  These functions are the benchmark's yardstick for the
roofline shares and the step MFU; they take plain integers and import
nothing of the program.

Conventions: one multiply-add is 2 operations.  Bytes are HBM bytes the
algorithm must move at least once per call: its inputs read and its outputs
written, with weights and KV codes counted at their stored width.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class Dims:
    """Decoder shapes (a configuration's published sizes)."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_config(cls, hf: dict) -> "Dims":
        return cls(n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
                   n_heads=hf["num_attention_heads"],
                   n_kv_heads=hf["num_key_value_heads"],
                   head_dim=hf["head_dim"], d_ff=hf["intermediate_size"],
                   vocab=hf["vocab_size"])


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def layer_matmul_shapes(d: Dims) -> list[tuple[int, int]]:
    """(K, N) of every weight matmul in one decoder layer: q, k, v, o,
    gate, up, down."""
    hd = d.head_dim
    return [(d.d_model, d.n_heads * hd), (d.d_model, d.n_kv_heads * hd),
            (d.d_model, d.n_kv_heads * hd), (d.n_heads * hd, d.d_model),
            (d.d_model, d.d_ff), (d.d_model, d.d_ff), (d.d_ff, d.d_model)]


def all_matmul_shapes(d: Dims) -> list[tuple[int, int]]:
    """(K, N) of every weight matmul of one forward row: the layers' and
    the LM head's."""
    return layer_matmul_shapes(d) * d.n_layers + [(d.d_model, d.vocab)]


def weight_flops_per_token(d: Dims) -> int:
    """Matmul operations of one token through every weight (layers + head)."""
    return sum(2 * k * n for k, n in all_matmul_shapes(d))


def causal_kv_sum(start: int, end: int) -> int:
    """Sum over query positions p in [start, end) of the keys each attends
    to (p + 1): the causal attention work of one sequence's rows."""
    return (end * (end + 1) - start * (start + 1)) // 2


def attention_flops(d: Dims, rows: list[tuple[int, int]]) -> int:
    """QK^T and PV operations of one layer over sequences whose new rows
    are positions [start, end), given as (start, end) pairs."""
    return 4 * d.n_heads * d.head_dim * sum(causal_kv_sum(s, e)
                                            for s, e in rows)


def attention_kv_bytes(skv: int, kvh: int, dk: int, dv: int, *,
                       kv_bits: int = 8) -> int:
    """KV bytes one attention call reads when codes are DMA'd once and
    dequantized on chip: every live K and V element at its stored width."""
    return skv * kvh * (dk + dv) * (kv_bits // 8)


def ragged_attention_call(d: Dims, rows: list[tuple[int, int]], *,
                          kv_bits: int = 8, act_bytes: int = 2
                          ) -> tuple[int, int]:
    """(operations, bytes) of one ragged paged attention call (one layer)
    over sequences with new rows [start, end): each sequence's live KV
    (``end`` positions) read once, its queries read and outputs written."""
    flops = attention_flops(d, rows)
    kv = sum(attention_kv_bytes(e, d.n_kv_heads, d.head_dim, d.head_dim,
                                kv_bits=kv_bits) for _, e in rows)
    q_rows = sum(e - s for s, e in rows)
    qo = 2 * q_rows * d.n_heads * d.head_dim * act_bytes
    return flops, kv + qo


def int8_matmul_call(m: int, k: int, n: int, *, out_bytes: int = 1
                     ) -> tuple[int, int]:
    """(operations, bytes) of one int8 x int8 matmul at the dispatched
    shape (m, k) x (k, n): 2mnk, and int8 operands read plus the output
    written at ``out_bytes`` per element."""
    return 2 * m * n * k, m * k + k * n + m * n * out_bytes


def step_model_flops(d: Dims, rows: list[tuple[int, int]]) -> int:
    """Model operations of one serving step: every real row through every
    weight, plus causal attention in every layer.  Padding rows and
    recomputation are not counted."""
    n = sum(e - s for s, e in rows)
    return n * weight_flops_per_token(d) + d.n_layers * attention_flops(d, rows)


def least_time_s(flops: float, nbytes: float, flops_per_s: float,
                 bytes_per_s: float) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc, tm = flops / flops_per_s, nbytes / bytes_per_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
