"""The operation and byte counts against hand counts at qwen3_1_7b's shapes
(28 layers, d_model 2048, 16/8 heads of 128, d_ff 6144, vocab 151936)."""
import json
from pathlib import Path

import pytest

from bench import costs

CFG = Path(__file__).resolve().parents[1] / "configs" / "qwen3_1_7b.json"


@pytest.fixture(scope="module")
def dims():
    return costs.Dims.from_config(json.loads(CFG.read_text()))


def test_weight_flops_per_token(dims):
    # per layer: q 2048x2048, k and v 2048x1024, o 2048x2048, gate and up
    # 2048x6144, down 6144x2048 = 50,331,648 weights; 28 layers; a tied head
    # of 2048 x 151936 = 311,164,928 weights; 2 operations per weight
    per_layer = 4194304 + 2 * 2097152 + 4194304 + 3 * 12582912
    assert per_layer == 50331648
    assert costs.weight_flops_per_token(dims) == 2 * (28 * per_layer
                                                      + 311164928)


def test_attention_flops_decode_and_chunk(dims):
    # one decode row at position 999 attends to 1000 keys: QK and PV are
    # 2 * 16 heads * 128 * 1000 operations each
    assert costs.attention_flops(dims, [(999, 1000)]) == 4 * 16 * 128 * 1000
    # a chunk of rows 0..511 attends to 1 + 2 + ... + 512 keys
    assert costs.causal_kv_sum(0, 512) == 512 * 513 // 2
    assert costs.attention_flops(dims, [(0, 512)]) == \
        4 * 16 * 128 * 131328


def test_ragged_attention_bytes(dims):
    # KV of 1000 positions: 8 KV heads x 128 x (K + V) x 1 byte = 2,048,000;
    # one query row read and one output row written: 2 x 16 x 128 x 2 bytes
    flops, nbytes = costs.ragged_attention_call(dims, [(999, 1000)])
    assert nbytes == 2048000 + 8192
    assert flops == 8192000
    # 57,344 B of KV per token over all 28 layers
    assert 28 * costs.attention_kv_bytes(1, 8, 128, 128) == 57344


def test_int8_matmul_call():
    flops, nbytes = costs.int8_matmul_call(640, 2048, 6144)
    assert flops == 2 * 640 * 2048 * 6144
    assert nbytes == 640 * 2048 + 2048 * 6144 + 640 * 6144


def test_step_model_flops_counts_real_rows_only(dims):
    rows = [(0, 300), (999, 1000), (40, 41)]
    want = 302 * costs.weight_flops_per_token(dims) + 28 * (
        costs.attention_flops(dims, rows))
    assert costs.step_model_flops(dims, rows) == want


def test_least_time_names_its_bound():
    assert costs.least_time_s(197e12, 1.0, 197e12, 819e9) == (1.0, "compute")
    t, bound = costs.least_time_s(1.0, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")


def test_unknown_device_is_an_error():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")
