"""A new configuration, traffic mix or per-layer metric is added by adding
files and entries: the harness finds each by the name BENCHMARK.json gives,
with no edit to a file that is already there."""
import json
import shutil
from pathlib import Path

import pytest

from bench import costs, run

BENCH = Path(__file__).resolve().parents[1]


@pytest.fixture
def tree(tmp_path):
    """A checkout holding the committed benchmark plus one new config, mix
    and metric, each only added."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((BENCH / "configs" / "qwen3_1_7b.json").read_text())
    cfg["num_hidden_layers"] = 2
    (root / "bench/configs/tiny_qwen3.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix["rate_per_s"] = 1.5
    (root / "bench/traffic/burst.json").write_text(json.dumps(mix))
    (root / "bench/metrics/rows_per_step.py").write_text(
        "def read(x):\n"
        "    return sum(s['t_pad'] for s in x.steps) / len(x.steps)\n")
    spec["configs"].append({"name": "tiny_qwen3", "source": "x",
                            "file": "bench/configs/tiny_qwen3.json",
                            "reduced": ["num_hidden_layers"], "why": "x"})
    spec["workloads"].append({"name": "tiny_qwen3.burst",
                              "config": "tiny_qwen3", "traffic": "burst",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "rows_per_step", "unit": "1",
                              "better": "lower", "source": "program_counter",
                              "layer": "serving engine", "moves": "itl_p50_s",
                              "workloads": ["tiny_qwen3.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, before


def test_new_cell_found_by_name(tree):
    root, before = tree
    cell = run.find_cell("tiny_qwen3.burst", root)
    assert cell.config["num_hidden_layers"] == 2
    assert cell.mix["rate_per_s"] == 1.5
    assert [m["name"] for m in cell.end_to_end] == \
        ["itl_p50_s", "itl_p95_s", "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert "rows_per_step" in names and "int8_matmul_roofline" not in names
    x = run.LayerInputs(trace=None, counters={"dispatched_tokens": 10,
                                              "padded_tokens": 4},
                        steps=[{"t_pad": 8, "rows": []},
                               {"t_pad": 16, "rows": []}],
                        dims=costs.Dims.from_config(cell.config), peaks={},
                        w8a8=False, kv_bits=8)
    got = run.per_layer(cell, x, root / "bench" / "metrics")
    # the new reader reads; readers with no trace leave their metric out
    assert got == {"padded_token_frac": {"value": 0.4, "unit": "1"},
                   "rows_per_step": {"value": 12.0, "unit": "1"}}
    after = {p: p.read_bytes() for p in (root / "bench").rglob("*")
             if p.is_file() and p in before}
    assert after == before


def test_existing_cells_unchanged_by_the_new_one(tree):
    root, _ = tree
    before = run.find_cell("qwen3_1_7b.chat")
    cell = run.find_cell("qwen3_1_7b.chat", root)
    assert cell.config == before.config and cell.mix == before.mix
    assert cell.end_to_end == before.end_to_end
    assert cell.per_layer == before.per_layer


def test_missing_pieces_are_errors(tree, tmp_path):
    root, _ = tree
    with pytest.raises(run.BenchError):
        run.find_cell("no.such_cell", root)
    with pytest.raises(run.BenchError):
        run.find_cell("qwen3_1_7b.chat", tmp_path / "empty")
