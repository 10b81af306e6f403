"""A tiny cell of the harness for CPU tests: the program's smoke-size qwen3
(2 layers, d_model 64) under a small open-loop mix."""
import json
from pathlib import Path

from bench import run

DATA = Path(__file__).resolve().parent / "data"
E2E = [{"name": "ttft_p50_s", "unit": "s"}, {"name": "itl_p50_s", "unit": "s"},
       {"name": "itl_p95_s", "unit": "s"}]


def tiny_cell(w8a8: bool = False, loop: str = "open") -> run.Cell:
    cfg = json.loads((DATA / ("tiny-w8a8.json" if w8a8 else "tiny.json"))
                     .read_text())
    mix = json.loads((DATA / f"tiny_{loop}.json").read_text())
    return run.Cell(name=f"tiny.{loop}", chips=1, config=cfg, mix=mix,
                    end_to_end=E2E, per_layer=[])
