"""BENCHMARK.json against the rules the benchmark's files must keep: names,
units, bounds, the cells each metric is reported in, and the files each
entry names."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_keys_and_names(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in spec[k]}) == len(spec[k])
    metrics = [m["name"] for k in ("end_to_end", "per_layer")
               for m in spec[k]]
    assert len(set(metrics)) == len(metrics)
    assert 1 <= spec["run_seconds"] <= 51


def test_files_exist(spec):
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "bench" / "references"
                / f"{cfg['reference']}.py").is_file()
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}

    def reported(m):
        return set(m.get("workloads", cells))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert reported(m) <= cells
    for cell in cells:
        mine = [m for m in spec["end_to_end"] if cell in reported(m)]
        assert len(mine) >= 2
        assert any(cell in reported(m) for m in spec["per_layer"])
    for m in spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert reported(m) <= reported(e2e[m["moves"]])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert any("mfu" in m["name"] for m in spec["per_layer"])
