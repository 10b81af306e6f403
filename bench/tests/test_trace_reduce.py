"""The trace reduction on a hand-made trace and on two ragged steps of a
recorded TPU v5e trace (``data/trace_two_steps.json.gz``)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import costs, trace_reduce as tr
from bench.metrics import int8_matmul_roofline

DATA = Path(__file__).resolve().parent / "data"


def hand_trace():
    step = "jit_ragged_sampled_step(1)"
    return {"devices": {"/device:TPU:0": {
        "modules": [[step, 0.0, 1.0], [step, 1.5, 1.0]],
        "ops": [["%while.1 = (...) while(...)", 0.0, 1.0],
                ["%ragged_paged_flash.3 = bf16[8,2048] custom-call()", 0.1, 0.5],
                ["%fusion.2 = f32[8] fusion()", 0.4, 0.4],
                ["%ragged_paged_flash.3 = bf16[8,2048] custom-call()", 1.5, 0.25],
                ["%fusion.2 = f32[8] fusion()", 2.0, 0.5]]}},
        "host": [["step", 0.9, 0.7], ["np.asarray", 1.1, 0.2]]}


def test_union_merges_overlaps():
    assert tr.union([(0, 1), (0.5, 1), (2, 1)]) == [(0, 1.5), (2, 3)]


def test_reduce_hand_trace():
    red = tr.reduce(hand_trace())
    assert red["window_s"] == pytest.approx(2.5)
    # ops cover [0, 1) (the while) and [1.5, 1.75) and [2.0, 2.5)
    assert red["busy_s"] == pytest.approx(1.75)
    assert red["n_steps"] == 2
    assert tr.kernel_seconds(red, "ragged_paged_flash") == pytest.approx(0.75)
    assert dict(red["top_ops"]) == pytest.approx(
        {"ragged_paged_flash": 0.75, "fusion": 0.9})      # no while
    # the longest gap, [1.0, 1.5), lies in np.asarray inside step
    assert red["idle_gaps"][0][0] == "np.asarray < step"
    assert red["idle_gaps"][0][1] == pytest.approx(0.5)


@pytest.fixture(scope="module")
def recorded():
    d = json.loads(gzip.open(DATA / "trace_two_steps.json.gz").read())
    n, h = d["names"], d["host_names"]
    return {"devices": {"/device:TPU:0": {
        "ops": [[n[i], s, t] for i, s, t in d["ops"]],
        "modules": [[n[i], s, t] for i, s, t in d["modules"]]}},
        "host": [[h[i], s, t] for i, s, t in d["host"]]}


def test_reduce_recorded_trace(recorded):
    red = tr.reduce(recorded)
    dev = recorded["devices"]["/device:TPU:0"]
    steps = sorted(m[1:] for m in dev["modules"]
                   if "ragged_sampled_step" in m[0])
    assert red["n_steps"] == len(steps) == 2
    lo, hi = steps[0][0], steps[-1][0] + steps[-1][1]
    assert red["window_s"] == pytest.approx(hi - lo)
    # busy by brute force: 10 us cells covered by some op
    inside = [(s, s + d) for _, s, d in dev["ops"] if s >= lo and s + d <= hi]
    cells = set()
    for a, b in inside:
        cells.update(range(int((a - lo) * 1e5), int((b - lo) * 1e5)))
    assert red["busy_s"] == pytest.approx(len(cells) * 1e-5, rel=0.01)
    assert 0.9 < red["busy_s"] / red["window_s"] <= 1.0
    flash = sum(d for name, s, d in dev["ops"]
                if name.startswith("%ragged_paged_flash")
                and s >= lo and s + d <= hi)
    assert tr.kernel_seconds(red, "ragged_paged_flash") == pytest.approx(flash)
    # 28 layers: one attention call and seven int8 matmuls per layer per
    # step, and the LM head
    ops = tr.kernel_ops(red, "ragged_paged_flash")
    assert sum(n for n, _ in ops.values()) == 2 * 28
    mm = tr.kernel_ops(red, "int8_matmul")
    assert sum(n for n, _ in mm.values()) == 2 * (28 * 7 + 1)


def test_int8_roofline_reads_dispatched_shapes(recorded):
    red = tr.reduce(recorded)

    class X:
        trace = red
        peaks = costs.peaks("TPU v5 lite")
    share = int8_matmul_roofline.read(X)
    assert 0.0 < share <= 100.0
    # by hand: the head is s8[640,151936] from s8[640,2048]
    head = [k for k in tr.kernel_ops(red, "int8_matmul") if "151936" in k]
    m = int8_matmul_roofline.SHAPES.search(head[0])
    assert m.groups() == ("640", "151936", "640", "2048")
