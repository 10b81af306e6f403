"""The controls, at a tiny size on the CPU (the chip runs them at each
cell's own size with ``bench/control.py``): the W8A8 configuration's control
(the reference in int4) and the bf16 configuration's control (the program's
own int8 path on the served tokens) each come out not correct under the
limits that the sound runs pass; and the control's hand-fed W8A8 step puts
first the tokens that the W8A8 engine itself served."""
import numpy as np
import pytest

from bench import control, run
from bench.tests._tiny import tiny_cell

SEEDS = (3, 2**33 + 5)


@pytest.mark.parametrize("seed", SEEDS)
def test_int4_control_fails_where_w8a8_passes(seed):
    cell = tiny_cell(w8a8=True)
    r = control.readings(cell, seed, 3.0, True, chip_check=False,
                         compile_cache=False)
    limit = cell.config["correct"]["limits"]["max_token_regret_std"]
    assert r["tokens"] >= 20
    assert r["sound"]["max_token_regret_std"] <= limit \
        < r["control_stats"]["max_token_regret_std"]
    assert r["control"] == "int4 reference"
    assert r["correct"] is True and r["control_correct"] is False


def test_bf16_control_is_the_program_int8_path():
    r = control.readings(tiny_cell(), SEEDS[1], 3.0, True, chip_check=False,
                         compile_cache=False)
    assert r["control"] == "program W8A8 path"
    assert r["sound"]["max_token_regret_std"] <= 0.5
    assert r["control_agreement"] < 1.0
    assert r["correct"] is True and r["control_correct"] is False
    assert r["control_checks"]["max_token_regret_std"]["value"] > \
        r["control_checks"]["max_token_regret_std"]["limit"]


def test_w8a8_step_replays_the_w8a8_engine():
    cell = tiny_cell(w8a8=True)
    seed = SEEDS[0]
    r = run.serve_cell(cell, seed, 3.0, False, chip_check=False,
                       compile_cache=False)
    c = cell.config["correct"]
    sample = run.sample_requests(r["tracks"], seed, c["sample_tokens"],
                                 c["max_requests"])
    picks = control.w8a8_argmax(cell, r["ref"], seed, sample)
    served = [run.served(tr)[2] for tr in sample]
    assert sum(len(g) for g in served) >= 20
    assert all(np.array_equal(p, g) for p, g in zip(picks, served))
