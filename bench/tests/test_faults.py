"""A whole run with the chip check skipped, at a tiny size on the CPU: sound,
it comes out correct; with the served tokens altered where the engine's
step produces them, it comes out not correct."""
import pytest

from bench import run
from bench.tests._tiny import tiny_cell

SEED = 2**34 + 99


def alter_tokens(monkeypatch):
    build = run.build_engine

    def broken(cell, ref, seed):
        built = build(cell, ref, seed)
        eng = built["engine"]
        step, vocab = eng._ragged_fn, cell.config["vocab_size"]

        def altered(*args):
            out, n_acc, cache = step(*args)
            return (out + 1) % vocab, n_acc, cache
        eng._ragged_fn = altered
        return built
    monkeypatch.setattr(run, "build_engine", broken)


@pytest.mark.parametrize("fault", [None, "token altered"])
def test_run_judges_served_tokens(monkeypatch, fault):
    if fault:
        alter_tokens(monkeypatch)
    out = run.run_cell(tiny_cell(), SEED, 3.0, False, chip_check=False,
                       compile_cache=False)
    c = out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert c["sampled_tokens_min"]["value"] >= 20
    assert out["correct"] is (fault is None)
    if fault:
        assert c["max_token_regret_std"]["value"] > \
            3 * c["max_token_regret_std"]["limit"]
    assert list(out)[-1] == "checks"
