"""The traffic generator: seed determinism, the same work for every seed,
the mixes' length quantiles, and arrivals inside the window."""
import numpy as np
import pytest

from bench.traffic import generate

SEED = 2**33 + 12345


@pytest.fixture(scope="module")
def chat():
    return generate.load_mix("chat")


def test_same_seed_same_requests(chat):
    a = generate.open_loop(chat, 30.0, SEED, 151936)
    b = generate.open_loop(chat, 30.0, SEED, 151936)
    assert [(r.due, r.max_new_tokens) for r in a] == \
        [(r.due, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_sizes_not_order(chat):
    """Every seed gets the same sizes and arrival times in the same order
    (so the window holds the same work); only the token ids differ."""
    a = generate.open_loop(chat, 30.0, SEED, 151936)
    b = generate.open_loop(chat, 30.0, SEED + 1, 151936)
    assert [(len(r.prompt), r.max_new_tokens, r.due) for r in a] == \
        [(len(r.prompt), r.max_new_tokens, r.due) for r in b]
    assert len({len(r.prompt) for r in a}) > 1
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_open_loop_arrivals_in_window(chat):
    reqs = generate.open_loop(chat, 40.0, SEED, 151936)
    due = np.array([r.due for r in reqs])
    pre = chat["preroll_s"]
    assert pre >= 30.0      # about one request lifetime at the knee, or more
    assert len(reqs) == round(chat["rate_per_s"] * (pre + 40.0))
    assert np.all(np.diff(due) >= 0) and -pre < due[0] < 0 and due[-1] < 40.0
    assert 0 < np.sum(due >= 0) < len(reqs)


def test_chat_length_quantiles(chat):
    p = generate.quantile_sizes(chat["prompt"], 1000)
    o = generate.quantile_sizes(chat["output"], 1000)
    assert abs(np.median(p) - 512) <= 2 and abs(np.median(o) - 128) <= 1
    assert p.min() >= 64 and p.max() <= 4096
    assert o.min() >= 16 and o.max() <= 1024
    assert np.all(p + o <= chat["max_model_len"])


def test_longctx_closed_loop():
    mix = generate.load_mix("longctx")
    queues = generate.closed_loop(mix, SEED, 151936)
    assert len(queues) == mix["clients"] == mix["n_slots"]
    flat = [r for q in queues for r in q]
    lens = np.array([len(r.prompt) for r in flat])
    outs = np.array([r.max_new_tokens for r in flat])
    assert lens.min() >= 2048 and lens.max() <= 6144
    assert abs(np.median(lens) - 4096) <= 64
    assert outs.min() >= 256 and outs.max() <= 1024
    assert np.all(lens + outs <= mix["max_model_len"])
    assert all(r.client == i for i, q in enumerate(queues) for r in q)


def test_unknown_mix_is_an_error():
    with pytest.raises(FileNotFoundError):
        generate.load_mix("no_such_mix")
