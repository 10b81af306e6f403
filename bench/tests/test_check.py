"""The pieces of the comparison that decides ``correct``: which requests are
checked, the numbers over their gaps, and how each number meets its
limit."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import run


def track(rid, n_prompt, n_gen, done):
    req = SimpleNamespace(rid=rid, prompt=np.zeros(n_prompt, np.int32),
                          generated=list(range(n_gen)))
    return SimpleNamespace(req=req, done=done)


def test_sample_takes_the_longest_finished_then_finished_first():
    tracks = [track(0, 100, 10, True), track(1, 900, 50, True),
              track(2, 50, 10, True), track(3, 4000, 300, False)]
    pick = run.sample_requests(tracks, 2**35 + 1, 70, 8)
    assert [t.req.rid for t in pick][0] == 1
    assert {t.req.rid for t in pick} == {0, 1, 2}


def test_sample_tops_up_with_requests_still_decoding():
    tracks = [track(0, 100, 10, True), track(1, 4000, 300, False),
              track(2, 4000, 1, False)]
    pick = run.sample_requests(tracks, 7, 200, 8)
    assert [t.req.rid for t in pick] == [0, 1]
    # none finished: the longest still decoding leads
    pick = run.sample_requests(tracks[1:] + [track(4, 100, 9, False)], 7,
                               200, 8)
    assert [t.req.rid for t in pick] == [1]
    assert run.sample_requests([track(1, 10, 1, False)], 7, 200, 8) == []


def test_gap_stats():
    s = run.gap_stats([np.array([0.0, 0.5]), np.array([3.5, 0.0])])
    assert s == pytest.approx({"max_token_regret_std": 3.5,
                               "mean_token_regret_std": 1.0,
                               "p90_token_regret_std": 2.6,
                               "share_regret_over_1std": 25.0,
                               "share_regret_over_2std": 25.0,
                               "share_regret_over_3std": 25.0})
    assert run.gap_stats([])["max_token_regret_std"] == float("inf")


@pytest.mark.parametrize("value, ok", [(0.4, True), (0.5, True), (0.51, False)])
def test_passed_holds_each_number_to_its_limit(value, ok):
    checks = {"max_token_regret_std": {"value": value, "limit": 0.5},
              "sampled_tokens_min": {"value": 300, "limit": 256},
              "short_outputs": {"value": 0, "limit": 0}}
    assert run.passed(checks) is ok
    checks["sampled_tokens_min"]["value"] = 255
    assert run.passed(checks) is False
