"""The drivers, through a whole run at a tiny size on the CPU: the open
loop serves its pre-roll before the window opens, and counts only what is
due in the window; the closed loop's clients are prefilled before it
opens."""
import pytest

from bench import run
from bench.tests._tiny import tiny_cell

SEED = 2**35 + 17
SECONDS = 3.0


@pytest.fixture(scope="module")
def open_run():
    return run.serve_cell(tiny_cell(), SEED, SECONDS, False,
                          chip_check=False, compile_cache=False)


def test_open_loop_serves_the_preroll_before_the_window(open_run):
    tracks = open_run["tracks"]
    pre = [tr for tr in tracks if tr.due < 0.0]
    mix = tiny_cell().mix
    assert pre and min(tr.due for tr in pre) >= -mix["preroll_s"]
    # the clock starts at the first due time, so the pre-roll begins
    # before the window opens; a step that runs long on a loaded host may
    # delay later sends, so they are held to their order and never early
    assert pre[0].sent < 0.0
    assert all(tr.sent >= tr.due for tr in tracks)
    sent = [tr.sent for tr in tracks]
    assert sent == sorted(sent)
    # the pre-roll's requests were served
    assert all(tr.stamps for tr in pre)
    assert all(tr.first is not None for tr in tracks)


def test_open_loop_counts_only_what_is_due_in_the_window(open_run):
    out, tracks = open_run["out"], open_run["tracks"]
    assert out["attempted"] == sum(1 for tr in tracks
                                   if 0.0 <= tr.due < SECONDS)
    assert out["failed"] == 0
    assert out["metrics"]["setup_s"]["value"] > 0.0


def test_closed_loop_prefills_before_the_window():
    r = run.serve_cell(tiny_cell(loop="closed"), SEED, SECONDS, False,
                       chip_check=False, compile_cache=False)
    tracks = r["tracks"]
    mix = tiny_cell(loop="closed").mix
    first = [tr for tr in tracks if tr.due < 0.0]
    assert len(first) == mix["clients"]
    assert all(tr.first is not None and tr.first < 0.0 for tr in first)
    assert len(tracks) > mix["clients"]      # clients sent again in it
