"""Scheduler property tests: fairness and no-starvation (DESIGN §9).

The scheduler is pure host bookkeeping, so these tests drive the full
WAITING→PREFILL→DECODE→DONE lifecycle with a FAKE model (every "decode"
emits token 1) under random arrival traces and verify: every request
completes in bounded steps (no starvation), admission is strictly FCFS in
arrival order (head-of-line blocking — a late small request never
overtakes an early large one), preempted requests resume and still emit
exactly ``max_new_tokens``, and the pool ends empty with invariants held
throughout.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving.kv_pool import BlockPool
from repro.serving.scheduler import (Request, RequestState, Scheduler,
                                     chunk_bucket)

MAX_LEN = 32


def _mk_requests(rng, n, max_len=MAX_LEN):
    reqs, t = [], 0.0
    for i in range(n):
        t += float(rng.exponential(0.1))
        p = int(rng.integers(1, max_len - 1))
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, 100, size=p).astype(np.int32),
            max_new_tokens=int(rng.integers(1, max_len - p + 1)),
            arrival=t))
    return reqs


def _drive(sched: Scheduler, requests, max_iters=10_000):
    """Fake-model engine loop mirroring ServingEngine.step's structure
    (including the §10 prefix-cache paths: COW before a chunk writes into
    a shared block, commits that publish completed blocks)."""
    pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
    pool = sched.pool
    clock = 0.0
    iters = 0
    while pending or not sched.idle:
        iters += 1
        assert iters < max_iters, "scheduler made no progress (starvation)"
        clock += 0.01
        if sched.idle and pending and pending[0].arrival > clock:
            clock = pending[0].arrival
        while pending and pending[0].arrival <= clock:
            sched.submit(pending.pop(0))
        sched.admit(clock)
        # chunked prefill under the budget
        budget = sched.prefill_token_budget
        for req in sched.prefill_jobs():
            while budget > 0 and req.state is RequestState.PREFILL:
                start = req.n_prefilled
                c = min(sched.chunk, len(req.feed) - start, budget)
                bs = pool.block_size
                preempted = False
                for idx in range(start // bs, -(-(start + c) // bs)):
                    if idx >= pool.n_blocks_of(req.rid):
                        break
                    if not pool.block_writable(req.rid, idx):
                        if sched.cow_for_prefill(req, idx, clock) is None:
                            preempted = True     # req itself evicted
                            break
                if preempted:
                    break
                req.n_prefilled += c
                req.n_ctx = req.n_prefilled
                pool.commit(req.rid, start, req.feed[start:start + c])
                budget -= c
                if req.n_prefilled == len(req.feed):
                    tok = 1                      # fake first sampled token
                    if req.t_first is None:
                        req.t_first = clock
                    done = req.finished_by(tok, sched.max_model_len)
                    req.generated.append(tok)
                    if done:
                        sched.finish(req, clock)
                    else:
                        req.state = RequestState.DECODE
        # one decode step over all live slots
        for req in list(sched.decode_reqs()):
            if req.slot is None or req.state is not RequestState.DECODE:
                continue                         # preempted this iteration
            if not sched.grow_for_decode(req, clock):
                continue
            pool.commit(req.rid, req.n_ctx, [req.generated[-1]])
            req.n_ctx += 1
            tok = 1
            done = req.finished_by(tok, sched.max_model_len)
            req.generated.append(tok)
            if done:
                sched.finish(req, clock)
        sched.pool.check_invariants()
    return iters


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 100_000), slots=st.integers(1, 4),
       blocks=st.integers(9, 24))
def test_random_traces_complete_fcfs(seed, slots, blocks):
    rng = np.random.default_rng(seed)
    pool = BlockPool(num_blocks=blocks, block_size=4)
    sched = Scheduler(pool, n_slots=slots, chunk=8, max_model_len=MAX_LEN)
    reqs = _mk_requests(rng, int(rng.integers(3, 12)))
    _drive(sched, reqs)
    # every request completed with exactly its token budget — preemption
    # (if any) resumed without dropping or duplicating generated tokens
    assert len(sched.done) == len(reqs)
    for r in reqs:
        assert r.state is RequestState.DONE
        assert len(r.generated) == r.max_new_tokens
        assert r.t_first is not None and r.t_done is not None
    # FIRST admissions are strictly FCFS in (arrival, rid) order: a later
    # request never overtakes an earlier one into the batch
    first_admission = []
    for rid in sched.admission_log:
        if rid not in first_admission:
            first_admission.append(rid)
    by_arrival = [r.rid for r in sorted(reqs,
                                        key=lambda r: (r.arrival, r.rid))]
    assert first_admission == by_arrival
    # pool fully drained
    assert pool.n_live == 0
    pool.check_invariants()


def test_tight_pool_preempts_youngest_and_completes():
    """Pool sized so concurrent decodes MUST collide: the youngest-admitted
    request is evicted (oldest always progresses), resumes, and still
    produces its full token count."""
    pool = BlockPool(num_blocks=6, block_size=4)   # 5 usable = 20 rows
    sched = Scheduler(pool, n_slots=2, chunk=8, max_model_len=20)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 100, size=7).astype(
        np.int32), max_new_tokens=12, arrival=0.0) for i in range(3)]
    _drive(sched, reqs)
    assert len(sched.done) == 3
    assert pool.stats.evictions > 0
    assert all(len(r.generated) == 12 for r in reqs)
    # the earliest-admitted request is never the chosen victim while a
    # younger runner exists
    oldest = min(reqs, key=lambda r: (r.t_admit, r.rid))
    youngest_preempted = max(r.preemptions for r in reqs)
    assert youngest_preempted > 0 and oldest.preemptions == 0
    assert pool.n_live == 0


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 100_000), slots=st.integers(1, 4),
       blocks=st.integers(10, 24))
def test_shared_prefix_traces_complete_with_cache(seed, slots, blocks):
    """Same completeness/FCFS/invariant guarantees with the prefix cache
    ON and a workload dominated by a shared system prompt: requests
    re-attach each other's published blocks (cached_tokens > 0 once the
    prefix is published), duplicates exercise the full-feed COW path, and
    preemption/resume still yields exactly max_new_tokens per request."""
    rng = np.random.default_rng(seed)
    pool = BlockPool(num_blocks=blocks, block_size=4, prefix_cache=True)
    sched = Scheduler(pool, n_slots=slots, chunk=8, max_model_len=MAX_LEN)
    shared = rng.integers(0, 100, size=12).astype(np.int32)
    reqs, t = [], 0.0
    for i in range(int(rng.integers(4, 10))):
        t += float(rng.exponential(0.1))
        if rng.random() < 0.3:
            prompt = shared.copy()               # exact repeat: COW path
        else:
            tail = rng.integers(0, 100, size=int(rng.integers(1, 6)))
            prompt = np.concatenate([shared, tail.astype(np.int32)])
        reqs.append(Request(
            rid=i, prompt=prompt,
            max_new_tokens=int(rng.integers(1, MAX_LEN - len(prompt) + 1)),
            arrival=t))
    _drive(sched, reqs)
    assert len(sched.done) == len(reqs)
    for r in reqs:
        assert len(r.generated) == r.max_new_tokens
    assert pool.n_live == 0
    pool.check_invariants()


def test_cached_admission_skips_prefill_and_cows_full_hits():
    """Deterministic cache behavior on a single slot (strictly sequential
    service): the second request attaches the published 3-block prefix
    (cached_tokens == 12), and an exact repeat of the prompt is a
    FULL-feed hit — prefill shrinks to the one re-fed token (cached 11)
    whose write copy-on-writes the last shared block."""
    pool = BlockPool(num_blocks=24, block_size=4, prefix_cache=True)
    sched = Scheduler(pool, n_slots=1, chunk=8, max_model_len=32)
    shared = np.arange(12, dtype=np.int32)
    reqs = [
        Request(rid=0, prompt=shared.copy(), max_new_tokens=2, arrival=0.0),
        Request(rid=1, prompt=np.concatenate(
            [shared, np.asarray([77, 78], np.int32)]),
            max_new_tokens=2, arrival=0.1),
        Request(rid=2, prompt=shared.copy(), max_new_tokens=2, arrival=0.2),
    ]
    _drive(sched, reqs)
    assert len(sched.done) == 3
    assert reqs[0].cached_tokens == 0              # cold
    assert reqs[1].cached_tokens == 12             # 3 full blocks attached
    assert reqs[2].cached_tokens == 11             # full hit, last token re-fed
    assert pool.cache.stats.cow_copies == 1
    assert pool.cache.stats.hits == 6
    pool.check_invariants()


def test_big_early_request_not_starved_by_small_late_ones():
    """Head-of-line blocking: while the big request 0 waits for blocks,
    later small requests must NOT be admitted around it."""
    pool = BlockPool(num_blocks=8, block_size=4)   # 28 rows
    sched = Scheduler(pool, n_slots=2, chunk=8, max_model_len=28)
    rng = np.random.default_rng(1)
    big = Request(rid=0, prompt=rng.integers(0, 100, size=20).astype(
        np.int32), max_new_tokens=8, arrival=0.0)
    small = [Request(rid=i, prompt=rng.integers(0, 100, size=2).astype(
        np.int32), max_new_tokens=2, arrival=0.001 * i)
        for i in range(1, 6)]
    _drive(sched, [big] + small)
    assert len(sched.done) == 6
    assert sched.admission_log[0] == 0             # big admitted first
    assert big.t_done is not None


def test_submit_validation():
    pool = BlockPool(num_blocks=8, block_size=4)
    sched = Scheduler(pool, n_slots=1, chunk=8, max_model_len=16)
    with pytest.raises(ValueError, match="max_model_len"):
        sched.submit(Request(rid=0, prompt=np.arange(10, dtype=np.int32),
                             max_new_tokens=10))
    with pytest.raises(ValueError, match="empty prompt"):
        sched.submit(Request(rid=1, prompt=np.zeros((0,), np.int32),
                             max_new_tokens=2))
    # a scheduler whose max_model_len exceeds pool capacity could deadlock
    with pytest.raises(ValueError, match="pool capacity"):
        Scheduler(BlockPool(num_blocks=3, block_size=4), n_slots=1,
                  chunk=8, max_model_len=16)


def test_chunk_bucket_bounded_pow2():
    for chunk in (8, 16, 64):
        seen = set()
        for n in range(1, chunk + 1):
            b = chunk_bucket(n, chunk)
            assert b >= n and b <= chunk
            assert b & (b - 1) == 0                # power of two
            seen.add(b)
        assert len(seen) <= chunk.bit_length()     # bounded compile set
        assert chunk_bucket(5 * chunk, chunk) == chunk


def test_grow_for_spec_degrades_before_preempting():
    """DESIGN §11 variable growth: the speculative tail is optional — a
    draft count the pool cannot hold shrinks to what fits, WITHOUT
    preempting a peer; only the mandatory single-token growth may."""
    pool = BlockPool(num_blocks=9, block_size=4)   # 8 usable = 32 rows
    sched = Scheduler(pool, n_slots=2, chunk=16, max_model_len=32)
    pool.alloc_seq(0, 13)     # 4 blocks, 3 spare rows in the last
    pool.alloc_seq(1, 13)     # 4 blocks -> 8 live, 0 free
    ra = Request(rid=0, prompt=np.arange(12, dtype=np.int32),
                 max_new_tokens=8)
    rb = Request(rid=1, prompt=np.arange(12, dtype=np.int32),
                 max_new_tokens=8)
    for req, slot, t in ((ra, 0, 0.0), (rb, 1, 0.1)):
        req.state = RequestState.DECODE
        req.slot = slot
        req.n_ctx = 13
        req.t_admit = t
        sched.slots[slot] = req
    # seq 0 wants 6 drafts; its own last block has 3 spare rows (one of
    # which the mandatory fed token takes) and the pool has no free
    # blocks -> degrade to 2 drafts, NO eviction
    granted = sched.grow_for_spec(ra, 1.0, 6)
    assert granted == 2
    assert pool.stats.seq_evictions == 0
    assert sched.slots[1] is rb                    # peer untouched
    assert pool.n_blocks_of(0) == 4                # no new block needed
    pool.check_invariants()


def test_grow_for_spec_mandatory_row_preempts_youngest():
    """When even the non-speculative +1 row needs a block, grow_for_spec
    falls back to the §9 youngest-first preemption retry."""
    pool = BlockPool(num_blocks=5, block_size=4)   # 4 usable = 16 rows
    sched = Scheduler(pool, n_slots=2, chunk=16, max_model_len=16)
    pool.alloc_seq(0, 8)      # 2 blocks
    pool.alloc_seq(1, 8)      # 2 blocks -> pool exhausted
    old = Request(rid=0, prompt=np.arange(8, dtype=np.int32),
                  max_new_tokens=8)
    young = Request(rid=1, prompt=np.arange(8, dtype=np.int32),
                    max_new_tokens=8)
    for req, slot, t in ((old, 0, 0.0), (young, 1, 0.5)):
        req.state = RequestState.DECODE
        req.slot = slot
        req.n_ctx = 8
        req.t_admit = t
        sched.slots[slot] = req
    granted = sched.grow_for_spec(old, 1.0, 3)
    # the youngest was evicted to make room for the OLD request's row;
    # the draft count was computed under pressure (0 spare pre-eviction)
    assert granted == 0
    assert young.state is RequestState.WAITING
    assert young.preemptions == 1
    assert pool.n_blocks_of(0) == 3
    pool.check_invariants()


def test_cow_failure_retry_under_pool_pressure():
    """ISSUE 5 satellite: the CoW-failure retry path.  A COW that cannot
    get a destination block under pool pressure preempts the youngest
    runner and retries; when the writer itself is youngest, it returns
    None (the engine's zero-progress contract) and its state flips —
    which is exactly what the engine's prefill progress guard relies on."""
    # 6 usable blocks, BS=4.  Seq 0 publishes a 3-block prefix; seq 1
    # attaches all 3 shared blocks (fully-cached feed) and must COW the
    # last one to re-feed — after fillers exhaust the free list.
    pool = BlockPool(num_blocks=7, block_size=4, prefix_cache=True)
    sched = Scheduler(pool, n_slots=2, chunk=16, max_model_len=24)
    feed = np.arange(12, dtype=np.int32)
    pool.alloc_seq(0, 12)
    pool.commit(0, 0, feed)                        # 3 published blocks
    pool.alloc_seq(99, 8)                          # filler: 2 blocks
    plan = pool.plan_seq(len(feed), token_ids=feed)
    assert plan.feasible and len(plan.hit_blocks) == 3
    pool.alloc_seq(1, 12, plan=plan)               # pure attach: no alloc
    pool.alloc_seq(98, 4)                          # filler: last free block
    assert pool.n_free == 0
    owner = Request(rid=0, prompt=feed.copy(), max_new_tokens=4)
    writer = Request(rid=1, prompt=feed.copy(), max_new_tokens=4)
    for req, slot, t, state in ((owner, 0, 0.0, RequestState.DECODE),
                                (writer, 1, 0.2, RequestState.PREFILL)):
        req.state = state
        req.slot = slot
        req.n_ctx = 12 if req is owner else 11
        req.t_admit = t
        sched.slots[slot] = req
    # the writer is the YOUNGEST active: the CoW retry must preempt the
    # writer ITSELF and report None — never loop forever
    assert not pool.block_writable(1, 2)
    out = sched.cow_for_prefill(writer, 2, 1.0)
    assert out is None
    assert writer.state is RequestState.WAITING
    assert writer.preemptions == 1
    assert owner.slot == 0                         # older peer survived
    pool.check_invariants()
    # with pressure relieved, the SAME shared-attach + COW succeeds and
    # yields a fresh private destination (the source keeps its key)
    pool.free_seq(99)                              # 2 blocks back
    plan = pool.plan_seq(len(feed), token_ids=feed)
    pool.alloc_seq(2, 12, plan=plan)
    re_writer = Request(rid=2, prompt=feed.copy(), max_new_tokens=4)
    re_writer.state = RequestState.PREFILL
    re_writer.slot = 1
    re_writer.n_ctx = 11
    re_writer.t_admit = 2.0
    sched.slots[1] = re_writer
    pair = sched.cow_for_prefill(re_writer, 2, 2.0)
    assert pair is not None
    src_blk, dst_blk = pair
    assert src_blk != dst_blk and pool.block_writable(2, 2)
    assert pool.cache.is_published(src_blk)
    pool.check_invariants()
