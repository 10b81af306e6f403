"""Property tests for the content-addressed prefix cache (DESIGN §10).

The refcounted pool + cache must keep, under arbitrary interleavings of
alloc/share/divert(COW)/extend/free/evict:

* no orphans, no double ownership drift: every non-trash block is exactly
  one of free / idle-cached / live, and ``refcount == number of owning
  sequences`` (``BlockPool.check_invariants``);
* double frees raise, never corrupt;
* COW never mutates a shared block — the writer gets a FRESH private
  block, the source keeps its key and its other readers;
* eviction (LRU reclaim) only ever touches refcount-0 idle blocks;
* identical prefixes resolve to the SAME physical blocks (that is the
  whole point), different scale exponents or different histories never do.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving.kv_pool import TRASH_BLOCK, BlockPool, BlockPoolError
from repro.serving.prefix_cache import ROOT_KEY, block_key

BS = 4


def _pool(num_blocks=24, **kw):
    kw.setdefault("scale_exp", 4)
    return BlockPool(num_blocks, BS, prefix_cache=True, **kw)


def _prefill(pool, sid, feed, start, c):
    """Engine-shaped prefill piece: COW anything shared in the write
    range, then commit (publishing completed blocks)."""
    c = min(c, len(feed) - start)
    for idx in range(start // BS, -(-(start + c) // BS)):
        if idx >= pool.n_blocks_of(sid):
            break
        if not pool.block_writable(sid, idx):
            r_before = int(pool.refcount[pool.seq_blocks(sid)[idx]])
            src, dst = pool.cow(sid, idx)
            # COW never mutates the shared block: the source keeps its
            # key, its other readers, or at worst parks idle-cached
            assert dst != src and pool.cache.is_published(src)
            assert int(pool.refcount[src]) == r_before - 1
            assert int(pool.refcount[dst]) == 1
            assert not pool.cache.is_published(dst)
    pool.commit(sid, start, feed[start:start + c])
    return start + c


# ---------------------------------------------------------------------------
# random interleaved traces
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 100_000))
def test_random_trace_invariants(seed):
    rng = np.random.default_rng(seed)
    pool = _pool(num_blocks=int(rng.integers(10, 40)))
    shared = rng.integers(0, 50, size=8 * BS).astype(np.int32)
    live: dict[int, dict] = {}     # sid -> {feed, written, prefilled}
    next_sid = 0
    for _ in range(80):
        op = int(rng.integers(5))
        if op == 0:                # admit: shared-prefix prompt, plan+alloc
            sid, next_sid = next_sid, next_sid + 1
            pfx = int(rng.integers(0, len(shared) + 1))
            tail = rng.integers(50, 99, size=int(rng.integers(1, 10)))
            feed = np.concatenate([shared[:pfx],
                                   tail.astype(np.int32)])
            plan = pool.plan_seq(len(feed), token_ids=feed)
            if plan.feasible:
                blocks = pool.alloc_seq(sid, len(feed), plan=plan)
                assert TRASH_BLOCK not in blocks
                hit = min(plan.hit_tokens, len(feed) - 1)
                assert blocks[:len(plan.hit_blocks)] == plan.hit_blocks
                live[sid] = {"feed": list(feed), "written": hit}
            else:
                with pytest.raises(BlockPoolError):
                    pool.alloc_seq(sid, len(feed), plan=plan)
        elif op == 1 and live:     # chunked prefill with COW
            sid = int(rng.choice(list(live)))
            s = live[sid]
            if s["written"] < len(s["feed"]):
                s["written"] = _prefill(
                    pool, sid, np.asarray(s["feed"], np.int32),
                    s["written"], int(rng.integers(1, 9)))
        elif op == 2 and live:     # decode: grow one row, commit token
            sid = int(rng.choice(list(live)))
            s = live[sid]
            if s["written"] == len(s["feed"]):
                tok = int(rng.integers(50, 99))
                try:
                    pool.extend(sid, len(s["feed"]) + 1)
                except BlockPoolError:
                    continue       # pool pressure: engine would preempt
                s["feed"].append(tok)
                # the decode row's block is ALWAYS writable: tails are
                # private by the COW-at-prefill invariant
                assert pool.block_writable(
                    sid, (len(s["feed"]) - 1) // BS)
                pool.commit(sid, len(s["feed"]) - 1, [tok])
                s["written"] += 1
        elif op == 3 and live:     # finish
            sid = int(rng.choice(list(live)))
            pool.free_seq(sid)
            del live[sid]
        elif op == 4 and live:     # preempt (release references)
            sid = int(rng.choice(list(live)))
            pool.evict(sid)
            del live[sid]
        pool.check_invariants()
        # live accounting: every owned block is reachable from a live seq
        expect = len({b for sid in live for b in pool.seq_blocks(sid)})
        assert pool.n_live == expect
    for sid in list(live):
        pool.free_seq(sid)
    pool.check_invariants()
    assert pool.n_live == 0
    # cached idle blocks remain resident (that is the point); flushing
    # returns every block to the free stack
    pool.flush_cache()
    pool.check_invariants()
    assert pool.n_free == pool.num_blocks - 1 and pool.n_cached == 0


# ---------------------------------------------------------------------------
# sharing / COW / eviction specifics
# ---------------------------------------------------------------------------

def _alloc_committed(pool, sid, feed):
    """Alloc + fully prefill (commit) a sequence; returns its blocks."""
    plan = pool.plan_seq(len(feed), token_ids=feed)
    blocks = pool.alloc_seq(sid, len(feed), plan=plan)
    _prefill(pool, sid, feed, min(plan.hit_tokens, len(feed) - 1),
             len(feed))
    return blocks, plan


def test_identical_prefixes_share_physical_blocks():
    pool = _pool()
    feed = np.arange(3 * BS + 2, dtype=np.int32)   # 3 full blocks + tail
    a, _ = _alloc_committed(pool, 0, feed)
    b, plan = _alloc_committed(pool, 1, feed)
    # the acceptance assertion: SAME physical block ids for the prefix
    assert b[:3] == a[:3] and plan.hit_tokens == 3 * BS
    assert b[3] != a[3]                            # private tails differ
    assert (pool.refcount[a[:3]] == 2).all()
    pool.check_invariants()
    # and the tail block was never published (partial)
    assert not pool.cache.is_published(a[3])


def test_full_hit_cow_leaves_source_intact():
    pool = _pool()
    feed = np.arange(2 * BS, dtype=np.int32)       # block-aligned feed
    a, _ = _alloc_committed(pool, 0, feed)
    plan = pool.plan_seq(len(feed), token_ids=feed)
    assert plan.hit_tokens == len(feed)            # fully cached
    assert plan.need_new == 1                      # COW reservation
    b = pool.alloc_seq(1, len(feed), plan=plan)
    assert b == a                                  # attached, both blocks
    # engine re-feeds the last token -> last block must COW
    assert not pool.block_writable(1, 1)
    src, dst = pool.cow(1, 1)
    assert (src, dst) == (a[1], pool.seq_blocks(1)[1]) and dst != a[1]
    # seq 0's table is untouched, the cache still serves the source
    assert pool.seq_blocks(0) == a
    assert pool.cache.is_published(src)
    assert pool.cache.stats.cow_copies == 1
    pool.check_invariants()


def test_chain_key_encodes_history_and_scale_exp():
    t = np.arange(BS, dtype=np.int32)
    assert block_key(ROOT_KEY, t, 4) != block_key(ROOT_KEY, t, 5)
    k1 = block_key(ROOT_KEY, t, 4)
    assert block_key(k1, t, 4) != k1               # same tokens, new parent
    pool = _pool()
    feed = np.arange(2 * BS, dtype=np.int32)
    _alloc_committed(pool, 0, feed)
    # same tokens at a different scale exponent: must MISS (the exponent
    # is a per-shard kernel constant — shared blocks must share it)
    plan = pool.plan_seq(len(feed), token_ids=feed, scale_exp=5)
    assert plan.hit_tokens == 0 and not plan.hit_blocks


def test_preempted_sequence_blocks_survive_for_resume():
    pool = _pool()
    feed = np.arange(2 * BS + 1, dtype=np.int32)
    a, _ = _alloc_committed(pool, 0, feed)
    pool.evict(0)                                  # preemption: release
    assert pool.stats.seq_evictions == 1
    assert pool.n_cached == 2                      # full blocks stay cached
    plan = pool.plan_seq(len(feed), token_ids=feed)
    assert plan.hit_blocks == a[:2]                # resume re-attaches
    pool.alloc_seq(0, len(feed), plan=plan)
    assert pool.seq_blocks(0)[:2] == a[:2]
    pool.check_invariants()


def test_lru_reclaim_oldest_idle_only_under_pressure():
    pool = _pool(num_blocks=7)                     # 6 usable
    f1 = np.arange(2 * BS, dtype=np.int32)
    f2 = 100 + np.arange(2 * BS, dtype=np.int32)
    a, _ = _alloc_committed(pool, 0, f1)
    b, _ = _alloc_committed(pool, 1, f2)
    pool.free_seq(0)                               # a idle (older)
    pool.free_seq(1)                               # b idle (newer)
    assert pool.n_cached == 4 and pool.n_free == 6
    # a LIVE reader pins its blocks against reclaim
    plan = pool.plan_seq(len(f2), token_ids=f2)
    pool.alloc_seq(2, len(f2), plan=plan)          # re-attach b
    # force reclaim: 2 fresh blocks needed, free stack has 2 left
    pool.alloc_seq(3, 2 * BS)
    assert pool.stats.cache_evictions == 0         # no pressure yet
    pool.alloc_seq(4, 2 * BS)                      # must reclaim from idle
    assert pool.stats.cache_evictions == 2
    # the reclaimed blocks are a's (oldest idle); b's stay — still live
    assert not pool.cache.is_published(a[0])
    assert not pool.cache.is_published(a[1])
    assert pool.cache.is_published(b[0]) and pool.cache.is_published(b[1])
    assert pool.seq_blocks(2) == b                 # live reader untouched
    pool.check_invariants()
    # and the evicted prefix now misses
    assert pool.plan_seq(len(f1), token_ids=f1).hit_tokens == 0


def test_cached_blocks_count_as_allocatable():
    pool = _pool(num_blocks=5)                     # 4 usable
    _alloc_committed(pool, 0, np.arange(4 * BS, dtype=np.int32))
    pool.free_seq(0)
    assert pool.n_free == 4 and pool.n_cached == 4
    assert pool.can_alloc(4)                       # reclaimable on demand
    pool.alloc_seq(1, 4 * BS)
    assert pool.stats.cache_evictions == 4
    pool.check_invariants()


def test_double_free_and_stale_plan_raise():
    pool = _pool()
    feed = np.arange(BS, dtype=np.int32)
    _alloc_committed(pool, 0, feed)
    pool.free_seq(0)
    with pytest.raises(BlockPoolError, match="double free"):
        pool.free_seq(0)
    with pytest.raises(BlockPoolError, match="double free"):
        pool.evict(0)
    # a plan made before the cache content changed must not attach blindly
    plan = pool.plan_seq(len(feed), token_ids=feed)
    assert plan.hit_blocks
    pool.flush_cache()
    with pytest.raises(BlockPoolError, match="stale plan"):
        pool.alloc_seq(1, len(feed), plan=plan)
    pool.check_invariants()


def test_cow_of_writable_block_is_refused():
    pool = _pool()
    pool.alloc_seq(0, BS)                          # private, unpublished
    with pytest.raises(BlockPoolError, match="writable"):
        pool.cow(0, 0)


def test_concurrent_identical_prompts_publish_once():
    """Two sequences prefill the same prompt before either publishes:
    the second publish attempt finds the key taken and stays anonymous —
    no corruption, and later requests hit the first copy."""
    pool = _pool()
    feed = np.arange(2 * BS, dtype=np.int32)
    pa = pool.plan_seq(len(feed), token_ids=feed)
    a = pool.alloc_seq(0, len(feed), plan=pa)
    pb = pool.plan_seq(len(feed), token_ids=feed)
    assert not pb.hit_blocks                       # nothing published yet
    b = pool.alloc_seq(1, len(feed), plan=pb)
    _prefill(pool, 0, feed, 0, len(feed))
    _prefill(pool, 1, feed, 0, len(feed))
    assert set(a).isdisjoint(b)                    # physically separate
    assert pool.cache.is_published(a[0]) and not pool.cache.is_published(b[0])
    plan = pool.plan_seq(len(feed), token_ids=feed)
    assert plan.hit_blocks == a                    # hits the first copy
    pool.check_invariants()


# ---------------------------------------------------------------------------
# speculative rollback (DESIGN §11)
# ---------------------------------------------------------------------------

def test_retracted_speculative_rows_never_publish():
    """The §11 rollback contract: speculative tail blocks carry no
    content key (commit never covered them), retract returns them to the
    FREE stack — not the idle cache — and the cache's key maps never see
    a rejected token."""
    pool = _pool()
    feed = np.arange(2 * BS, dtype=np.int32)
    _alloc_committed(pool, 0, feed)                # 2 published blocks
    published_before = len(pool.cache)
    # speculative growth: 2 extra blocks' worth of drafted rows, written
    # but NEVER committed
    tail = pool.extend(0, 4 * BS)
    assert len(tail) == 2
    for blk in tail:
        assert not pool.cache.is_published(blk)
    freed = pool.retract(0, 2 * BS)                # reject everything
    assert freed == 2
    assert len(pool.cache) == published_before     # no new keys, ever
    assert all(not pool.cache.is_published(b) for b in tail)
    assert all(b in pool._free for b in tail)      # free, not idle-cached
    pool.check_invariants()
    pool.free_seq(0)
    pool.check_invariants()


def test_retract_refuses_committed_and_shared_rows():
    """Rollback must never touch committed state: retracting past the
    commit point trips the chain-state cross-check, and a shared
    (published, refcount > 1) tail block refuses block-level."""
    pool = _pool()
    feed = np.arange(3 * BS, dtype=np.int32)
    _alloc_committed(pool, 0, feed)                # 3 committed blocks
    # published-block guard: the full committed tail block refuses
    with pytest.raises(BlockPoolError, match="shared/published"):
        pool.retract(0, 2 * BS)
    # chain-state guard: a PARTIAL tail block is unpublished, so only the
    # commit-position cross-check can catch rows already committed there
    feed9 = np.arange(100, 100 + 2 * BS + 2, dtype=np.int32)
    _alloc_committed(pool, 9, feed9)
    with pytest.raises(AssertionError, match="already committed"):
        pool.retract(9, 2 * BS)
    pool.free_seq(9)
    # shared-block guard: seq 1 attaches the published chain, then tries
    # to retract INTO it (simulating a caller bug) — the block-level
    # refcount/published check refuses before anything mutates
    plan = pool.plan_seq(len(feed), token_ids=feed)
    pool.alloc_seq(1, len(feed), plan=plan)
    assert plan.hit_tokens > 0
    with pytest.raises(BlockPoolError, match="shared/published"):
        pool.retract(1, 0)
    pool.check_invariants()
    pool.free_seq(0)
    pool.free_seq(1)
    pool.check_invariants()


def test_interleaved_commit_retract_traces_keep_invariants():
    """Speculate -> commit the accepted prefix -> retract the rejected
    tail, interleaved with sharing and eviction: refcounts stay exact,
    published keys always re-derive from committed tokens only, and idle
    parking/LRU reclaim never sees a speculative block."""
    for seed in range(10):
        rng = np.random.default_rng(seed)
        pool = _pool(num_blocks=int(rng.integers(12, 30)))
        shared = rng.integers(0, 40, size=4 * BS).astype(np.int32)
        live: dict[int, dict] = {}
        streams: list[dict] = []       # every seq ever admitted (kept
        next_sid = 0                   # after free: its keys may survive)
        for _ in range(70):
            op = int(rng.integers(4))
            if op == 0:                    # admit (maybe shared prefix)
                sid, next_sid = next_sid, next_sid + 1
                pfx = int(rng.integers(0, len(shared) + 1))
                tail = rng.integers(40, 80, size=int(
                    rng.integers(1, 8))).astype(np.int32)
                feed = np.concatenate([shared[:pfx], tail])
                plan = pool.plan_seq(len(feed), token_ids=feed)
                if plan.feasible:
                    pool.alloc_seq(sid, len(feed), plan=plan)
                    hit = min(plan.hit_tokens, len(feed) - 1)
                    live[sid] = {"feed": list(feed), "written": hit}
                    streams.append(live[sid])
            elif op == 1 and live:         # prefill a chunk (commits)
                sid = int(rng.choice(list(live)))
                s = live[sid]
                if s["written"] < len(s["feed"]):
                    s["written"] = _prefill(
                        pool, sid, np.asarray(s["feed"], np.int32),
                        s["written"], int(rng.integers(1, 9)))
            elif op == 2 and live:         # speculative verify round
                sid = int(rng.choice(list(live)))
                s = live[sid]
                if s["written"] < len(s["feed"]):
                    continue               # still prefilling
                k = int(rng.integers(1, 6))
                try:
                    pool.extend(sid, len(s["feed"]) + k)
                except BlockPoolError:
                    continue               # pressure: engine degrades k
                acc = int(rng.integers(0, k + 1))   # accepted drafts
                toks = [int(t) for t in rng.integers(40, 80, size=acc)]
                pool.commit(sid, len(s["feed"]), toks)
                s["feed"].extend(toks)
                s["written"] += acc
                pool.retract(sid, len(s["feed"]))
            elif op == 3 and live:         # finish or preempt
                sid = int(rng.choice(list(live)))
                (pool.free_seq if rng.integers(2) else pool.evict)(sid)
                del live[sid]
            pool.check_invariants()
        # every published key must re-derive from some sequence's
        # committed token stream prefix — never from a rejected draft
        legal = set()
        for s in streams:
            parent = ROOT_KEY
            toks = np.asarray(s["feed"], np.int32)
            for b in range(s["written"] // BS):
                parent = block_key(parent, toks[b * BS:(b + 1) * BS], 4)
                legal.add(parent)
        for key in pool.cache._by_key:
            assert key in legal, \
                "published key not derivable from any COMMITTED token " \
                "stream — a rejected speculative row leaked into the cache"
        for sid in list(live):
            pool.free_seq(sid)
        pool.check_invariants()
        assert pool.n_live == 0
