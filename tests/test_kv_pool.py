"""Property tests for the serving engine's KV block pool (DESIGN §9).

Invariants under random alloc/extend/free/evict traces WITHOUT the prefix
cache (the refcounted sharing/COW paths live in tests/test_prefix_cache.py):
the non-trash blocks always partition into {free} ∪ {owned-by-exactly-one
-sequence}, double frees raise instead of corrupting, the trash block is
never handed out, utilization accounting matches ownership, and a live
block's Eq.-1 scale exponent never changes (codes are never requantized
while resident).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving.kv_pool import TRASH_BLOCK, BlockPool, BlockPoolError


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 100_000))
def test_random_trace_invariants(seed):
    rng = np.random.default_rng(seed)
    pool = BlockPool(num_blocks=int(rng.integers(2, 25)),
                     block_size=int(rng.integers(1, 9)), scale_exp=4)
    live: dict[int, int] = {}          # seq id -> tokens
    next_sid = 0
    for _ in range(60):
        op = int(rng.integers(4))
        if op == 0:                    # alloc a fresh sequence
            sid, next_sid = next_sid, next_sid + 1
            ntok = int(rng.integers(1, 40))
            if pool.can_alloc(pool.blocks_for(ntok)):
                blocks = pool.alloc_seq(sid, ntok)
                assert TRASH_BLOCK not in blocks
                live[sid] = ntok
            else:
                with pytest.raises(BlockPoolError):
                    pool.alloc_seq(sid, ntok)
        elif op == 1 and live:         # extend an existing sequence
            sid = int(rng.choice(list(live)))
            total = live[sid] + int(rng.integers(0, 20))
            before = pool.n_blocks_of(sid)
            try:
                new = pool.extend(sid, total)
                live[sid] = max(live[sid], total)
                assert pool.n_blocks_of(sid) == before + len(new)
            except BlockPoolError:     # atomic refusal: nothing changed
                assert pool.n_blocks_of(sid) == before
        elif op == 2 and live:         # free
            sid = int(rng.choice(list(live)))
            pool.free_seq(sid)
            del live[sid]
        elif op == 3 and live:         # evict (preemption path)
            sid = int(rng.choice(list(live)))
            pool.evict(sid)
            del live[sid]
        pool.check_invariants()
        # utilization accounting matches ownership exactly
        expect = sum(pool.blocks_for(n) for n in live.values())
        assert pool.n_live == expect
        assert pool.n_free == pool.num_blocks - 1 - expect
    for sid in list(live):
        pool.free_seq(sid)
    pool.check_invariants()
    assert pool.n_live == 0 and pool.utilization == 0.0
    assert pool.stats.frees + 0 == pool.stats.allocs  # all blocks returned


def test_double_free_raises():
    pool = BlockPool(num_blocks=4, block_size=8)
    pool.alloc_seq(7, 10)
    pool.free_seq(7)
    with pytest.raises(BlockPoolError, match="double free"):
        pool.free_seq(7)
    pool.check_invariants()


def test_double_alloc_raises():
    pool = BlockPool(num_blocks=6, block_size=8)
    pool.alloc_seq(1, 8)
    with pytest.raises(BlockPoolError, match="already allocated"):
        pool.alloc_seq(1, 8)


def test_trash_block_reserved():
    pool = BlockPool(num_blocks=5, block_size=4)
    blocks = pool.alloc_seq(0, 16)             # everything allocatable
    assert TRASH_BLOCK not in blocks and len(blocks) == 4
    assert not pool.can_alloc(1)               # trash is NOT allocatable
    # reading a table for an unknown sequence fails fast (decoding a
    # freed sequence against trash garbage must never happen silently)
    with pytest.raises(BlockPoolError, match="unknown sequence"):
        pool.table_row(999, 4)


def test_table_row_logical_order_and_padding():
    pool = BlockPool(num_blocks=10, block_size=4)
    blocks = pool.alloc_seq(3, 9)              # 3 blocks
    blocks += pool.extend(3, 14)               # +1 block
    row = pool.table_row(3, 6)
    assert row[:4].tolist() == blocks
    assert (row[4:] == TRASH_BLOCK).all()
    with pytest.raises(BlockPoolError, match="table"):
        pool.table_row(3, 2)                   # table too narrow


def test_scale_exp_written_once_and_uniform():
    pool = BlockPool(num_blocks=8, block_size=4, scale_exp=4)
    pool.alloc_seq(0, 8, scale_exp=5)
    pool.extend(0, 20)                         # inherits the seq's exponent
    assert pool.seq_scale_exp(0) == 5
    pool.alloc_seq(1, 4)                       # pool default
    assert pool.seq_scale_exp(1) == 4
    # a requantized (mutated) block is detected, never silently served
    blk = pool.table_row(0, 5)[0]
    pool.scale_exp[blk] = 2
    with pytest.raises(BlockPoolError, match="requantized"):
        pool.seq_scale_exp(0)


def test_exhaustion_counts_failures():
    pool = BlockPool(num_blocks=3, block_size=4)
    pool.alloc_seq(0, 8)
    with pytest.raises(BlockPoolError, match="exhausted"):
        pool.alloc_seq(1, 4)
    assert pool.stats.alloc_failures == 1
    with pytest.raises(BlockPoolError, match="exhausted"):
        pool.extend(0, 12)
    assert pool.stats.alloc_failures == 2


def test_evictions_counted_block_granular():
    """Regression (ISSUE 4 small fix): ``PoolStats.evictions`` counts
    evicted BLOCKS as documented (it used to count sequences); the
    per-sequence count and cache reclaims get their own counters."""
    pool = BlockPool(num_blocks=8, block_size=4)
    pool.alloc_seq(0, 12)                          # 3 blocks
    pool.alloc_seq(1, 4)                           # 1 block
    assert pool.evict(0) == 3
    assert pool.stats.evictions == 3               # blocks, not sequences
    assert pool.stats.seq_evictions == 1
    assert pool.evict(1) == 1
    assert pool.stats.evictions == 4
    assert pool.stats.seq_evictions == 2
    assert pool.stats.cache_evictions == 0         # no prefix cache here
    pool.check_invariants()


def test_retract_frees_speculative_tail():
    """DESIGN §11 rollback: retract shrinks the table to the committed
    rows, returns the rejected tail to the free stack, and is a counted,
    idempotent no-op once the tail is gone."""
    pool = BlockPool(num_blocks=10, block_size=4)
    pool.alloc_seq(0, 6)                   # 2 blocks of committed rows
    pool.extend(0, 14)                     # +2 speculative tail blocks
    free_before = pool.n_free
    assert pool.retract(0, 7) == 2         # keep 7 rows -> 2 blocks
    assert pool.n_blocks_of(0) == 2
    assert pool.n_free == free_before + 2
    assert pool.stats.retracts == 1 and pool.stats.retracted_blocks == 2
    pool.check_invariants()
    assert pool.retract(0, 7) == 0         # nothing left to roll back
    assert pool.stats.retracts == 1        # no-ops are not counted
    with pytest.raises(BlockPoolError, match="needs"):
        pool.retract(0, 99)                # cannot retract UP
    with pytest.raises(BlockPoolError, match="unknown"):
        pool.retract(5, 0)
    pool.free_seq(0)
    pool.check_invariants()


def test_random_trace_with_retract_invariants():
    """Interleaved alloc/extend/retract/free/evict traces: rollback must
    never break the free/live partition or the refcounts (cache-less
    pool; the publish-interaction traces live in test_prefix_cache)."""
    for seed in range(12):
        rng = np.random.default_rng(seed)
        pool = BlockPool(num_blocks=int(rng.integers(4, 20)),
                         block_size=int(rng.integers(1, 6)), scale_exp=4)
        live: dict[int, int] = {}          # sid -> committed rows
        spec: dict[int, int] = {}          # sid -> grown (spec) rows
        next_sid = 0
        for _ in range(80):
            op = int(rng.integers(5))
            if op == 0:
                sid, next_sid = next_sid, next_sid + 1
                ntok = int(rng.integers(1, 24))
                if pool.can_alloc(pool.blocks_for(ntok)):
                    pool.alloc_seq(sid, ntok)
                    live[sid] = ntok
                    spec[sid] = ntok
            elif op == 1 and live:         # speculative growth
                sid = int(rng.choice(list(live)))
                want = spec[sid] + int(rng.integers(1, 8))
                try:
                    pool.extend(sid, want)
                    spec[sid] = max(spec[sid], want)
                except BlockPoolError:
                    pass
            elif op == 2 and live:         # rollback to committed rows
                sid = int(rng.choice(list(live)))
                keep = int(rng.integers(live[sid], spec[sid] + 1))
                freed = pool.retract(sid, keep)
                assert pool.n_blocks_of(sid) == pool.blocks_for(
                    max(keep, 1)) or keep == 0
                assert freed >= 0
                spec[sid] = max(keep, live[sid])
                live[sid] = min(live[sid], max(keep, 1))
            elif op == 3 and live:
                sid = int(rng.choice(list(live)))
                pool.free_seq(sid)
                del live[sid], spec[sid]
            elif op == 4 and live:
                sid = int(rng.choice(list(live)))
                pool.evict(sid)
                del live[sid], spec[sid]
            pool.check_invariants()
        for sid in list(live):
            pool.free_seq(sid)
        pool.check_invariants()
        assert pool.n_live == 0
