"""Property tests for the fixed-slab recurrent-state substrate (§16).

Runs under hypothesis.  Locked in permanently:

* the slab-pool partition invariant — after ANY random interleaving of
  alloc / free / evict, {free} ∪ {live} exactly covers the non-trash
  slabs, every live sequence owns exactly one slab, and no refcount
  exceeds 1 (recurrent state is never shared);
* exhaustion is a clean refusal (``BlockPoolError`` + an
  ``alloc_failures`` count), never a corrupt handout;
* the growing-substrate verbs — extend / retract / COW — raise outright
  on slabs, mirroring the scheduler-level guards one layer down;
* the per-slab scale exponent is admission-time metadata: fixed from
  alloc to free, re-assignable only to a NEW owner of the slab;
* the Eq.-1 round trip on a po2 grid with fractional bit n reconstructs
  every in-range value to within half a step, ``2^-(n+1)`` — the bound
  the once-per-step whole-state requantization (and DESIGN §16's error
  story) leans on.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import qscheme as Q
from repro.serving import (BlockPoolError, StateSlabPool, TRASH_SLAB,
                           substrate_for)
from repro.configs import get_smoke_config


# -- pool lifecycle ---------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(num_slabs=st.integers(2, 12), seed=st.integers(0, 10_000))
def test_random_lifecycle_preserves_invariants(num_slabs, seed):
    """Random alloc/free/evict interleavings never break the partition."""
    rng = np.random.default_rng(seed)
    pool = StateSlabPool(num_slabs, scale_exp=4)
    live: dict[int, int] = {}           # seq -> slab (reference model)
    next_sid = 0
    for _ in range(60):
        op = rng.integers(3)
        if op == 0:                      # alloc
            sid, next_sid = next_sid, next_sid + 1
            if pool.n_free == 0:
                before = pool.stats.alloc_failures
                with pytest.raises(BlockPoolError):
                    pool.alloc_slab(sid)
                assert pool.stats.alloc_failures == before + 1
            else:
                slab = pool.alloc_slab(sid)
                assert slab != TRASH_SLAB
                assert slab not in live.values()
                live[sid] = slab
        elif live and op == 1:           # free
            sid = int(rng.choice(list(live)))
            del live[sid]
            assert pool.free_seq(sid) == 1
        elif live and op == 2:           # evict (preemption path)
            sid = int(rng.choice(list(live)))
            del live[sid]
            before = pool.stats.seq_evictions
            assert pool.evict(sid) == 1
            assert pool.stats.seq_evictions == before + 1
        pool.check_invariants()
        assert pool.n_live == len(live)
        assert {s: b[0] for s, b in pool._seqs.items()} == live
    # drain and verify the pool returns to pristine capacity
    for sid in list(live):
        pool.free_seq(sid)
    pool.check_invariants()
    assert pool.n_free == num_slabs - 1 and pool.n_live == 0


def test_double_ops_raise():
    pool = StateSlabPool(4)
    pool.alloc_slab(7)
    with pytest.raises(BlockPoolError):
        pool.alloc_slab(7)              # one slab per sequence, ever
    pool.free_seq(7)
    with pytest.raises(BlockPoolError):
        pool.free_seq(7)                # double free
    with pytest.raises(BlockPoolError):
        pool.slab_of(7)                 # unknown after free


def test_growing_substrate_verbs_raise_on_slabs():
    pool = StateSlabPool(4)
    pool.alloc_slab(0)
    for verb, arg in (("extend", 32), ("retract", 8), ("cow", 0)):
        with pytest.raises(BlockPoolError, match="slab|shared"):
            getattr(pool, verb)(0, arg)
    pool.check_invariants()             # failed verbs left nothing behind


def test_scale_exp_fixed_per_owner():
    """The exponent is admission-time metadata: constant while owned,
    re-assignable only when the slab moves to a new sequence."""
    pool = StateSlabPool(3, scale_exp=4)
    s0 = pool.alloc_slab(0)             # default exponent
    s1 = pool.alloc_slab(1, scale_exp=6)
    assert pool.slab_exp(0) == 4 and pool.slab_exp(1) == 6
    pool.free_seq(1)
    s2 = pool.alloc_slab(2, scale_exp=2)
    assert s2 == s1                     # LIFO reuse of the freed slab
    assert pool.slab_exp(2) == 2        # new owner, new grid
    assert pool.slab_exp(0) == 4 and s0 != s2


def test_reset_free_order_restores_pristine_lifo():
    pool = StateSlabPool(5)
    order = [pool.alloc_slab(i) for i in range(3)]
    for i in (1, 0, 2):
        pool.free_seq(i)
    pool.reset_free_order()
    assert [pool.alloc_slab(10 + i) for i in range(3)] == sorted(order)


# -- substrate routing ------------------------------------------------------

def test_substrate_for_routes_by_family():
    att = substrate_for(get_smoke_config("qwen3_1_7b"))
    rec = substrate_for(get_smoke_config("rwkv6_3b"))
    hyb = substrate_for(get_smoke_config("zamba2_2_7b"))
    assert (att.kind, att.grows, att.fixed_state) == ("attention",
                                                      True, False)
    assert (rec.kind, rec.grows, rec.fixed_state) == ("recurrent",
                                                      False, True)
    assert (hyb.kind, hyb.grows, hyb.fixed_state) == ("hybrid",
                                                      True, True)
    # fixed state forbids everything the growing substrate supports
    for sub in (rec, hyb):
        assert not (sub.supports_spec or sub.supports_prefix_cache
                    or sub.supports_ragged)
    # snapshot preemption needs the WHOLE sequence state in the slab;
    # a hybrid's KV half recomputes, so it falls back to recompute
    assert rec.snapshot_preempt and not hyb.snapshot_preempt


# -- Eq.-1 round trip on the slab grid --------------------------------------

@settings(max_examples=25, deadline=None)
@given(n=st.integers(0, 7), seed=st.integers(0, 10_000))
def test_state_roundtrip_error_within_half_step(n, seed):
    """|dequant(quant(x, n)) - x| <= 2^-(n+1) for every representable x —
    the per-element bound of the once-per-step slab requantization."""
    rng = np.random.default_rng(seed)
    hi = 127.0 * 2.0 ** -n              # signed-8-bit representable range
    x = jnp.asarray(rng.uniform(-hi, hi, size=(4, 64)), jnp.float32)
    back = Q.dequant(Q.quant(x, n), n)
    err = np.abs(np.asarray(back - x))
    assert err.max() <= 2.0 ** -(n + 1) + 1e-7
    # and the grid is a fixed point: a second pass changes nothing
    again = Q.dequant(Q.quant(back, n), n)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(again))
