"""Property tests for Algorithm-1 calibration (Eq. 5/6, DESIGN §3/§13).

Runs under hypothesis.  Properties:

  * the chosen (N_w, N_b, N_o) always lie inside the Eq.-6 narrowed
    windows ``[N^max - tau, N^max]`` of their tensors;
  * the winning reconstruction error is monotone non-increasing in tau
    (a wider window can only add candidates);
  * threading N_o -> N_x across two chained modules (``chain=``) equals
    calibrating the downstream module on the already-quantized upstream
    output — the paper's sequential joint scheme, stated as an equality;
  * calibration is deterministic for a fixed seed.
"""
import numpy as np
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.core.calibrate import calibrate_linear_module
from repro.core.lm_calibrate import calibrate_lm
from repro.core.qmodel import qlinear
from repro.core.qscheme import fake_quant, search_window


def _mats(seed, with_bias):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, rng.uniform(0.3, 3.0), (32, 16)),
                    jnp.float32)
    w = jnp.asarray(rng.normal(0, rng.uniform(0.01, 0.5), (16, 12)),
                    jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, (12,)), jnp.float32) \
        if with_bias else None
    return x, w, b


def _apply(xx, wq, bq):
    y = xx.astype(jnp.float32) @ wq.astype(jnp.float32)
    return y + bq.astype(jnp.float32) if bq is not None else y


def _o_ref(x, w, b):
    y = x.astype(jnp.float32) @ w.astype(jnp.float32)
    return y + b.astype(jnp.float32) if b is not None else y


def _cands(t, tau, bits=8):
    lo, hi = search_window(t, tau)
    return {(bits - 1) - i for i in range(lo, hi + 1)}


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), tau=st.integers(1, 5),
       with_bias=st.booleans())
def test_chosen_bits_inside_eq6_windows(seed, tau, with_bias):
    x, w, b = _mats(seed, with_bias)
    o_ref = _o_ref(x, w, b)
    r = calibrate_linear_module(fake_quant(x, 4), w, b, o_ref, _apply,
                                tau=tau)
    assert r.n_w in _cands(w, tau)
    assert (r.n_b is None) == (b is None)
    if b is not None:
        assert r.n_b in _cands(b, tau)
    assert r.n_o in _cands(o_ref, tau)
    assert np.isfinite(r.error) and r.error >= 0
    assert r.fp_norm > 0


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 10_000), tau=st.integers(1, 4),
       with_bias=st.booleans())
def test_error_monotone_non_increasing_in_tau(seed, tau, with_bias):
    """Eq. 6 widens with tau: every tau-window candidate is also a
    (tau+2)-window candidate, so the best error cannot get worse."""
    x, w, b = _mats(seed, with_bias)
    o_ref = _o_ref(x, w, b)
    xq = fake_quant(x, 4)
    r_narrow = calibrate_linear_module(xq, w, b, o_ref, _apply, tau=tau)
    r_wide = calibrate_linear_module(xq, w, b, o_ref, _apply, tau=tau + 2)
    assert r_wide.error <= r_narrow.error + 1e-6


def _two_module_forward(params, batch, ctx):
    h = qlinear(ctx, "m1", batch["x"], params["w1"])
    return qlinear(ctx, "m2", h, params["w2"])


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_threading_equals_calibrating_on_quantized_input(seed):
    """The chain edge m1 -> m2 must make calibrate_lm's m2 result EQUAL
    to hand-calibrating m2 on fake_quant(h, m1.n_o) — the one place the
    sequential joint scheme is more than bookkeeping."""
    rng = np.random.default_rng(seed)
    params = {"w1": jnp.asarray(rng.normal(0, 0.3, (16, 12)), jnp.float32),
              "w2": jnp.asarray(rng.normal(0, 0.3, (12, 8)), jnp.float32)}
    batch = {"x": jnp.asarray(rng.normal(0, 1.0, (32, 16)), jnp.float32)}
    ctx, report = calibrate_lm(_two_module_forward, params, batch,
                               chain={"m2": "m1"})
    m1, m2 = ctx.table["m1"], ctx.table["m2"]
    assert m2.n_x == m1.n_o

    # the upstream float output IS m2's captured input
    h = _o_ref(batch["x"], params["w1"], None)
    manual = calibrate_linear_module(
        fake_quant(h, m1.n_o), params["w2"], None,
        _o_ref(h, params["w2"], None), _apply)
    assert (m2.n_w, m2.n_b, m2.n_o) == (manual.n_w, manual.n_b, manual.n_o)
    assert np.isclose(report.results["m2"].error, manual.error, rtol=1e-5)

    # chain={} must disable threading: m2 goes through the fresh-input
    # N_x search instead of inheriting m1's output grid
    ctx_off, _ = calibrate_lm(_two_module_forward, params, batch, chain={})
    nx_hi = (8 - 1) - search_window(h, 0)[1]
    assert ctx_off.table["m2"].n_x in (nx_hi, nx_hi + 1, nx_hi + 2)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_deterministic_for_fixed_inputs(seed):
    rng = np.random.default_rng(seed)
    params = {"w1": jnp.asarray(rng.normal(0, 0.3, (16, 12)), jnp.float32),
              "w2": jnp.asarray(rng.normal(0, 0.3, (12, 8)), jnp.float32)}
    batch = {"x": jnp.asarray(rng.normal(0, 1.0, (32, 16)), jnp.float32)}
    ctx_a, rep_a = calibrate_lm(_two_module_forward, params, batch)
    ctx_b, rep_b = calibrate_lm(_two_module_forward, params, batch)
    assert dict(ctx_a.table) == dict(ctx_b.table)
    for name in rep_a.results:
        assert rep_a.results[name].error == rep_b.results[name].error
