"""Property-based round-trip invariants for the Eq. 1 po2 scheme.

Runs under hypothesis.  These lock in permanently:

* quantize -> dequantize IDEMPOTENCE: the po2 grid is a fixed point of
  Eq. 1, so a second pass through the quantizer changes nothing;
* power-of-two scale MONOTONICITY: the 2^-(n+1) grid is a superset of the
  2^-n grid (grids are nested), so reconstruction error is pointwise
  non-increasing in the fractional bit — the property Algorithm 1's
  window search relies on;
* bias-shift SIGN: ``shift_requant`` with a negative shift is an exact
  LEFT shift (and matches the float round-half-away reference for either
  sign), and ``ops.int8_matmul`` agrees bit-exactly with ``int_linear``
  when ``bias_shift`` < 0 — the PR 1 negative-shift kernel regression,
  held permanently by property rather than one fixed example.
"""
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import qscheme as Q
from repro.core.integer_ops import LinearQuantSpec, int_linear
from repro.kernels import ops


def _x(seed, size=512):
    return jnp.asarray(np.random.default_rng(seed).normal(size=size) * 4.0,
                       jnp.float32)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(-3, 7), bits=st.sampled_from([4, 8, 16]),
       seed=st.integers(0, 2**16))
def test_quantize_dequantize_idempotent(n, bits, seed):
    x = _x(seed)
    fq1 = Q.fake_quant(x, n, bits)
    # float fixed point: re-quantizing the reconstruction is the identity
    assert jnp.array_equal(Q.fake_quant(fq1, n, bits), fq1)
    # integer fixed point: codes survive a dequant -> quant round trip
    c1 = Q.quant(x, n, bits)
    assert jnp.array_equal(Q.quant(Q.dequant(c1, n), n, bits), c1)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(0, 5), seed=st.integers(0, 2**16))
def test_scale_monotonicity(n, seed):
    # inputs inside the clip-free range of BOTH grids: |x| < 1, so
    # |round(x * 2^(n+1))| <= 2^6 < 127 for n <= 5 — error differences are
    # purely rounding, never clipping
    x = jnp.asarray(np.random.default_rng(seed).uniform(-1, 1, 512),
                    jnp.float32)
    assert Q.QuantParams(n + 1).scale == Q.QuantParams(n).scale / 2
    err_coarse = jnp.abs(Q.fake_quant(x, n, 8) - x)
    err_fine = jnp.abs(Q.fake_quant(x, n + 1, 8) - x)
    # nested grids: every 2^-n point is a 2^-(n+1) point, so the fine
    # error can never exceed the coarse error POINTWISE
    assert jnp.all(err_fine <= err_coarse + 1e-7)


@settings(max_examples=50, deadline=None)
@given(shift=st.integers(-6, 10), seed=st.integers(0, 2**16))
def test_shift_requant_sign(shift, seed):
    # |acc| < 2^15 keeps acc * 2^-shift exact in f32 for the reference
    acc = jnp.asarray(np.random.default_rng(seed).integers(
        -(1 << 15), 1 << 15, size=256), jnp.int32)
    got = Q.shift_requant(acc, shift)
    ref = jnp.clip(Q.round_half_away(acc.astype(jnp.float32) * 2.0 ** -shift),
                   -128, 127).astype(jnp.int8)
    assert jnp.array_equal(got, ref), f"shift={shift}"
    if shift < 0:
        # negative shift == exact left shift (the RTL's other direction)
        assert jnp.array_equal(
            got, jnp.clip(acc << -shift, -128, 127).astype(jnp.int8))


@settings(max_examples=6, deadline=None)
@given(n_b=st.integers(0, 12), relu=st.booleans(), seed=st.integers(0, 999))
def test_int8_matmul_bias_shift_sign_property(n_b, relu, seed):
    """Kernel vs jnp reference across the bias_shift sign boundary
    (n_x + n_w = 5, so n_b > 5 exercises the negative left-shift branch
    the PR 1 fix covers).  m, k, n above launch thresholds so the Pallas
    kernel body genuinely executes."""
    spec = LinearQuantSpec(n_x=2, n_w=3, n_b=n_b, n_o=4, bits=8)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-128, 128, size=(16, 128)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, size=(128, 128)), jnp.int8)
    b = jnp.asarray(rng.integers(-128, 128, size=(128,)), jnp.int8)
    got = ops.int8_matmul(x, w, b, spec, relu=relu, force_kernel=True)
    ref = int_linear(x, w, b, spec, apply_relu=relu)
    assert jnp.array_equal(got, ref), f"bias_shift={spec.bias_shift}"


@settings(max_examples=30, deadline=None)
@given(shift=st.integers(-8, -1), seed=st.integers(0, 2**16))
def test_shift_requant_negative_saturates_instead_of_wrapping(shift, seed):
    """ISSUE 5 regression: an accumulator near 2^31 / 2^|shift| must
    SATURATE through the negative-shift (left-shift) path.  The old
    ``acc << -shift`` wrapped int32 BEFORE the clip, so a large positive
    accumulator came out as -128 (sign-flipped codes) instead of 127 —
    both the jnp reference and the Pallas epilogue helper are covered."""
    from repro.kernels.int8_matmul import _shift_requant_i32
    s = -shift
    edge = (2**31 - 1) >> s             # largest magnitude that shifts exact
    rng = np.random.default_rng(seed)
    acc = jnp.asarray(np.concatenate([
        rng.integers(edge - 4, 2**31 - 1, size=64),     # wrap zone
        -rng.integers(edge - 4, 2**31 - 1, size=64),
        rng.integers(-(1 << 12), 1 << 12, size=64),     # exact zone
    ]), jnp.int32)
    ref = jnp.clip(
        jnp.round(acc.astype(jnp.float64) * 2.0 ** s), -128, 127
    ).astype(jnp.int8)
    got = Q.shift_requant(acc, shift)
    assert jnp.array_equal(got, ref), f"shift={shift}"
    got_k = _shift_requant_i32(acc, shift, -128, 127).astype(jnp.int8)
    assert jnp.array_equal(got_k, ref), f"kernel helper, shift={shift}"
    # the old bug, pinned: the largest positive accumulators must map to
    # +127, never to the negative rail
    assert int(got[0]) == 127 and int(got[64]) == -128
