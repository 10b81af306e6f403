"""Unit + property tests for the Eq. 1 quantization scheme."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import qscheme as Q


def test_quant_dequant_roundtrip_exact_on_grid():
    # values already on the 2^-n grid must be exact (equal conversion
    # between integer and float representation — paper §1.1)
    n = 4
    vals = jnp.arange(-128, 128, dtype=jnp.float32) * 2.0 ** -n
    q = Q.quant(vals, n, 8)
    assert jnp.all(Q.dequant(q, n) == vals)


def test_fake_quant_equals_dequant_quant():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(128, 64)),
                    jnp.float32)
    for n in (-2, 0, 3, 7):
        fq = Q.fake_quant(x, n, 8)
        assert np.allclose(fq, Q.dequant(Q.quant(x, n, 8), n))


def test_negative_fractional_bits_select_high_digits():
    # N_r = -3 with 8-bit width keeps digits 3..10 before the binary point
    x = jnp.asarray([1024.0, 8.0, 1000.0])
    fq = Q.fake_quant(x, -3, 8)
    assert float(fq[0]) == 1016.0  # clipped at 127 * 8
    assert float(fq[1]) == 8.0
    assert float(fq[2]) == 1000.0


def test_unsigned_range_post_relu():
    x = jnp.linspace(0, 3, 100)
    fq = Q.fake_quant(x, 6, 8, unsigned=True)
    q = Q.quant(x, 6, 8, unsigned=True)
    assert q.dtype == jnp.uint8
    assert int(q.max()) <= 255 and int(q.min()) >= 0
    assert float(jnp.max(jnp.abs(fq - jnp.clip(x, 0, 255 / 64)))) <= 2.0 ** -7


def test_round_half_away():
    x = jnp.asarray([0.5, 1.5, -0.5, -1.5, 2.5])
    r = Q.round_half_away(x)
    assert list(np.asarray(r)) == [1.0, 2.0, -1.0, -2.0, 3.0]


def test_ste_gradient_passes_inside_clips_outside():
    n, bits = 3, 8
    g = jax.grad(lambda x: jnp.sum(Q.fake_quant_ste(x, jnp.asarray(n), bits)))
    x = jnp.asarray([0.1, 100.0, -100.0, 1.0])  # 100*8 >> 127 -> clipped
    gx = g(x)
    assert list(np.asarray(gx)) == [1.0, 0.0, 0.0, 1.0]


def test_shift_requant_matches_float_path():
    """Integer shift requant == fake-quant arithmetic (paper Eq. 3/4)."""
    rng = np.random.default_rng(1)
    acc = jnp.asarray(rng.integers(-2**20, 2**20, size=(256,)), jnp.int32)
    n_in, n_out = 12, 5          # shift = 7
    out_int = Q.shift_requant(acc, n_in - n_out, bits=8)
    float_path = Q.quant(Q.dequant(acc, n_in), n_out, 8)
    assert np.array_equal(np.asarray(out_int), np.asarray(float_path))


def test_shift_requant_negative_shift_left_shifts():
    acc = jnp.asarray([3, -3], jnp.int32)
    out = Q.shift_requant(acc, -2, bits=8)
    assert list(np.asarray(out)) == [12, -12]


@settings(max_examples=50, deadline=None)
@given(n=st.integers(-4, 10), bits=st.sampled_from([4, 6, 8]),
       seed=st.integers(0, 2**31 - 1))
def test_property_quantization_error_bound(n, bits, seed):
    """|Q(r) - r| <= 2^{-n-1} for r inside the representable range."""
    rng = np.random.default_rng(seed)
    lo, hi = Q.int_bounds(bits)
    span = (hi - 1) * 2.0 ** -n
    x = jnp.asarray(rng.uniform(-span, span, size=64), jnp.float32)
    err = jnp.abs(Q.fake_quant(x, n, bits) - x)
    assert float(jnp.max(err)) <= 2.0 ** (-n - 1) + 1e-6 * 2.0 ** -n


@settings(max_examples=30, deadline=None)
@given(n=st.integers(0, 8), seed=st.integers(0, 2**31 - 1))
def test_property_idempotent(n, seed):
    """Quantization is a projection: Q(Q(x)) == Q(x)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=128), jnp.float32)
    fq = Q.fake_quant(x, n, 8)
    assert np.array_equal(np.asarray(Q.fake_quant(fq, n, 8)), np.asarray(fq))


@settings(max_examples=30, deadline=None)
@given(shift=st.integers(0, 20), seed=st.integers(0, 2**31 - 1))
def test_property_shift_requant_monotone(shift, seed):
    """Requantization preserves order (a shifter cannot swap magnitudes)."""
    rng = np.random.default_rng(seed)
    acc = np.sort(rng.integers(-2**24, 2**24, size=64)).astype(np.int32)
    out = np.asarray(Q.shift_requant(jnp.asarray(acc), shift, bits=8))
    assert np.all(np.diff(out.astype(np.int32)) >= 0)
