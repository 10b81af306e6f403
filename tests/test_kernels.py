"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps + hypothesis.

Kernels run in interpret mode on CPU (the kernel BODY executes, so the
tiling/epilogue logic is what's validated; MXU lowering is the TPU target).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.integer_ops import LinearQuantSpec
from repro.kernels import ops, ref


def _codes(shape, seed, lo=-128, hi=128):
    return jnp.asarray(
        np.random.default_rng(seed).integers(lo, hi, size=shape), jnp.int8)


@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (256, 512, 512),
                                   (64, 128, 384), (200, 300, 130)])
@pytest.mark.parametrize("has_bias", [False, True])
def test_int8_matmul_shapes(m, k, n, has_bias):
    x, w = _codes((m, k), 1), _codes((k, n), 2)
    b = _codes((n,), 3) if has_bias else None
    spec = LinearQuantSpec(n_x=4, n_w=8, n_b=7, n_o=4)
    out = ops.int8_matmul(x, w, b, spec, force_kernel=True)
    expect = ref.int8_matmul_ref(x, w, b, shift=spec.requant_shift,
                                 bias_shift=spec.bias_shift)
    assert out.dtype == jnp.int8
    assert np.array_equal(np.asarray(out), np.asarray(expect))


def test_int8_matmul_negative_bias_shift():
    """Regression: bias grid finer than the accumulator grid (n_b > n_x+n_w).

    The epilogue used to pass ``-(-bias_shift)`` (still negative) into the
    shift helper, turning the intended rounding right-shift into a left
    shift — off by up to 2^(2|shift|) per bias element.
    """
    x, w = _codes((128, 256), 21), _codes((256, 128), 22)
    b = _codes((128,), 23)
    spec = LinearQuantSpec(n_x=2, n_w=2, n_b=10, n_o=1)
    assert spec.bias_shift < 0  # the buggy branch
    out = ops.int8_matmul(x, w, b, spec, force_kernel=True)
    expect = ref.int8_matmul_ref(x, w, b, shift=spec.requant_shift,
                                 bias_shift=spec.bias_shift)
    assert np.array_equal(np.asarray(out), np.asarray(expect))


def test_int8_matmul_batch_dims():
    x = _codes((4, 32, 256), 5)
    w = _codes((256, 128), 6)
    spec = LinearQuantSpec(n_x=4, n_w=8, n_b=8, n_o=4)
    out = ops.int8_matmul(x, w, None, spec, force_kernel=True)
    expect = ref.int8_matmul_ref(x.reshape(-1, 256), w, None,
                                 shift=spec.requant_shift).reshape(4, 32, 128)
    assert np.array_equal(np.asarray(out), np.asarray(expect))


def test_int8_matmul_fused_relu():
    x, w = _codes((128, 256), 7), _codes((256, 128), 8)
    spec = LinearQuantSpec(n_x=4, n_w=8, n_b=8, n_o=4, out_unsigned=True)
    out = ops.int8_matmul(x, w, None, spec, relu=True, force_kernel=True)
    expect = ref.int8_matmul_ref(x, w, None, shift=spec.requant_shift,
                                 relu=True, lo=0, hi=255, out_dtype=jnp.uint8)
    assert out.dtype == jnp.uint8
    assert np.array_equal(np.asarray(out), np.asarray(expect))


def test_int8_matmul_padded_tiles_negative_bias_shift():
    """Regression (W8A8 serving): zero-padded tiles cannot leak through
    bias-align under a negative ``bias_shift``.

    With k, n > 512 and non-multiples of the (bm, bk, bn) = (128, 512,
    512) tile quanta, the kernel genuinely zero-pads K and N (smaller
    operands clamp the block to the operand and never pad — see
    ``_pick_blocks``).  A finer-than-accumulator bias grid (n_b > n_x +
    n_w, bias_shift < 0) then routes every padded column's zero bias
    through the rounding right-shift; the contract is that a zero
    contribution stays exactly zero through BOTH shift signs, so the
    valid region must equal the unpadded integer reference bit-for-bit.
    """
    from repro.core.integer_ops import int_linear
    m, k, n = 150, 600, 640                 # pads to (256, 1024, 1024)
    x, w = _codes((m, k), 31), _codes((k, n), 32)
    b = _codes((n,), 33)
    spec = LinearQuantSpec(n_x=2, n_w=3, n_b=9, n_o=4)
    assert spec.bias_shift < 0 and spec.requant_shift > 0
    out = ops.int8_matmul(x, w, b, spec, force_kernel=True)
    assert out.shape == (m, n)              # padding stripped
    expect = int_linear(x, w, b, spec)      # serving's jnp reference path
    assert np.array_equal(np.asarray(out), np.asarray(expect))
    # saturating bias codes + fused relu on the same padded grid
    spec_r = LinearQuantSpec(n_x=2, n_w=3, n_b=9, n_o=4, out_unsigned=True)
    b_sat = jnp.where(jnp.arange(n) % 2 == 0, 127, -128).astype(jnp.int8)
    out_r = ops.int8_matmul(x, w, b_sat, spec_r, relu=True,
                            force_kernel=True)
    assert np.array_equal(np.asarray(out_r),
                          np.asarray(int_linear(x, w, b_sat, spec_r,
                                                apply_relu=True)))


@pytest.mark.parametrize("rows,cols", [(8, 128), (256, 512), (100, 640),
                                       (1024, 2048)])
@pytest.mark.parametrize("unsigned", [False, True])
def test_quantize_kernel(rows, cols, unsigned):
    x = jnp.asarray(np.random.default_rng(9).normal(size=(rows, cols)) * 4,
                    jnp.float32)
    out = ops.quantize_act(x, 4, unsigned=unsigned)
    expect = ref.quantize_ref(x, n=4, unsigned=unsigned)
    assert np.array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("rows,cols", [(16, 128), (256, 384)])
@pytest.mark.parametrize("relu", [False, True])
def test_residual_requant_kernel(rows, cols, relu):
    a, b = _codes((rows, cols), 10), _codes((rows, cols), 11)
    out = ops.residual_requant(a, b, n_a=5, n_b=3, n_o=4, relu=relu)
    expect = ref.residual_requant_ref(a, b, n_a=5, n_b=3, n_o=4, relu=relu)
    assert np.array_equal(np.asarray(out), np.asarray(expect))


@settings(max_examples=15, deadline=None)
@given(m=st.integers(16, 80), k=st.integers(128, 300), n=st.integers(128, 300),
       shift_in=st.integers(2, 12), seed=st.integers(0, 2**31 - 1))
def test_property_int8_matmul_any_shape(m, k, n, shift_in, seed):
    x = _codes((m, k), seed)
    w = _codes((k, n), seed + 1)
    spec = LinearQuantSpec(n_x=shift_in // 2, n_w=shift_in - shift_in // 2,
                           n_b=4, n_o=2)
    out = ops.int8_matmul(x, w, None, spec, force_kernel=True)
    expect = ref.int8_matmul_ref(x, w, None, shift=spec.requant_shift)
    assert np.array_equal(np.asarray(out), np.asarray(expect))


@settings(max_examples=15, deadline=None)
@given(n=st.integers(-2, 9), rows=st.integers(4, 40),
       cols=st.integers(100, 600), seed=st.integers(0, 2**31 - 1))
def test_property_quantize_matches_core(n, rows, cols, seed):
    from repro.core.qscheme import quant
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(rows, cols)),
                    jnp.float32)
    assert np.array_equal(np.asarray(ops.quantize_act(x, n)),
                          np.asarray(quant(x, n, 8)))
