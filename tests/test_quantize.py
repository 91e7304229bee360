"""Quantization + QuantizedLinear API tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import api, packing
from repro.core.quantize import QuantSpec, dequantize, quantize


@settings(max_examples=20, deadline=None)
@given(bits=st.integers(2, 6))
def test_grid_value_roundtrip(bits):
    spec = QuantSpec(bits, "int")
    vals = jnp.asarray(np.unique(spec.grid()).astype(np.float32))
    codes, scale = quantize(vals, spec, scale=jnp.asarray(1.0))
    back = dequantize(codes, scale, spec)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(vals))


@settings(max_examples=20, deadline=None)
@given(bits=st.integers(1, 5), seed=st.integers(0, 2**16))
def test_quantize_error_bounded(bits, seed):
    spec = QuantSpec(bits, "int")
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    codes, scale = quantize(x, spec)
    back = dequantize(codes, scale, spec)
    # max error <= half the largest grid gap (gap = 2 for the binary grid)
    max_gap = float(np.max(np.diff(np.unique(spec.grid()))))
    bound = float(scale) * max_gap / 2 * 1.02
    assert float(jnp.max(jnp.abs(back - x))) <= bound + 1e-6


@settings(max_examples=15, deadline=None)
@given(bw=st.sampled_from([1, 2, 4]), seed=st.integers(0, 2**16),
       k=st.integers(4, 48), f=st.integers(2, 24))
def test_pack_unpack_bits_roundtrip(bw, seed, k, f):
    rng = np.random.default_rng(seed)
    cpb = packing.codes_per_byte(bw)
    k = (k // cpb + 1) * cpb
    codes = jnp.asarray(rng.integers(0, 2**bw, (f, k)).astype(np.int32))
    packed = packing.pack_bits(codes, bw)
    assert packed.dtype == jnp.uint8 and packed.shape == (f, k // cpb)
    un = packing.unpack_bits(packed, bw)
    assert np.array_equal(np.asarray(un), np.asarray(codes))


@settings(max_examples=10, deadline=None)
@given(bw=st.sampled_from([1, 2, 4]), seed=st.integers(0, 2**16))
def test_quantized_linear_dequant_consistency(bw, seed):
    rng = np.random.default_rng(seed)
    k, f, b = 24, 16, 5
    w = jnp.asarray(rng.normal(size=(k, f)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    q = api.quantize_linear(w, api.LutLinearSpec(bw=bw, ba=4, mode="dequant"))
    wd = api.dequantize_weights(q)
    np.testing.assert_allclose(
        np.asarray(api.apply_linear(q, x)), np.asarray(x @ wd), rtol=2e-5, atol=2e-5
    )
    # storage really is bw/16 of bf16
    assert q.packed_bytes <= (k + 8) * f * bw / 8 + 1


def test_lut_mode_matches_dequant_up_to_activation_quant():
    rng = np.random.default_rng(0)
    k, f, b = 32, 24, 6
    w = jnp.asarray(rng.normal(size=(k, f)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    q = api.quantize_linear(w, api.LutLinearSpec(bw=2, ba=6, mode="dequant"))
    y_deq = api.apply_linear(q, x)
    q_lut = api.QuantizedLinear(
        codes=q.codes, scale=q.scale, bias=None,
        spec=api.LutLinearSpec(bw=2, ba=6, mode="lut", p=3), k=q.k,
    )
    y_lut = api.apply_linear(q_lut, x)
    rel = float(jnp.linalg.norm(y_lut - y_deq) / jnp.linalg.norm(y_deq))
    assert rel < 0.08  # ba=6 activation quantization noise only


def test_stream_mode_matches_lut_mode():
    """stream mode (tiled slice streaming) is bit-identical to lut mode."""
    rng = np.random.default_rng(0)
    k, f, b = 24, 12, 5
    w = jnp.asarray(rng.normal(size=(k, f)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    q = api.quantize_linear(w, api.LutLinearSpec(bw=2, ba=4, mode="lut", p=3))
    y_lut = api.apply_linear(q, x)
    q_s = api.QuantizedLinear(
        codes=q.codes, scale=q.scale, bias=None,
        spec=api.LutLinearSpec(bw=2, ba=4, mode="stream", p=3, tile_n=2), k=q.k,
    )
    y_stream = api.apply_linear(q_s, x)
    np.testing.assert_array_equal(np.asarray(y_stream), np.asarray(y_lut))
    stats = api.stream_stats_for(q_s, x)
    assert stats.lookups == f * (k // 3) * b
    assert stats.slices_streamed <= stats.flat_slices


def test_pallas_mode_matches_dequant():
    rng = np.random.default_rng(0)
    k, f, b = 64, 32, 4
    w = jnp.asarray(rng.normal(size=(k, f)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    q = api.quantize_linear(w, api.LutLinearSpec(bw=2, ba=4, mode="dequant"))
    y_deq = api.apply_linear(q, x)
    q_pl = api.QuantizedLinear(
        codes=q.codes, scale=q.scale, bias=None,
        spec=api.LutLinearSpec(bw=2, ba=4, mode="pallas"), k=q.k,
    )
    y_pl = api.apply_linear(q_pl, x)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_deq), rtol=2e-4, atol=2e-4)


def test_quantized_linear_is_pytree():
    w = jnp.zeros((8, 4))
    q = api.quantize_linear(w, api.LutLinearSpec(bw=2))
    leaves = jax.tree_util.tree_leaves(q)
    assert len(leaves) == 2  # codes + scale
    y = jax.jit(lambda q_, x_: api.apply_linear(q_, x_))(q, jnp.ones((3, 8)))
    assert y.shape == (3, 4)
