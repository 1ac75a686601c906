"""Block-quantization (NVFP4/MXFP4) tests."""
import numpy as np
import pytest
import jax
import ml_dtypes
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import formats
from repro.core.quantize import (MXFP4, NVFP4, BlockQuantSpec, block_quantize,
                                 fake_quant, pack_e2m1, unpack_e2m1)


def test_roundtrip_shapes():
    x = jnp.ones((4, 64), jnp.float32)
    qt = block_quantize(x, NVFP4, axis=-1)
    assert qt.codes.shape == (4, 64)
    assert qt.scales.shape == (4, 4)
    assert qt.dequant().shape == (4, 64)


@pytest.mark.parametrize("spec", [NVFP4, MXFP4,
                                  BlockQuantSpec(scale_fmt="e3m4", block=8),
                                  BlockQuantSpec(two_level=False)])
@pytest.mark.parametrize("axis", [0, 1, -1])
def test_reconstruction_error_bound(spec, axis):
    """Relative error per block bounded by FP4 resolution (~ half max ulp of
    the block: ulp(6)=2 => 1/6 of amax, plus scale rounding)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32)) * 3.0
    deq = np.asarray(fake_quant(x, spec, axis=axis))
    xb = np.asarray(x)
    err = np.abs(deq - xb)
    # per-element bound: half the largest code gap * scale; scale <= amax/4
    # (post-rounding) => err <= amax/4. Use a loose but meaningful bound.
    assert err.max() <= np.abs(xb).max() * 0.30


def test_exact_on_representable():
    """Values that are exactly scale*grid reconstruct exactly."""
    scales = 2.0 ** np.arange(-3, 3)
    grid = np.array([0, .5, 1, 1.5, 2, 3, 4, 6])
    x = (scales[:, None] * grid[None, :]).astype(np.float32)  # (6, 8)
    x = np.tile(x, (1, 2))  # block 16
    deq = np.asarray(fake_quant(jnp.asarray(x), NVFP4, axis=-1))
    np.testing.assert_allclose(deq, x, rtol=0, atol=0)


def test_codes_on_grid():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((16, 32)).astype(np.float32)) * 100
    qt = block_quantize(x, NVFP4)
    assert formats.snap_distance(np.asarray(qt.codes), formats.E2M1).max() == 0


def test_scales_on_scale_grid():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((8, 32)).astype(np.float32))
    qt = block_quantize(x, NVFP4)
    assert formats.snap_distance(np.asarray(qt.scales), formats.E4M3).max() == 0
    # MXFP4 scales are powers of two
    qt2 = block_quantize(x, MXFP4)
    log2s = np.log2(np.asarray(qt2.scales, np.float64))
    np.testing.assert_allclose(log2s, np.round(log2s), atol=0)


def test_two_level_scale_prevents_gross_clipping():
    """Without two_level, amax=1e6 >> 448*6 would clip the E4M3 block scale
    (scale saturates at 448 => values reconstruct at <= 448*6 = 2688, a 370x
    error).  With the per-tensor pow2 scale the error is bounded by block-scale
    RtN rounding (<= ulp/2 of E4M3 ~ 6%) plus code clipping."""
    x = jnp.full((1, 16), 1e6, jnp.float32)
    deq = np.asarray(fake_quant(x, NVFP4))
    np.testing.assert_allclose(deq, 1e6, rtol=0.07)
    deq_1l = np.asarray(
        fake_quant(x, BlockQuantSpec(two_level=False)))
    assert deq_1l.max() <= 448 * 6  # the failure mode two_level fixes


def test_mxfp4_ocp_scale_rule():
    # amax = 5.0: floor(log2 5)=2 -> scale = 2^(2-2) = 1
    x = jnp.asarray([[5.0] + [0.1] * 31], jnp.float32)
    qt = block_quantize(x, MXFP4)
    assert float(qt.scales[0, 0]) == 1.0


def test_bf16_exactness():
    """Simulation fidelity (DESIGN.md §3): every dequantized NVFP4 value
    (E2M1 code x E4M3 scale x pow2 tensor scale) is exactly representable in
    bf16, so bf16 MXU matmuls on dequantized operands are bit-identical to a
    native FP4 block-scaled GEMM."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((32, 64)).astype(np.float32)) * 7.3e4
    deq32 = np.asarray(fake_quant(x, NVFP4))
    roundtrip = deq32.astype(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(deq32, roundtrip)


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_sr_block_unbiased(seed):
    """Block-quant with SR is unbiased (within clipping): mean over many draws
    converges to x."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((1, 16)).astype(np.float32))
    spec = NVFP4.with_rounding(stochastic=True)
    draws = []
    for i in range(256):
        draws.append(fake_quant(x, spec, key=jax.random.PRNGKey(i)))
    mean = np.mean(np.stack(draws), axis=0)
    qt = block_quantize(x, spec, key=jax.random.PRNGKey(0))
    # Representable ceiling of the block: 6 * (E4M3-rounded scale) * tscale.
    # When the scale rounds *down*, the block's amax element saturates — the
    # one documented bias source (tail clipping; identical in FP4 hardware).
    ceil = 6.0 * float(qt.scales[0, 0] * qt.tscale)
    clipped = np.abs(np.asarray(x)) > ceil
    scale = float(jnp.max(jnp.abs(x))) / 6.0
    # SR noise per draw is <= one code gap * scale; SE shrinks as 1/sqrt(256)
    np.testing.assert_allclose(mean[~clipped], np.asarray(x)[~clipped],
                               atol=4 * scale / 16 + 1e-4)
    # clipped elements deterministically saturate to sign * ceiling
    np.testing.assert_allclose(
        np.abs(mean[clipped]), np.full(clipped.sum(), ceil), rtol=1e-6)


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((8, 32)).astype(np.float32))
    qt = block_quantize(x, NVFP4)
    packed = pack_e2m1(qt.codes)
    assert packed.dtype == jnp.uint8
    assert packed.shape == (8, 16)
    unpacked = unpack_e2m1(packed)
    np.testing.assert_array_equal(np.asarray(unpacked), np.asarray(qt.codes))


def test_zero_block():
    x = jnp.zeros((2, 32), jnp.float32)
    deq = fake_quant(x, NVFP4)
    np.testing.assert_array_equal(np.asarray(deq), 0.0)
    assert np.isfinite(np.asarray(block_quantize(x, NVFP4).scales)).all()


def test_indivisible_block_raises():
    with pytest.raises(ValueError):
        block_quantize(jnp.ones((2, 17)), NVFP4)
