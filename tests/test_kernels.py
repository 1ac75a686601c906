"""Pallas kernels (interpret mode) vs pure-jnp oracles.

Bit-equality is asserted for quantization outputs (codes/scales); matmul
results are allclose (accumulation order differs between tiled Pallas
accumulation and XLA's single dot).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import fqt
from repro.core.quantize import BlockQuantSpec, NVFP4, MXFP4, block_quantize
from repro.kernels import ops, ref

I = dict(interpret=True)


def _rand(shape, seed=0, scale=1.0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.standard_normal(shape) * scale).astype(dtype))


SPECS = [
    NVFP4,
    MXFP4,
    NVFP4.with_rounding(stochastic=True),
    MXFP4.with_rounding(stochastic=True),
    BlockQuantSpec(scale_fmt="e3m4", block=8, two_level=False),
]


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: (
    f"{s.scale_fmt}-b{s.block}-{'sr' if s.stochastic else 'rtn'}"))
@pytest.mark.parametrize("shape", [(8, 32), (128, 128), (64, 256), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_kernel_matches_ref(spec, shape, dtype):
    x = _rand(shape, seed=hash((shape, str(dtype))) % 2**31).astype(dtype) * 3
    rbits = (jax.random.bits(jax.random.PRNGKey(5), shape=shape,
                             dtype=jnp.uint32) if spec.stochastic else None)
    codes_k, scales_k = ops.block_quantize(x, spec, rbits=rbits, **I)
    codes_r, scales_r = ref.block_quant_ref(x, spec, rbits=rbits, axis=-1)
    np.testing.assert_array_equal(np.asarray(codes_k), np.asarray(codes_r))
    np.testing.assert_array_equal(np.asarray(scales_k), np.asarray(scales_r))


def test_quant_kernel_matches_core_block_quantize():
    """Kernel semantics == repro.core.quantize.block_quantize (RtN)."""
    x = _rand((64, 128), 3, 2.5)
    codes_k, scales_k = ops.block_quantize(x, NVFP4, **I)
    qt = block_quantize(x, NVFP4, axis=-1)
    np.testing.assert_array_equal(np.asarray(codes_k), np.asarray(qt.codes))
    np.testing.assert_array_equal(np.asarray(scales_k),
                                  np.asarray(qt.scales, np.float32))


@pytest.mark.parametrize("shape_mnk", [(32, 32, 32), (128, 128, 256),
                                       (64, 48, 512), (16, 128, 64)])
def test_block_matmul_matches_ref(shape_mnk):
    M, N, K = shape_mnk
    a = _rand((M, K), 11)
    b = _rand((K, N), 12)
    ac, asc = ref.block_quant_ref(a, NVFP4, axis=1)
    bc, bsc = ref.block_quant_ref(b, NVFP4, axis=0)
    ts = ref.tensor_scale_ref(a, NVFP4) * ref.tensor_scale_ref(b, NVFP4)
    out_k = ops.block_matmul(ac, asc, bc, bsc, ts, block=16, **I)
    out_r = ref.block_matmul_ref(ac, asc, bc, bsc, ts, 16)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sr", [False, True])
@pytest.mark.parametrize("shape_mnk", [(64, 64, 64), (128, 96, 256)])
def test_fused_quant_matmul_matches_ref(shape_mnk, sr):
    M, N, K = shape_mnk
    a = _rand((M, K), 21, 1.5)
    b = _rand((K, N), 22, 0.7)
    spec = NVFP4.with_rounding(stochastic=sr)
    arb = (jax.random.bits(jax.random.PRNGKey(1), shape=(M, K),
                           dtype=jnp.uint32) if sr else None)
    brb = (jax.random.bits(jax.random.PRNGKey(2), shape=(K, N),
                           dtype=jnp.uint32) if sr else None)
    out_k = ops.fused_quant_matmul(a, b, spec, spec, a_rbits=arb,
                                   b_rbits=brb, **I)
    out_r = ref.fused_quant_matmul_ref(a, b, spec, spec, a_rbits=arb,
                                       b_rbits=brb)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


def test_fqt_jnp_vs_pallas_forward():
    """The two fp4_matmul impls produce identical quantized operands; outputs
    agree to accumulation order."""
    x, w = _rand((64, 128), 31), _rand((128, 96), 32)
    y_j = fqt.fp4_matmul(x, w, cfg=fqt.nvfp4_paper_config("jnp"),
                         seed=jnp.uint32(9))
    y_p = fqt.fp4_matmul(x, w, cfg=fqt.nvfp4_paper_config("pallas"),
                         seed=jnp.uint32(9))
    np.testing.assert_allclose(np.asarray(y_j), np.asarray(y_p),
                               rtol=2e-5, atol=2e-5)


def test_fqt_jnp_vs_pallas_grads():
    """SR streams are shared between impls => same stochastic decisions."""
    x, w = _rand((64, 64), 33), _rand((64, 64), 34)
    c = _rand((64, 64), 35)

    def grads(impl):
        cfg = fqt.nvfp4_paper_config(impl)

        def loss(x, w):
            return jnp.sum(fqt.fp4_matmul(x, w, cfg=cfg, seed=jnp.uint32(4)) * c)
        return jax.grad(loss, argnums=(0, 1))(x, w)

    (dxj, dwj), (dxp, dwp) = grads("jnp"), grads("pallas")
    np.testing.assert_allclose(np.asarray(dxj), np.asarray(dxp),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dwj), np.asarray(dwp),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 8), st.integers(1, 8), st.integers(1, 8),
       st.integers(0, 2 ** 31 - 1))
def test_fused_matmul_property(mb, nb, kb, seed):
    """Random block-multiple shapes: kernel == oracle."""
    M, N, K = 8 * mb, 8 * nb, 16 * kb
    a = _rand((M, K), seed % 1000, 1.1)
    b = _rand((K, N), seed % 997, 0.9)
    out_k = ops.fused_quant_matmul(a, b, NVFP4, NVFP4, **I)
    out_r = ref.fused_quant_matmul_ref(a, b, NVFP4, NVFP4)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


def test_kernel_vmem_tiling_shapes():
    """Tiles must divide dims; uneven dims fall back to full-dim tiles."""
    a = _rand((24, 48), 41)
    b = _rand((48, 40), 42)
    out_k = ops.fused_quant_matmul(a, b, NVFP4, NVFP4, **I)
    out_r = ref.fused_quant_matmul_ref(a, b, NVFP4, NVFP4)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)
