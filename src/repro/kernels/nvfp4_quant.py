"""Pallas TPU kernel: fused NVFP4/MXFP4 block quantization.

One pass over the tensor: per 16-element block (along the last axis) compute
amax -> quantized shared scale (E4M3 RtN or E8M0 floor) -> E2M1 codes
(RtN or SR with explicit random bits).  HBM -> VMEM tiles via BlockSpec;
the work is on the VPU (the MXU only compacts the per-element scales to one
per block), so tiles are sized for VMEM residency and lane alignment (last
dim multiples of 128, sublane multiples of 8).

On Blackwell this step is fused into the tensor-core data path; on TPU we
expose it standalone (for cache/checkpoint packing and for the unfused
matmul) and fused into the GEMM kernel (fp4_matmul.py) for the hot path —
see DESIGN.md §3 (hardware adaptation).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantize import BlockQuantSpec
from repro.kernels import common as c


def _quant_kernel(x_ref, rbits_ref, ts_ref, codes_ref, scales_ref, *,
                  block: int, data_p: c.FmtParams, scale_p: c.FmtParams,
                  scale_is_e8m0: bool, stochastic: bool):
    x = x_ref[...].astype(jnp.float32)                    # (TM, TK)
    absmax = c.block_amax_k(x, block, 1)                  # per element
    tscale = ts_ref[0, 0]
    if scale_is_e8m0:
        scales = c.e8m0_block_scale_k(absmax, data_p.emax)
    else:
        scales = c.generic_block_scale_k(absmax, data_p.max, scale_p, tscale)
    scaled = x / (scales * tscale)
    if stochastic:
        codes = c.quantize_sr_k(scaled, data_p,
                                c.uniform_from_bits_k(rbits_ref[...]))
    else:
        codes = c.quantize_rtn_k(scaled, data_p)
    codes_ref[...] = codes.astype(codes_ref.dtype)
    # one scale per block: compact each LANE*block-wide chunk of the tile
    # to LANE scale lanes (a fixed-size selection, whatever TK is)
    tk = x.shape[1]
    step = c.LANE * block
    for lo in range(0, tk, step):
        hi = min(lo + step, tk)
        scales_ref[:, lo // block:hi // block] = c.block_first_k(
            scales[:, lo:hi], block).astype(scales_ref.dtype)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def block_quantize_pallas(
        x: jax.Array, spec: BlockQuantSpec, *,
        rbits: Optional[jax.Array] = None,
        tscale: Optional[jax.Array] = None,
        interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Quantize a 2D array along its last axis.  Returns (codes, scales).

    codes: x.shape, values on the E2M1 grid (times 1.0); scales:
    (M, K/block) float32.  Multiply codes*repeat(scales)*tscale to dequant.
    """
    if x.ndim != 2:
        raise ValueError(f"expected 2D input, got {x.shape}")
    M, K = x.shape
    B = spec.block
    if K % B:
        raise ValueError(f"K={K} not divisible by block={B}")
    if tscale is None:
        from repro.kernels.ref import tensor_scale_ref
        tscale = tensor_scale_ref(x, spec)
    tscale = jnp.asarray(tscale, jnp.float32).reshape(1, 1)
    if rbits is None:
        rbits = jnp.zeros((1, 1), jnp.uint32) if not spec.stochastic else None
    if spec.stochastic and (rbits is None or rbits.shape != x.shape):
        raise ValueError("SR requires rbits with the same shape as x")

    # the (TM, TK//B) scale tile is lane-aligned: TK is a multiple of
    # LANE*B or all of K; TM keeps a tile near 512K elements
    TK = c.pick_tile(K, c.LANE * B, c.LANE * B)
    TM = c.pick_tile(M, max(c.SUBLANE, (1 << 19) // TK), c.SUBLANE)
    grid = (M // TM, K // TK)

    kernel = functools.partial(
        _quant_kernel, block=B,
        data_p=c.FmtParams.of(spec.data), scale_p=c.FmtParams.of(spec.scale),
        scale_is_e8m0=(spec.scale_fmt == "e8m0"), stochastic=spec.stochastic)

    rb_spec = (pl.BlockSpec((TM, TK), lambda i, j: (i, j))
               if spec.stochastic else pl.BlockSpec((1, 1), lambda i, j: (0, 0)))
    if not spec.stochastic:
        rbits = jnp.zeros((1, 1), jnp.uint32)

    codes, scales = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TM, TK), lambda i, j: (i, j)),
            rb_spec,
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TM, TK), lambda i, j: (i, j)),
            pl.BlockSpec((TM, TK // B), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, K), x.dtype),
            jax.ShapeDtypeStruct((M, K // B), jnp.float32),
        ],
        interpret=interpret,
    )(x, rbits, tscale)
    return codes, scales
