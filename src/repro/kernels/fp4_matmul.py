"""Pallas TPU kernels: block-scaled FP4 matmul (unfused and fused-quant).

TPU-native adaptation of Blackwell's block-scaled FP4 MMA (DESIGN.md §3):

  * ``block_matmul``: consumes pre-quantized (codes, scales) operands; each
    grid step loads (TM,TK)/(TK,TN) tiles into VMEM, dequantizes in VREGs
    (codes * broadcast(scales) — exact in bf16), and feeds the MXU with an
    fp32-accumulating dot.  Accumulation runs over the innermost K grid axis
    into the output tile (revisited, standard Pallas matmul pattern).

  * ``fused_quant_matmul``: additionally quantizes *raw* bf16/f32 operand
    tiles on the fly (amax -> scale -> codes in VREGs, RtN or SR with
    explicit random bits), so quantization costs zero extra HBM traffic.
    This is the kernel the FQT layer uses for all three training GEMMs
    (operands pre-transposed so blocks always lie along the contraction
    axis: A (M,K) blocked along K/axis-1, B (K,N) blocked along K/axis-0).

  * ``packed_block_matmul``: the served GEMM, activation x packed NVFP4
    weight, decoding the weight's 4-bit codes tile by tile in VMEM.

Tile defaults (TM,TN,TK)=(128,128,512): MXU-aligned (128 lanes), VMEM use
~1.2 MB for the fused kernel at fp32 — comfortably within the ~16 MB/core
budget while leaving room for double buffering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import BlockQuantSpec
from repro.kernels import common as c


# ---- unfused: pre-quantized operands ----------------------------------------


def _block_matmul_kernel(ac_ref, as_ref, bc_ref, bs_ref, o_ref, *, block: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ac = ac_ref[...].astype(jnp.float32)          # (TM, TK)
    bc = bc_ref[...].astype(jnp.float32)          # (TK, TN)
    ad = ac * c.block_repeat_k(as_ref[...], block, 1)   # scales (TM, TK//B)
    bd = bc * c.block_repeat_k(bs_ref[...], block, 0)   # scales (TK//B, TN)
    o_ref[...] += jnp.dot(ad, bd, preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "tm", "tn", "tk"))
def block_matmul(a_codes, a_scales, b_codes, b_scales, tscale, *,
                 block: int = 16, interpret: bool = False,
                 tm: int = 128, tn: int = 128, tk: int = 512) -> jax.Array:
    """(M,K) @ (K,N) with per-block scales; returns fp32 (M,N) * tscale."""
    M, K = a_codes.shape
    K2, N = b_codes.shape
    assert K == K2, (a_codes.shape, b_codes.shape)
    TM, TN = c.pick_tile(M, tm, c.SUBLANE), c.pick_tile(N, tn, c.LANE)
    # the (TM, TK//block) scale tile is lane-aligned too
    TK = c.pick_tile(K, tk, c.LANE * block)
    grid = (M // TM, N // TN, K // TK)

    out = pl.pallas_call(
        functools.partial(_block_matmul_kernel, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TM, TK), lambda i, j, k: (i, k)),
            pl.BlockSpec((TM, TK // block), lambda i, j, k: (i, k)),
            pl.BlockSpec((TK, TN), lambda i, j, k: (k, j)),
            pl.BlockSpec((TK // block, TN), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(a_codes, a_scales, b_codes, b_scales)
    return out * tscale


# ---- fused: quantize raw tiles on the fly, then MMA -------------------------


def _quant_tile(x, rb, tscale, *, axis, block, data_p, scale_p, scale_is_e8m0,
                stochastic):
    """Quantize a tile with blocks along ``axis``; returns the dequantized
    tile (codes*scales, tscale NOT applied — folded into the epilogue).
    Elementwise on the tile's own layout: the block absmax is broadcast
    back by ``block_amax_k``, so no reshape splits the vreg tiling."""
    absmax = c.block_amax_k(x, block, axis)
    if scale_is_e8m0:
        scales = c.e8m0_block_scale_k(absmax, data_p.emax)
    else:
        scales = c.generic_block_scale_k(absmax, data_p.max, scale_p, tscale)
    scaled = x / (scales * tscale)
    if stochastic:
        codes = c.quantize_sr_k(scaled, data_p, c.uniform_from_bits_k(rb))
    else:
        codes = c.quantize_rtn_k(scaled, data_p)
    return codes * scales


def _quant_params(spec: BlockQuantSpec) -> dict:
    return dict(data_p=c.FmtParams.of(spec.data),
                scale_p=c.FmtParams.of(spec.scale),
                scale_is_e8m0=(spec.scale_fmt == "e8m0"),
                stochastic=spec.stochastic)


def _mxu_dtype(*specs: BlockQuantSpec):
    """bf16 when every operand's dequantized values (code * block scale)
    are exact in bf16 — true for E2M1 codes with E4M3 or E8M0 scales — so
    the MXU runs one bf16 pass with f32 accumulation; else f32."""
    def sig_bits(s):
        scale_bits = 0 if s.scale_fmt == "e8m0" else s.scale.man_bits
        return s.data.man_bits + scale_bits + 2
    return (jnp.bfloat16 if all(sig_bits(s) <= 8 for s in specs)
            else jnp.float32)


def _fused_kernel(a_ref, b_ref, arb_ref, brb_ref, tsa_ref, tsb_ref, o_ref, *,
                  block: int, qa: dict, qb: dict, mxu_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    tsa = tsa_ref[0, 0]
    tsb = tsb_ref[0, 0]
    a = a_ref[...].astype(jnp.float32)            # (TM, TK) blocked along TK
    b = b_ref[...].astype(jnp.float32)            # (TK, TN) blocked along TK
    ad = _quant_tile(a, arb_ref[...], tsa, axis=1, block=block, **qa)
    bd = _quant_tile(b, brb_ref[...], tsb, axis=0, block=block, **qb)
    o_ref[...] += jnp.dot(ad.astype(mxu_dtype), bd.astype(mxu_dtype),
                          preferred_element_type=jnp.float32) * (tsa * tsb)


@functools.partial(jax.jit, static_argnames=(
    "spec_a", "spec_b", "interpret", "tm", "tn", "tk", "out_dtype"))
def fused_quant_matmul(a: jax.Array, b: jax.Array,
                       spec_a: BlockQuantSpec, spec_b: BlockQuantSpec, *,
                       a_rbits: Optional[jax.Array] = None,
                       b_rbits: Optional[jax.Array] = None,
                       out_dtype=jnp.float32, interpret: bool = False,
                       tm: int = 128, tn: int = 128,
                       tk: int = 512) -> jax.Array:
    """Quantize-a (blocks along axis1) x quantize-b (blocks along axis0) GEMM.

    The FQT hot path: one pallas_call per training GEMM, quantization fused.
    """
    if spec_a.block != spec_b.block:
        raise ValueError("operand block sizes must match")
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    B = spec_a.block
    if K % B:
        raise ValueError(f"K={K} not divisible by block={B}")

    from repro.kernels.ref import tensor_scale_ref
    tsa = tensor_scale_ref(a, spec_a).reshape(1, 1)
    tsb = tensor_scale_ref(b, spec_b).reshape(1, 1)

    dummy = jnp.zeros((1, 1), jnp.uint32)
    if not spec_a.stochastic:
        a_rbits = dummy
    if not spec_b.stochastic:
        b_rbits = dummy
    if spec_a.stochastic and (a_rbits is None or a_rbits.shape != a.shape):
        raise ValueError("spec_a stochastic requires a_rbits of a.shape")
    if spec_b.stochastic and (b_rbits is None or b_rbits.shape != b.shape):
        raise ValueError("spec_b stochastic requires b_rbits of b.shape")

    TM, TN = c.pick_tile(M, tm, c.SUBLANE), c.pick_tile(N, tn, c.LANE)
    TK = c.pick_tile(K, tk, c.LANE)
    grid = (M // TM, N // TN, K // TK)

    def _rb_spec(stoch, shape_map):
        if stoch:
            return pl.BlockSpec(*shape_map)
        return pl.BlockSpec((1, 1), lambda i, j, k: (0, 0))

    kernel = functools.partial(
        _fused_kernel, block=B, qa=_quant_params(spec_a),
        qb=_quant_params(spec_b), mxu_dtype=_mxu_dtype(spec_a, spec_b))

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TM, TK), lambda i, j, k: (i, k)),
            pl.BlockSpec((TK, TN), lambda i, j, k: (k, j)),
            _rb_spec(spec_a.stochastic, ((TM, TK), lambda i, j, k: (i, k))),
            _rb_spec(spec_b.stochastic, ((TK, TN), lambda i, j, k: (k, j))),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(a, b, a_rbits, b_rbits, tsa, tsb)
    return out.astype(out_dtype)


# ---- packed weights: activation x packed NVFP4 weight ----------------------
#
# The served GEMM.  The weight stays in HBM as its packed store, (K/2, N)
# uint8 codes paired along K (byte r of block b holds rows r and
# r + block/2 of that block, ``quantize._pack_blocked``) and (K/block, N)
# block scales, and is dequantized tile by tile in VMEM: no bf16 copy of
# it is ever written.  The activation comes in already quantized (RtN at
# ``fqt.q.fwd_a``) and is split by the wrapper into the columns that meet
# the low nibbles and those that meet the high ones, so each code plane
# multiplies its own half of A and no lane is interleaved in the kernel.

# Largest tiles: TM rows of A (the 64-slot decode and the 256-token prefill
# are one row tile), TN and TK of the weight (the fastest on v5e at the
# serve cell's shapes, PERF.md §5).
TM_MAX, TN_MAX, TK_MAX = 256, 2048, 4096
SCALE_ROWS = 32               # sublanes of an 8-bit tile: a chunk's scale rows


def packed_tiles(M: int, K: int, N: int, block: int) -> Optional[tuple]:
    """(TM, TN, TK, CK) for an (M, K) x packed (K, N) GEMM, or None where
    the kernel does not take the shape.  TM is M up to TM_MAX; above it,
    the fewest row tiles, each a multiple of 16 rows (every row tile
    decodes the weight again, so M is padded by less than 16 a tile
    rather than up to a multiple of TM_MAX).  TN is a multiple of 128
    dividing N; TK divides K and is a whole number of chunks of CK code
    rows, a chunk being the rows whose scales fill one 8-bit tile (32
    blocks), or TK = K (one chunk) where K has no such chunk."""
    if N % c.LANE or K % block or block % 16:
        return None
    n_m = -(-M // TM_MAX)
    TM = M if n_m == 1 else -(-M // (16 * n_m)) * 16
    TN = c.pick_tile(N, TN_MAX, c.LANE)
    span = SCALE_ROWS * block                    # K rows of one chunk
    if K % span == 0:
        return TM, TN, c.pick_tile(K, max(TK_MAX, span), span), span // 2
    if K % (2 * c.LANE) or K > TK_MAX:
        return None
    return TM, TN, K, K // 2


def _nibble_k(p: jax.Array, shift: int) -> jax.Array:
    """f32 E2M1 grid values of the nibble at bit ``shift`` (0 or 4) of the
    int32 bytes ``p``: the magnitude bits eem go under an f32 exponent
    bias of 126, which is exact for e > 0 and gives 0.5 + m/4 for e = 0;
    min(x, 2x - 1) maps that to m/2 and leaves x >= 1 as it is.  The sign
    bit is or-ed in last."""
    bits = ((p << (22 - shift)) & (7 << 22)) + (126 << 23)
    mag = jax.lax.bitcast_convert_type(bits, jnp.float32)
    mag = jnp.minimum(mag, mag + mag - 1.0)
    sign = (p << (28 - shift)) & jnp.int32(-2 ** 31)
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(mag, jnp.int32) | sign, jnp.float32)


def _packed_kernel(alo_ref, ahi_ref, w_ref, s_ref, t_ref, o_ref, acc_ref, *,
                   half: int, ck: int, n_chunks: int):
    """acc += A_lo @ W_lo + A_hi @ W_hi over one (TK/2, TN) code tile, in
    chunks of ``ck`` code rows: each chunk's bytes are decoded on the VPU
    into both nibble planes, each plane's rows scaled by their block's
    scale (one scale row per ``half`` code rows), and multiplied on the
    MXU with f32 accumulation.  The tensor scale is applied once, at the
    last K step."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tn = w_ref.shape[-1]
    nb = ck // half
    dt = alo_ref.dtype

    def chunk(i, carry):
        r = pl.multiple_of(i * ck, ck)
        p = w_ref[pl.ds(r, ck), :].astype(jnp.int32)
        s = s_ref[pl.ds(pl.multiple_of(i * nb, nb), nb), :].astype(
            jnp.float32)
        s = jnp.broadcast_to(s[:, None, :], (nb, half, tn)).reshape(ck, tn)
        acc_ref[...] += (
            jnp.dot(alo_ref[:, pl.ds(r, ck)], (_nibble_k(p, 0) * s).astype(dt),
                    preferred_element_type=jnp.float32)
            + jnp.dot(ahi_ref[:, pl.ds(r, ck)],
                      (_nibble_k(p, 4) * s).astype(dt),
                      preferred_element_type=jnp.float32))
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * t_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "out_dtype",
                                             "interpret"))
def packed_block_matmul(a: jax.Array, b_packed: jax.Array,
                        b_scales: jax.Array, b_tscale: jax.Array, *,
                        block: int = 16, out_dtype=None,
                        interpret: bool = False) -> jax.Array:
    """(M, K) activation x packed (K, N) NVFP4 weight -> (M, N).

    ``b_packed``: (K/2, N) uint8, ``b_scales``: (K/block, N) block scales
    (float8 or the source dtype), ``b_tscale``: the weight's scalar pow2
    tensor scale.  ``a`` is multiplied as given (quantize it first); its
    dtype is the MXU's.  Accumulates in f32; only the order of that
    accumulation differs from ``a @ dequant(b)``.
    """
    M, K = a.shape
    N = b_packed.shape[1]
    if b_packed.shape[0] * 2 != K or b_scales.shape != (K // block, N):
        raise ValueError(f"packed weight {b_packed.shape}/{b_scales.shape} "
                         f"does not match K={K}, block={block}")
    tiles = packed_tiles(M, K, N, block)
    if tiles is None:
        raise ValueError(f"no packed GEMM tiling for ({M}, {K}) x ({K}, {N})")
    TM, TN, TK, CK = tiles
    out_dtype = a.dtype if out_dtype is None else out_dtype
    half = block // 2
    # the columns of A that meet each nibble plane, in code-row order
    a4 = a.reshape(M, K // block, 2, half)
    a_lo = a4[:, :, 0, :].reshape(M, K // 2)
    a_hi = a4[:, :, 1, :].reshape(M, K // 2)
    Mp = -(-M // TM) * TM
    if Mp != M:
        a_lo = jnp.pad(a_lo, ((0, Mp - M), (0, 0)))
        a_hi = jnp.pad(a_hi, ((0, Mp - M), (0, 0)))
    ts = jnp.asarray(b_tscale, jnp.float32).reshape(1, 1)
    isz = jnp.dtype(a.dtype).itemsize
    osz = jnp.dtype(out_dtype).itemsize
    ssz = b_scales.dtype.itemsize
    # double-buffered inputs, the accumulator and output, and a chunk's
    # decode temporaries (~24 bytes a weight: int32 codes, f32 planes)
    vmem = (2 * (TM * TK * isz + TK // 2 * TN + TK // block * TN * ssz)
            + TM * TN * (4 + 2 * osz) + 24 * CK * TN)
    a_spec = pl.BlockSpec((TM, TK // 2), lambda j, i, k: (i, k))
    out = pl.pallas_call(
        functools.partial(_packed_kernel, half=half, ck=CK,
                          n_chunks=TK // 2 // CK),
        grid=(N // TN, Mp // TM, K // TK),
        in_specs=[
            a_spec, a_spec,
            pl.BlockSpec((TK // 2, TN), lambda j, i, k: (k, j)),
            pl.BlockSpec((TK // block, TN), lambda j, i, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda j, i, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((TM, TN), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=min(max(2 * vmem, 32 << 20), 100 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * Mp * K * N, transcendentals=0,
            bytes_accessed=(N // TN) * Mp * K * isz + K // 2 * N
            + K // block * N * ssz + Mp * N * osz),
        interpret=interpret,
    )(a_lo, a_hi, b_packed, b_scales, ts)
    return out[:M] if Mp != M else out


# ---- tensor-parallel form (mesh-native serving) -------------------------------
#
# The explicit Megatron decomposition of the packed FQT matmul, written as a
# shard_map over the serving mesh (it runs on CPU host-platform device
# counts too):
#
#   column-parallel: W sharded on N (output features) — each device runs a
#     local packed GEMM on its own nibble-code / block-scale shard; NO
#     collective (the output stays sharded on N, which is exactly what the
#     next row-parallel GEMM wants).
#   row-parallel: X and W sharded on K (contraction) — local packed GEMM,
#     then a SINGLE psum of the partial products.
#
# With an FSDP-style ``gather_axis``, the weight is additionally sharded
# along K over that axis and the body first all-gathers the PACKED wire
# format (uint8 nibbles + f8 scales, ~4.5 bits/param — see
# distributed/compression.allgather_packed) instead of gathered bf16.
#
# This is the collective form the GSPMD engine path lowers to when packed
# leaves carry ``spec_for_packed`` partition specs; it exists explicitly so
# the decomposition is testable device-count-by-device-count (and is the
# shape a future Pallas ring-collective kernel would fuse into).


def tp_fp4_matmul(x, w, *, cfg, mesh, seed=None, parallel: str = "column",
                  axis: str = "model", gather_axis: Optional[str] = None):
    """Tensor-parallel packed FQT matmul: (..., K) @ packed (K, N) -> (..., N).

    ``w`` is a ``PackedQuantizedTensor`` (blocking axis -2).  The activation
    is quantized ONCE with global (single-device) semantics — ``cfg.fwd_a``
    amax over the full K — so column-parallel output is bit-identical to
    the 1-device packed forward; row-parallel differs only by psum
    reduction order.  Returns the full (global) product on every device
    per the out_specs (column: sharded on N; row: replicated).
    """
    import dataclasses

    from jax.sharding import PartitionSpec as P

    from repro.core import fqt
    from repro.core.quantize import PackedQuantizedTensor
    from repro.kernels import ops as kops

    if not isinstance(w, PackedQuantizedTensor) or w.ndim != 2:
        raise ValueError("tp_fp4_matmul needs a 2D PackedQuantizedTensor")
    if parallel not in ("column", "row"):
        raise ValueError(f"parallel={parallel!r}")
    K, N = w.shape
    if x.shape[-1] != K:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    if seed is None:
        seed = jnp.zeros((), jnp.uint32)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)

    # activation quantization OUTSIDE the shard_map: global amax semantics
    fwd_a = fqt._if_divisible(cfg.fwd_a, K)
    qx = fqt._maybe_q(x2, fwd_a, axis=-1,
                      seed=jnp.asarray(seed, jnp.uint32), site=0)

    tp = axis
    k_axes = ((tp,) if parallel == "row" else ()) + \
        ((gather_axis,) if gather_axis else ())
    k_spec = None if not k_axes else \
        k_axes[0] if len(k_axes) == 1 else k_axes
    n_spec = tp if parallel == "column" else None
    # scale spec DERIVED from the code spec (same K/N axes) — the
    # congruence rule of distributed/sharding.spec_for_packed
    w_specs = dataclasses.replace(
        w, packed=P(k_spec, n_spec), scales=P(k_spec, n_spec), tscale=P())
    x_spec = P(None, tp if parallel == "row" else None)
    out_spec = P(None, tp) if parallel == "column" else P()

    def body(qx_l, w_l):
        if gather_axis:
            from repro.distributed.compression import allgather_packed
            w_l = allgather_packed(w_l, gather_axis, dim=0)
        with kops.program_devices(1):       # each shard runs on one
            y = fqt.packed_gemm(qx_l, w_l, out_dtype=jnp.float32)
        if parallel == "row":
            y = jax.lax.psum(y, tp)
        return y.astype(x.dtype)

    y = jax.shard_map(body, mesh=mesh, in_specs=(x_spec, w_specs),
                      out_specs=out_spec, check_vma=False)(qx, w)
    return y.reshape(lead + (N,))
