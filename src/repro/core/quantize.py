"""Block (micro-scaled) quantization: NVFP4, MXFP4 and the paper's sweeps.

A *block-quantized* tensor stores, per contiguous block of ``block`` elements
along the blocking axis:

  * FP4 (``data_fmt``, default E2M1) codes, and
  * one shared scale in ``scale_fmt`` (E4M3 for NVFP4, E8M0 for MXFP4), and
  * (optionally, ``two_level=True``) one per-tensor scale that normalises the
    block scales into the scale format's representable range — the NVFP4
    hardware convention.  We round the tensor scale to a power of two so that
    ``codes * block_scale * tensor_scale`` stays exactly representable in
    bf16 (2-bit significand x 4-bit significand x 2^k <= 8-bit significand);
    see DESIGN.md §4.

The blocking axis must be the GEMM *contraction* axis of the operand as
consumed (this is what Blackwell block-scaled MMA requires, and what the
paper's six quantization points mean).  Operands therefore get re-quantized
per GEMM, exactly as in the paper.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats
from repro.core.formats import FloatFormat, get_format
from repro.obs import scopes


@dataclasses.dataclass(frozen=True)
class BlockQuantSpec:
    """How to block-quantize one GEMM operand."""

    data_fmt: str = "e2m1"
    scale_fmt: str = "e4m3"
    block: int = 16
    two_level: bool = True     # per-tensor pow2 scale under the block scale
    stochastic: bool = False   # SR (True) or RtN (False)

    @property
    def data(self) -> FloatFormat:
        return get_format(self.data_fmt)

    @property
    def scale(self) -> FloatFormat:
        return get_format(self.scale_fmt)

    def with_rounding(self, stochastic: bool) -> "BlockQuantSpec":
        return dataclasses.replace(self, stochastic=stochastic)


NVFP4 = BlockQuantSpec(data_fmt="e2m1", scale_fmt="e4m3", block=16,
                       two_level=True)
MXFP4 = BlockQuantSpec(data_fmt="e2m1", scale_fmt="e8m0", block=32,
                       two_level=False)


class QuantizedTensor(NamedTuple):
    """codes * scales (block-broadcast) * tscale reconstructs the tensor.

    ``codes`` hold *dequantized-grid* values (exact E2M1 grid points) in the
    original dtype; ``scales`` has shape = codes.shape with the blocking axis
    divided by ``block``; ``tscale`` is a scalar (1.0 when two_level=False).
    """

    codes: jax.Array
    scales: jax.Array
    tscale: jax.Array
    axis: int
    block: int

    def dequant(self) -> jax.Array:
        s = jnp.repeat(self.scales, self.block, axis=self.axis)
        return (self.codes * s * self.tscale).astype(self.codes.dtype)


def _norm_axis(ndim: int, axis: int) -> int:
    return axis % ndim


def _blocked(x: jax.Array, axis: int, block: int) -> jax.Array:
    """Reshape so the blocking axis becomes (..., nblocks, block, ...)."""
    axis = _norm_axis(x.ndim, axis)
    if x.shape[axis] % block != 0:
        raise ValueError(
            f"axis {axis} of shape {x.shape} not divisible by block {block}")
    new_shape = x.shape[:axis] + (x.shape[axis] // block, block) + x.shape[axis + 1:]
    return x.reshape(new_shape)


def _block_scales(absmax: jax.Array, spec: BlockQuantSpec,
                  tscale: jax.Array) -> jax.Array:
    """Quantized per-block scales from per-block absmax (fp32 in/out)."""
    data_max = spec.data.max
    if spec.scale_fmt == "e8m0":
        # OCP MX rule: scale = 2^(floor(log2 amax) - emax_elem); here tscale==1.
        scale = formats.e8m0_floor(absmax) / (2.0 ** spec.data.emax)
        scale = jnp.where(absmax > 0, scale, 1.0)
        return scale
    raw = absmax / (data_max * tscale)
    scale = formats.quantize_rtn(raw, spec.scale)
    scale = jnp.where(scale > 0, scale, 1.0)
    return scale


def _tensor_scale(x_abs_max: jax.Array, spec: BlockQuantSpec) -> jax.Array:
    """Power-of-two tensor scale mapping the largest block scale into range."""
    if not spec.two_level:
        return jnp.ones((), dtype=jnp.float32)
    target = spec.data.max * spec.scale.max          # e.g. 6 * 448
    raw = x_abs_max / target
    # round *up* to a power of two so no block scale can clip (ldexp: exact)
    _, k = jnp.frexp(raw.astype(jnp.float32))        # raw = m * 2^k, m in [.5,1)
    ts = jnp.ldexp(jnp.ones((), jnp.float32), k)     # 2^ceil(log2 raw)
    return jnp.where(x_abs_max > 0, ts, jnp.ones((), jnp.float32))


def block_quantize(x: jax.Array, spec: BlockQuantSpec, *, axis: int = -1,
                   key: Optional[jax.Array] = None,
                   u: Optional[jax.Array] = None) -> QuantizedTensor:
    """Quantize x to (codes, scales, tscale) per ``spec`` along ``axis``.

    SR randomness: pass either ``key`` (threefry; statistics tests) or ``u``
    — uniforms in [0,1) of x.shape, e.g. from ``formats.counter_bits``,
    which XLA fuses into the quantize chain (the FQT hot path).
    """
    axis = _norm_axis(x.ndim, axis)
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    xb = _blocked(xf, axis, spec.block)              # (..., nb, B, ...)
    baxis = axis + 1                                 # the size-B axis
    absmax = jnp.max(jnp.abs(xb), axis=baxis)        # (..., nb, ...)
    tscale = _tensor_scale(jnp.max(jnp.abs(xf)), spec)
    scales = _block_scales(absmax, spec, tscale)     # (..., nb, ...)
    denom = jnp.expand_dims(scales, baxis) * tscale
    if spec.stochastic and u is not None:
        codes = formats.quantize_sr_with_u(
            xb / denom, spec.data, _blocked(u.astype(jnp.float32), axis,
                                            spec.block))
    else:
        codes = formats.quantize(xb / denom, spec.data,
                                 stochastic=spec.stochastic, key=key)
    codes = codes.reshape(x.shape).astype(orig_dtype)
    return QuantizedTensor(codes=codes, scales=scales.astype(orig_dtype),
                           tscale=tscale, axis=axis, block=spec.block)


def fake_quant(x: jax.Array, spec: BlockQuantSpec, *, axis: int = -1,
               key: Optional[jax.Array] = None,
               u: Optional[jax.Array] = None) -> jax.Array:
    """Quantize-dequantize in one step (the FQT simulation primitive)."""
    return block_quantize(x, spec, axis=axis, key=key, u=u).dequant()


def scale_health(x: jax.Array, spec: BlockQuantSpec, *,
                 axis: int = -1) -> dict:
    """Block-scale saturation/underflow counts for telemetry (host-side).

    Replays the ``_block_scales`` rounding on ``x`` and counts blocks
    whose raw scale exceeds the scale format's max (E4M3: 448 — the
    two-level tensor scale should make this impossible, so a nonzero
    count flags a scaling bug or an overflowing tensor) or whose nonzero
    absmax rounds to a zero scale (underflow — ``_block_scales`` clamps
    it to 1.0, quantizing the whole block to zero).  Returns plain ints;
    call OUTSIDE jit (this is trainer telemetry, not a training op).
    """
    axis = _norm_axis(x.ndim, axis)
    xf = jnp.asarray(x).astype(jnp.float32)
    xb = _blocked(xf, axis, spec.block)
    absmax = jnp.max(jnp.abs(xb), axis=axis + 1)
    tscale = _tensor_scale(jnp.max(jnp.abs(xf)), spec)
    if spec.scale_fmt == "e8m0":
        scale = formats.e8m0_floor(absmax) / (2.0 ** spec.data.emax)
        saturated = jnp.zeros((), jnp.int32)  # E8M0 spans the fp32 range
        underflow = jnp.sum((scale <= 0) & (absmax > 0))
    else:
        raw = absmax / (spec.data.max * tscale)
        scale = formats.quantize_rtn(raw, spec.scale)
        saturated = jnp.sum(raw > spec.scale.max)
        underflow = jnp.sum((scale <= 0) & (absmax > 0))
    return {"blocks": int(absmax.size), "saturated": int(saturated),
            "underflow": int(underflow)}


# ---- packed storage (serving weight store / checkpoint / cache paths) --------

# E2M1 magnitude grid, indexed by the 3 low nibble bits (matches the
# ml_dtypes.float4_e2m1fn bit layout: s eem, codes 0..7 -> these values).
_E2M1_GRID = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], np.float32)


def _e2m1_nibbles(codes: jax.Array) -> jax.Array:
    """Exact E2M1 grid values -> 4-bit codes (uint8), sign<<3 | grid index:
    the float4_e2m1fn bit layout, by arithmetic (jax<0.5 cannot hold
    float4 arrays)."""
    absv = jnp.abs(codes).astype(jnp.float32)
    idx = jnp.searchsorted(jnp.asarray(_E2M1_GRID), absv).astype(jnp.uint8)
    sign = (codes.astype(jnp.float32) < 0).astype(jnp.uint8)
    return (sign << 3) | idx


def _e2m1_values(nib: jax.Array) -> jax.Array:
    """4-bit codes (any integer dtype, high bits clear) -> f32 grid values."""
    mag = jnp.asarray(_E2M1_GRID)[nib & 0x7]
    return mag * jnp.where(nib & 0x8, -1.0, 1.0)


def pack_e2m1(codes: jax.Array) -> jax.Array:
    """Pack E2M1 grid values into nibbles, two per uint8 (last axis even):
    element 2j in the low nibble, 2j+1 in the high one — the KV cache's
    row format.  ``codes`` must hold exact grid values (the quantizers'
    output), any float dtype."""
    if codes.shape[-1] % 2:
        raise ValueError(f"last axis must be even to pack, got {codes.shape}")
    nib = _e2m1_nibbles(codes)
    lo, hi = nib[..., 0::2], nib[..., 1::2]
    return lo | (hi << 4)


def unpack_e2m1(packed: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Inverse of ``pack_e2m1``: uint8 nibble pairs -> exact E2M1 grid values."""
    lo = packed & 0x7
    hi = (packed >> 4) & 0x7
    mag = jnp.asarray(_E2M1_GRID)
    vlo = mag[lo] * jnp.where(packed & 0x8, -1.0, 1.0)
    vhi = mag[hi] * jnp.where(packed & 0x80, -1.0, 1.0)
    stacked = jnp.stack([vlo, vhi], axis=-1)
    flat = stacked.reshape(*packed.shape[:-1], packed.shape[-1] * 2)
    return flat.astype(dtype)


def _pack_blocked(codes: jax.Array, axis: int, block: int) -> jax.Array:
    """Pack E2M1 grid values two per uint8 along the blocking ``axis``:
    byte r of block b holds element r (low nibble) and element
    r + block/2 (high nibble) of that block, so both nibbles of a byte
    share the block's scale.  The packed axis is halved."""
    ax = _norm_axis(codes.ndim, axis)
    nib = _blocked(_e2m1_nibbles(codes), ax, block)      # (..., nb, B, ...)
    half = block // 2
    lo = jax.lax.slice_in_dim(nib, 0, half, axis=ax + 1)
    hi = jax.lax.slice_in_dim(nib, half, block, axis=ax + 1)
    packed = lo | (hi << 4)                              # (..., nb, B/2, ...)
    shape = list(codes.shape)
    shape[ax] //= 2
    return packed.reshape(shape)


def _unpack_blocked(packed: jax.Array, axis: int, block: int) -> jax.Array:
    """Inverse of ``_pack_blocked`` in block form: f32 grid values of shape
    (..., nblocks, block, ...) along the blocking ``axis``."""
    ax = _norm_axis(packed.ndim, axis)
    p = _blocked(packed, ax, block // 2)                 # (..., nb, B/2, ...)
    return jnp.concatenate([_e2m1_values(p & 0xF), _e2m1_values(p >> 4)],
                           axis=ax + 1)


@dataclasses.dataclass(frozen=True)
class PackedQuantizedTensor:
    """Quantize-once packed NVFP4 storage: 4-bit codes + block scales.

    The serving-side counterpart of ``QuantizedTensor``: E2M1 codes are
    nibble-packed two-per-uint8 along the BLOCKING axis (``packed``): byte
    r of block b holds element r (low nibble) and element r + block/2
    (high nibble), so both nibbles share block b's scale and a (K, N)
    weight blocked on K packs to (K/2, N).  The block
    scales live in ``scales`` (float8_e4m3fn when the scale format is E4M3,
    else the source dtype) and ``tscale`` is the per-tensor pow2 scale —
    one per leading *batch* slice when the weight is a stacked layer/expert
    array, so a scan/vmap slice of this pytree is exactly the per-matrix
    quantization the fake-quant forward computes.

    ``dequant()`` reproduces ``QuantizedTensor.dequant()`` BIT-EXACTLY (all
    three factors are exactly representable in bf16 — see module docstring),
    which is what keeps packed serving token-identical to the QAF forward.

    ``axis`` is the blocking axis as a NEGATIVE index (so the same metadata
    stays valid when leading batch dims are sliced away by scan/vmap).
    """

    packed: jax.Array          # uint8, logical shape with axis halved
    scales: jax.Array          # logical shape with axis divided by block
    tscale: jax.Array          # f32, shape = leading batch dims (or scalar)
    axis: int                  # negative blocking-axis index
    block: int
    dtype_name: str = "bfloat16"     # dequant target dtype

    @property
    def dtype(self):
        return jnp.dtype(self.dtype_name)

    @property
    def shape(self):
        ax = self.axis % self.packed.ndim
        s = self.packed.shape
        return s[:ax] + (s[ax] * 2,) + s[ax + 1:]

    @property
    def ndim(self) -> int:
        return self.packed.ndim

    def nbytes(self) -> int:
        """Stored bytes (codes + scales + tscale)."""
        return int(self.packed.size * self.packed.dtype.itemsize
                   + self.scales.size * self.scales.dtype.itemsize
                   + self.tscale.size * 4)

    def wire_nbytes(self) -> int:
        """Bytes an FSDP-style all-gather of this tensor moves: the nibble
        codes + block scales ARE the wire format (~4.5 bits/param for
        NVFP4 vs 16 for a bf16 gather); the per-slice tscale is replicated
        and never travels."""
        return int(self.packed.size * self.packed.dtype.itemsize
                   + self.scales.size * self.scales.dtype.itemsize)

    def map_leaves(self, f) -> "PackedQuantizedTensor":
        """Apply ``f(name, array)`` to the data leaves (packed/scales/
        tscale), keeping metadata — the hook the sharding layer uses to
        attach per-leaf partition specs / device placements
        (distributed/sharding.spec_for_packed)."""
        return dataclasses.replace(
            self, packed=f("packed", self.packed),
            scales=f("scales", self.scales),
            tscale=f("tscale", self.tscale))

    def dequant(self) -> jax.Array:
        """codes * block_scales * tscale, bit-identical to the fake-quant
        (QuantizedTensor) reconstruction of the same tensor."""
        dt = self.dtype
        ax = self.axis % self.ndim
        with jax.named_scope(scopes.PACKED_DEQUANT):
            codes = _unpack_blocked(self.packed, ax, self.block).astype(dt)
            s = jnp.expand_dims(self.scales.astype(dt), ax + 1)
            t = self.tscale.reshape(
                self.tscale.shape + (1,) * (codes.ndim - self.tscale.ndim))
            return (codes * s * t).astype(dt).reshape(self.shape)


jax.tree_util.register_dataclass(
    PackedQuantizedTensor,
    data_fields=["packed", "scales", "tscale"],
    meta_fields=["axis", "block", "dtype_name"])


def _pack_scales(scales: jax.Array, spec: BlockQuantSpec) -> jax.Array:
    """Store E4M3 block scales in float8 (exact: they lie on the e4m3 grid);
    other scale formats keep their source dtype."""
    if spec.scale_fmt == "e4m3":
        return scales.astype(jnp.float8_e4m3fn)
    return scales


def pack_quantized(qt: QuantizedTensor,
                   spec: BlockQuantSpec = NVFP4) -> PackedQuantizedTensor:
    """Convert a QuantizedTensor (dequantized-grid codes) to packed storage."""
    if spec.data_fmt != "e2m1":
        raise ValueError("packed storage is E2M1-only")
    return PackedQuantizedTensor(
        packed=_pack_blocked(qt.codes, qt.axis, qt.block),
        scales=_pack_scales(qt.scales, spec),
        tscale=jnp.asarray(qt.tscale, jnp.float32),
        axis=qt.axis - qt.codes.ndim,
        block=qt.block,
        dtype_name=jnp.dtype(qt.codes.dtype).name)


# ---- KV-cache row quantization (serving decode path) -------------------------
#
# The decode-attention analogue of the packed weight store: cache rows are
# block-quantized along the HEAD dim (the qk^T contraction axis, so score
# tiles can dequantize K blocks in-register) with RtN — the paper's
# inference-compatible forward rounding.  Unlike weights, cache slots are
# written incrementally (prefill + one row per decoded token), so there is NO
# per-tensor second-level scale: a global absmax over future tokens cannot be
# known at append time.  Each block therefore carries a self-contained scale:
#
#   nvfp4:  E2M1 nibble codes (2/uint8) + one float8_e4m3fn scale per
#           ``block`` elements      -> 0.5 + 1/16 = 0.5625 bytes/elem (3.56x)
#   fp8:    float8_e4m3fn codes + one bf16 scale per ``block`` elements
#                                   -> 1 + 2/16   = 1.125  bytes/elem (1.78x)
#   bf16:   unquantized escape hatch (models/layers.KVCache).

KV_CACHE_FORMATS = ("bf16", "nvfp4", "fp8")


def kv_quant_rows(x: jax.Array, fmt: str, block: int = 16):
    """Quantize cache rows along the last (head) dim.  Returns (codes, scales).

    ``x``: (..., D) with D % block == 0.  RtN only (forward path).  Codes are
    storage-dtype (uint8 nibble pairs for nvfp4, float8_e4m3fn for fp8);
    scales have the last axis divided by ``block``.
    """
    if fmt not in ("nvfp4", "fp8"):
        raise ValueError(f"kv_quant_rows: unknown format {fmt!r}")
    e4m3 = get_format("e4m3")
    xf = x.astype(jnp.float32)
    xb = _blocked(xf, -1, block)                      # (..., nb, B)
    absmax = jnp.max(jnp.abs(xb), axis=-1)            # (..., nb)
    if fmt == "nvfp4":
        e2m1 = get_format("e2m1")
        scales = formats.quantize_rtn(absmax / e2m1.max, e4m3)
        scales = jnp.where(scales > 0, scales, 1.0)
        codes = formats.quantize_rtn(xb / scales[..., None], e2m1)
        return (pack_e2m1(codes.reshape(x.shape)),
                scales.astype(jnp.float8_e4m3fn))
    # fp8: scale each block into the e4m3 range; bf16 scale (rounded before
    # use so the stored scale is exactly the one the codes were built with)
    scales = jnp.where(absmax > 0, absmax / e4m3.max, 1.0
                       ).astype(jnp.bfloat16)
    codes = formats.quantize_rtn(
        xb / scales.astype(jnp.float32)[..., None], e4m3)
    return (codes.reshape(x.shape).astype(jnp.float8_e4m3fn),
            scales)


def kv_dequant(codes: jax.Array, scales: jax.Array, fmt: str,
               block: int = 16, dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of ``kv_quant_rows``: reconstruct (..., D) rows in ``dtype``."""
    if fmt == "nvfp4":
        vals = unpack_e2m1(codes, dtype=jnp.float32)
    elif fmt == "fp8":
        vals = codes.astype(jnp.float32)
    else:
        raise ValueError(f"kv_dequant: unknown format {fmt!r}")
    s = jnp.repeat(scales.astype(jnp.float32), block, axis=-1)
    return (vals * s).astype(dtype)


def kv_bytes_per_elem(fmt: str, block: int = 16) -> float:
    """Stored cache bytes per logical K/V element for ``fmt``."""
    if fmt == "bf16":
        return 2.0
    if fmt == "nvfp4":
        return 0.5 + 1.0 / block
    if fmt == "fp8":
        return 1.0 + 2.0 / block
    raise ValueError(f"unknown kv cache format {fmt!r}")


def pack_quantize(x: jax.Array, spec: BlockQuantSpec = NVFP4, *,
                  axis: int = -2, batch_dims: int = 0
                  ) -> PackedQuantizedTensor:
    """Quantize-once packing of a weight (RtN), optionally batched.

    ``batch_dims`` leading axes are treated as independent tensors (stacked
    layer / expert weights): the per-tensor pow2 scale is computed per slice,
    so slicing the result along those axes (lax.scan / vmap) yields exactly
    ``block_quantize(x[i], spec, axis=...)`` — the invariant that makes the
    packed store bit-identical to the per-GEMM fake-quant forward.
    """
    if spec.data_fmt != "e2m1":
        raise ValueError("packed storage is E2M1-only")
    if spec.stochastic:
        raise ValueError("packed weight store is RtN (forward) only")
    nd = x.ndim
    ax = _norm_axis(nd, axis)
    if ax < batch_dims:
        raise ValueError(f"blocking axis {ax} inside batch dims {batch_dims}")
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    xb = _blocked(xf, ax, spec.block)                  # (..., nb, B, ...)
    absmax = jnp.max(jnp.abs(xb), axis=ax + 1)         # (..., nb, ...)
    tmax = jnp.max(jnp.abs(xf), axis=tuple(range(batch_dims, nd)))
    # batch-shaped even for two_level=False (where _tensor_scale returns a
    # scalar 1.0): every data field must carry the leading batch dims or
    # lax.scan/vmap cannot slice the pytree
    tscale = jnp.broadcast_to(_tensor_scale(tmax, spec), tmax.shape)
    ts_b = tscale.reshape(tscale.shape + (1,) * (absmax.ndim - tscale.ndim))
    scales = _block_scales(absmax, spec, ts_b)
    denom = jnp.expand_dims(scales, ax + 1) * jnp.expand_dims(ts_b, ax + 1)
    codes = formats.quantize(xb / denom, spec.data)
    codes = codes.reshape(x.shape).astype(orig_dtype)
    return PackedQuantizedTensor(
        packed=_pack_blocked(codes, ax, spec.block),
        scales=_pack_scales(scales.astype(orig_dtype), spec),
        tscale=tscale.astype(jnp.float32),
        axis=ax - nd,
        block=spec.block,
        dtype_name=jnp.dtype(orig_dtype).name)
