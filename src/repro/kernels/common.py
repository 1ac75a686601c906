"""Arithmetic-only minifloat quantization helpers usable *inside* Pallas
kernel bodies (no frexp, no exotic dtypes — just bitcasts, shifts, round,
floor, rotations; all supported by Mosaic on TPU and by interpret mode on
CPU).  No helper reshapes across the (8, 128) vreg tiling: Mosaic refuses
to split the lane axis, so block reductions are rotation butterflies and
lane permutations are 0/1 selection matmuls.

Bit-exact against repro.core.formats (tested in tests/test_kernels.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import FloatFormat

LANE = 128      # last dim of a vreg tile; block last dims are multiples
SUBLANE = 8     # second-to-last dim of a 32-bit vreg tile


class FmtParams(NamedTuple):
    """Static per-format constants passed into kernels."""
    man_bits: int
    emin: int           # smallest normal exponent
    emax: int           # largest normal exponent
    max: float          # largest finite

    @classmethod
    def of(cls, fmt: FloatFormat) -> "FmtParams":
        return cls(fmt.man_bits, fmt.emin, fmt.emax, fmt.max)


def _ulp_from_bits(a: jax.Array, p: FmtParams) -> jax.Array:
    """Grid spacing at |a| (a >= 0, float32), via exponent-field extraction.

    ulp = 2^(clip(floor(log2 a), emin, emax) - man_bits); matches
    formats._ulp bit-for-bit (incl. binade boundaries: 2^k has exponent k).
    """
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    e = ((bits >> 23) & 0xFF).astype(jnp.int32) - 127   # floor(log2 a)
    e = jnp.clip(e, p.emin, p.emax)
    ulp_bits = ((e - p.man_bits + 127) << 23).astype(jnp.uint32)
    return jax.lax.bitcast_convert_type(ulp_bits, jnp.float32)


def quantize_rtn_k(x: jax.Array, p: FmtParams) -> jax.Array:
    """Round-to-nearest-even onto the grid (float32 in/out), saturating."""
    s = jnp.sign(x)
    a = jnp.minimum(jnp.abs(x), p.max)
    ulp = _ulp_from_bits(a, p)
    q = jnp.round(a / ulp) * ulp
    return s * jnp.minimum(q, p.max)


def quantize_sr_k(x: jax.Array, p: FmtParams, u: jax.Array) -> jax.Array:
    """Stochastic rounding with uniforms u in [0,1):  floor(|x|/ulp + u)*ulp."""
    s = jnp.sign(x)
    a = jnp.minimum(jnp.abs(x), p.max)
    ulp = _ulp_from_bits(a, p)
    q = jnp.floor(a / ulp + u) * ulp
    return s * jnp.minimum(q, p.max)


def uniform_from_bits_k(rbits: jax.Array) -> jax.Array:
    """uint32 -> [0,1) float32; same convention as formats.uniform_from_bits.
    (The 24-bit value goes through int32: Mosaic has no uint32 -> f32.)"""
    return ((rbits >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(2.0 ** -24))


def _decode_e2m1_nibble_k(nib: jax.Array) -> jax.Array:
    """4-bit E2M1 code (s eem) -> float32 grid value, arithmetic only.

    Normals (e>0): value = (1 + m/2) * 2^(e-1), built by assembling the f32
    bit pattern directly (exponent field e-1+127, mantissa bit 22 = m) —
    shifts + bitcast, the same toolbox as the rest of this module.
    Subnormals (e==0): value = m * 0.5.
    """
    n = nib.astype(jnp.int32)
    sign = jnp.where((n & 0x8) != 0, jnp.float32(-1.0), jnp.float32(1.0))
    e = (n >> 1) & 0x3
    m = n & 0x1
    vbits = ((e + 126) << 23) | (m << 22)
    normal = jax.lax.bitcast_convert_type(vbits, jnp.float32)
    mag = jnp.where(e == 0, m.astype(jnp.float32) * 0.5, normal)
    return sign * mag


def select_matmul_k(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` where one operand is a 0/1 (bool) selection matrix with at
    most one 1 on each contraction line: a permutation, compaction or
    repeat on the MXU.  Exact (each output is one product by 1.0; HIGHEST
    keeps all 24 bits of an f32 operand, a bf16 one needs a single pass)."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    prec = (jax.lax.Precision.HIGHEST if dt == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jnp.dot(a.astype(dt), b.astype(dt),
                   preferred_element_type=jnp.float32, precision=prec)


def block_repeat_k(s: jax.Array, block: int, axis: int) -> jax.Array:
    """``jnp.repeat(s, block, axis)`` for 2D ``s``: each block scale spread
    over its ``block`` elements, by a selection matmul."""
    n = s.shape[axis] * block
    shape = (n, n // block) if axis == 0 else (n // block, n)
    elem = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    blk = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - axis) * block
    rep = (elem >= blk) & (elem < blk + block)
    return select_matmul_k(rep, s) if axis == 0 else select_matmul_k(s, rep)


def block_first_k(x: jax.Array, block: int) -> jax.Array:
    """``x[:, ::block]`` for 2D ``x`` (the first element of each block along
    the lanes), by a selection matmul."""
    n = x.shape[1]
    elem = jax.lax.broadcasted_iota(jnp.int32, (n, n // block), 0)
    blk = jax.lax.broadcasted_iota(jnp.int32, (n, n // block), 1)
    return select_matmul_k(x, elem == blk * block)


def pick_tile(dim: int, pref: int, align: int) -> int:
    """Largest multiple of ``align`` that divides ``dim`` and is <= ``pref``.
    The whole ``dim`` when it is <= ``pref`` or has no such divisor: a block
    equal to the array dimension is always legal for Mosaic."""
    if dim <= pref:
        return dim
    t = pref - pref % align
    while t >= align:
        if dim % t == 0:
            return t
        t -= align
    return dim


def unpack_e2m1_k(packed: jax.Array) -> jax.Array:
    """uint8 nibble pairs (R, C) -> f32 E2M1 grid values (R, 2C) in
    ``quantize.pack_e2m1``'s interleaved order (low nibble -> column 2j,
    high nibble -> 2j+1); usable inside Pallas kernels.  The interleave is
    two selection matmuls in bf16 (grid values are exact there)."""
    p = packed.astype(jnp.int32)
    lo = _decode_e2m1_nibble_k(p & 0xF).astype(jnp.bfloat16)
    hi = _decode_e2m1_nibble_k(p >> 4).astype(jnp.bfloat16)
    half = p.shape[-1]
    src = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (half, 2 * half), 1)
    return (select_matmul_k(lo, dst == 2 * src)
            + select_matmul_k(hi, dst == 2 * src + 1))


def block_amax_k(x: jax.Array, block: int, axis: int) -> jax.Array:
    """|x| max over each run of ``block`` elements along ``axis`` (a power
    of two dividing that axis), broadcast back to every element of the run.

    An XOR butterfly of rotations (log2(block) steps, partner i ^ s), so
    the lane/sublane tiling is never reshaped."""
    if block & (block - 1):
        raise ValueError(f"block={block} must be a power of two")
    n = x.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    m = jnp.abs(x)
    s = 1
    while s < block:
        up = pltpu.roll(m, n - s, axis)      # m[i + s]
        down = pltpu.roll(m, s, axis)        # m[i - s]
        m = jnp.maximum(m, jnp.where((idx & s) == 0, up, down))
        s *= 2
    return m


def e8m0_block_scale_k(absmax: jax.Array, data_emax: int) -> jax.Array:
    """OCP MX rule: scale = 2^(floor(log2 amax) - emax_elem); 1.0 for amax=0."""
    bits = jax.lax.bitcast_convert_type(absmax, jnp.uint32)
    e = ((bits >> 23) & 0xFF).astype(jnp.int32) - 127
    e = jnp.clip(e, -127, 127)
    pbits = ((e + 127) << 23).astype(jnp.uint32)
    p2 = jax.lax.bitcast_convert_type(pbits, jnp.float32)   # 2^floor(log2 amax)
    scale = p2 / jnp.float32(2.0 ** data_emax)              # exact pow2 division
    return jnp.where(absmax > 0, scale, 1.0)


def generic_block_scale_k(absmax: jax.Array, data_max: float,
                          scale_p: FmtParams, tscale: jax.Array) -> jax.Array:
    """RtN block scale: Q_rtn(amax / (data_max * tscale)); 1.0 for zero."""
    raw = absmax / (data_max * tscale)
    scale = quantize_rtn_k(raw, scale_p)
    return jnp.where(scale > 0, scale, 1.0)
