"""jit'd public wrappers around the Pallas kernels.

``interpret`` mode is selected automatically: on the CPU backend the kernels
execute their bodies in interpret mode (bit-exact semantics, used by tests
and this container); on TPU they compile via Mosaic.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantize import BlockQuantSpec, PackedQuantizedTensor
from repro.kernels import fp4_matmul as _mm
from repro.kernels import nvfp4_quant as _q


@functools.lru_cache(maxsize=None)
def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def block_quantize(x: jax.Array, spec: BlockQuantSpec, *,
                   rbits: Optional[jax.Array] = None,
                   interpret: Optional[bool] = None):
    """Standalone fused block-quantization kernel; returns (codes, scales)."""
    if interpret is None:
        interpret = _interpret_default()
    return _q.block_quantize_pallas(x, spec, rbits=rbits, interpret=interpret)


def block_matmul(a_codes, a_scales, b_codes, b_scales, tscale, *,
                 block: int = 16, interpret: Optional[bool] = None):
    """Block-scaled matmul on pre-quantized operands."""
    if interpret is None:
        interpret = _interpret_default()
    return _mm.block_matmul(a_codes, a_scales, b_codes, b_scales, tscale,
                            block=block, interpret=interpret)


def fused_quant_matmul(a, b, spec_a: BlockQuantSpec, spec_b: BlockQuantSpec, *,
                       a_rbits=None, b_rbits=None, out_dtype=jnp.float32,
                       interpret: Optional[bool] = None,
                       tm: int = 128, tn: int = 128, tk: int = 512):
    """The FQT hot path: quantize both operands on the fly + block-scaled MMA."""
    if interpret is None:
        interpret = _interpret_default()
    return _mm.fused_quant_matmul(a, b, spec_a, spec_b, a_rbits=a_rbits,
                                  b_rbits=b_rbits, out_dtype=out_dtype,
                                  interpret=interpret, tm=tm, tn=tn, tk=tk)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# devices of the program being traced, where a caller knows them: GSPMD
# cannot partition a Mosaic call, so a packed GEMM in a program over more
# than one device dequantizes instead (unset: ``jax.device_count()``)
_PROGRAM_DEVICES = contextvars.ContextVar("program_devices", default=None)


@contextlib.contextmanager
def program_devices(n: int):
    """Trace the packed GEMMs inside as parts of a program over ``n``
    devices: a serving engine's mesh size, or 1 in a ``shard_map`` body."""
    token = _PROGRAM_DEVICES.set(n)
    try:
        yield
    finally:
        _PROGRAM_DEVICES.reset(token)


def packed_kernel_takes(m: int, w: PackedQuantizedTensor) -> bool:
    """Whether an (m, K) activation times packed ``w`` runs as the Pallas
    kernel: on a TPU backend, in a program over one device, for a (K, N)
    weight blocked along K whose shape has a tiling
    (``fp4_matmul.packed_tiles``).  Elsewhere the GEMM dequantizes the
    weight and calls ``jnp.matmul``."""
    n = _PROGRAM_DEVICES.get()
    n = jax.device_count() if n is None else n
    return (_on_tpu() and n == 1 and w.ndim == 2 and w.axis == -2
            and _mm.packed_tiles(m, *w.shape, w.block) is not None)


def packed_block_matmul(a, w: PackedQuantizedTensor, *, out_dtype=None,
                        interpret: Optional[bool] = None):
    """(M, K) activation x packed NVFP4 (K, N) weight, dequantized in VMEM
    tile by tile (the layout ``pack_quantize(w, axis=-2)`` produces)."""
    if interpret is None:
        interpret = _interpret_default()
    if w.ndim != 2 or w.axis != -2:
        raise ValueError(f"packed weight must be (K, N) blocked along K, got "
                         f"shape {w.shape}, axis {w.axis}")
    return _mm.packed_block_matmul(a, w.packed, w.scales, w.tscale,
                                   block=w.block, out_dtype=out_dtype,
                                   interpret=interpret)
