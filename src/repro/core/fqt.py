"""Fully-quantized-training matmul: the paper's six quantization points.

The paper (eqs. 1-6) quantizes *both operands of all three training GEMMs*:

  [Forward]   z = Q(W) Q(a)           -> points  fwd_w (RtN), fwd_a (RtN)
  [Backward]  g_in = Q(W^T) Q(delta)  -> points  bwd_w (RtN), bwd_g (SR)
  [Update]    dW = Q(delta) Q(a^T)    -> points  upd_g (SR),  upd_a (SR)

``fp4_matmul`` is a custom_vjp matmul that applies an independent
``BlockQuantSpec`` (format, block size, scale format, rounding mode) at each
of the six points, with blocks always along the contraction axis of the GEMM
in which the operand is consumed (weights/activations/grads are therefore
re-quantized per GEMM, exactly as block-scaled FP4 hardware requires).

Randomness for stochastic rounding is threaded as an explicit uint32 ``seed``
operand (counter-based, derived per-layer/per-step by the caller), so training
is deterministic and replayable after checkpoint restart.

The straight-through estimator is implicit: the backward rule differentiates
the *unquantized* matmul and then re-quantizes its operands, which is exactly
eqs. (5)-(6) and is also what the paper's Gaudi2 simulation does.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, FrozenSet

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import (BlockQuantSpec, NVFP4, MXFP4, fake_quant,
                                 PackedQuantizedTensor)
from repro.obs import scopes
from repro.obs.trace import Counters

# the six quantization points
POINTS = ("fwd_w", "fwd_a", "bwd_w", "bwd_g", "upd_g", "upd_a")
# each point's site number, as _maybe_q and _site_bits take it (it seeds
# the point's SR bits), mapped to the point's profiler scope
SITE_SCOPES = tuple(scopes.FQT_Q + p for p in ("fwd_a", "fwd_w", "bwd_g",
                                               "bwd_w", "upd_a", "upd_g"))
# the paper's selective-rounding scheme (eqs. 4-6): SR on neural gradients in
# backward+update GEMMs and on activations in the update GEMM.
PAPER_SR_POINTS: FrozenSet[str] = frozenset({"bwd_g", "upd_g", "upd_a"})
# trace-time counts of packed-weight GEMMs and their packed bytes by route:
# ``packed_gemm.kernel[.bytes]`` and ``packed_gemm.dequant[.bytes]``
PACKED_GEMM = "packed_gemm."
PACKED_GEMMS = Counters()


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Which BlockQuantSpec (or None = keep bf16) applies at each GEMM point."""

    fwd_w: Optional[BlockQuantSpec] = None
    fwd_a: Optional[BlockQuantSpec] = None
    bwd_w: Optional[BlockQuantSpec] = None
    bwd_g: Optional[BlockQuantSpec] = None
    upd_g: Optional[BlockQuantSpec] = None
    upd_a: Optional[BlockQuantSpec] = None
    # "jnp" (fake-quant reference path) or "pallas" (fused TPU kernels) for
    # the training GEMMs; packed weights route by platform and shape
    impl: str = "jnp"

    @property
    def enabled(self) -> bool:
        return any(getattr(self, p) is not None for p in POINTS)

    def spec(self, point: str) -> Optional[BlockQuantSpec]:
        return getattr(self, point)

    def replace(self, **kw) -> "QuantConfig":
        return dataclasses.replace(self, **kw)


# ---- presets (paper Table 2 + sweeps) ----------------------------------------


def bf16_config() -> QuantConfig:
    """BF16 baseline: no quantization anywhere."""
    return QuantConfig()


def fqt_config(base: BlockQuantSpec = NVFP4,
               sr_points: FrozenSet[str] = PAPER_SR_POINTS,
               impl: str = "jnp") -> QuantConfig:
    """Full FQT of all six points; ``sr_points`` use SR, the rest RtN."""
    kw = {p: base.with_rounding(stochastic=(p in sr_points)) for p in POINTS}
    return QuantConfig(impl=impl, **kw)


def nvfp4_paper_config(impl: str = "jnp") -> QuantConfig:
    """The paper's scheme: NVFP4 everywhere, split rounding (eqs. 4-6)."""
    return fqt_config(NVFP4, PAPER_SR_POINTS, impl)


def mxfp4_config(impl: str = "jnp") -> QuantConfig:
    return fqt_config(MXFP4, PAPER_SR_POINTS, impl)


def qaf_config(impl: str = "jnp") -> QuantConfig:
    """Quantization-aware finetuning: FP4 forward, BF16 backward+update."""
    return QuantConfig(fwd_w=NVFP4, fwd_a=NVFP4, impl=impl)


def wang2025_config() -> QuantConfig:
    """[21] Wang et al.: FP4 weights+activations (forward only), BF16 grads."""
    return QuantConfig(fwd_w=NVFP4, fwd_a=NVFP4, bwd_w=NVFP4)


def tseng2025_config() -> QuantConfig:
    """[19] Tseng et al.: MXFP4+SR neural gradients only, BF16 W/A."""
    sr = MXFP4.with_rounding(stochastic=True)
    return QuantConfig(bwd_g=sr, upd_g=sr)


# ---- seed plumbing -----------------------------------------------------------


def _site_seed32(seed: jax.Array, site: int) -> jax.Array:
    """Per-quantization-site 32-bit counter seed from the layer/step seed."""
    return (jnp.asarray(seed, jnp.uint32) * jnp.uint32(0x9E3779B1)
            ^ jnp.uint32((site * 0x7FB5D329) & 0xFFFFFFFF))


def _site_bits(x_shape, seed: jax.Array, site: int) -> jax.Array:
    """SR random bits for a site — counter-based (formats.counter_bits), so
    the jnp path fuses them into the quantize chain (zero HBM traffic) and
    the Pallas path receives the *identical* stream as an operand."""
    from repro.core import formats
    with jax.named_scope(SITE_SCOPES[site]):
        return formats.counter_bits(_site_seed32(seed, site), x_shape)


def _site_u(seed: jax.Array, site: int, shape) -> jax.Array:
    from repro.core import formats
    return formats.uniform_from_bits(_site_bits(shape, seed, site))


def _maybe_q(x: jax.Array, spec: Optional[BlockQuantSpec], axis: int,
             seed: jax.Array, site: int) -> jax.Array:
    if spec is None:
        return x
    with jax.named_scope(SITE_SCOPES[site]):
        u = _site_u(seed, site, x.shape) if spec.stochastic else None
        return fake_quant(x, spec, axis=axis, u=u)


def _pallas_gemm(a2d, b2d, spec_a, spec_b, seed, site_a, site_b, out_dtype,
                 rb_a=None, rb_b=None):
    """One fused quantize+matmul Pallas call (blocks: a axis1, b axis0)."""
    from repro.kernels import ops as kops
    if spec_a is not None and spec_a.stochastic and rb_a is None:
        rb_a = _site_bits(a2d.shape, seed, site_a)
    if spec_b is not None and spec_b.stochastic and rb_b is None:
        rb_b = _site_bits(b2d.shape, seed, site_b)
    return kops.fused_quant_matmul(a2d, b2d, spec_a, spec_b, a_rbits=rb_a,
                                   b_rbits=rb_b, out_dtype=out_dtype)


def _float0_zero(x: jax.Array):
    """Zero cotangent for an integer-dtype primal (tangent dtype float0)."""
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


# ---- the FQT matmul ----------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fp4_matmul(x: jax.Array, w: jax.Array, seed: jax.Array,
                cfg: QuantConfig) -> jax.Array:
    return _forward(x, w, seed, cfg)


def _use_pallas(cfg, spec_a, spec_b, k_dim) -> bool:
    return (cfg.impl == "pallas" and spec_a is not None and spec_b is not None
            and spec_a.block == spec_b.block and k_dim % spec_a.block == 0)


def _if_divisible(spec: Optional[BlockQuantSpec], dim: int):
    """Quantization applies only when the contraction dim is block-divisible;
    otherwise that GEMM falls back to bf16 (hardware would pad — irregular
    dims only occur in reduced smoke configs, never in the real arch configs,
    which are all multiples of 16)."""
    if spec is not None and dim % spec.block != 0:
        return None
    return spec


def _forward(x, w, seed, cfg):
    """[Forward] z = Q_rtn(a) @ Q_rtn(W); blocks along K for both operands."""
    K, N = w.shape
    fwd_a = _if_divisible(cfg.fwd_a, K)
    fwd_w = _if_divisible(cfg.fwd_w, K)
    if _use_pallas(cfg, fwd_a, fwd_w, K):
        x2 = x.reshape(-1, K)
        with jax.named_scope(scopes.DOT_FWD):
            y = _pallas_gemm(x2, w, fwd_a, fwd_w, seed, 0, 1, x.dtype)
        return y.reshape(x.shape[:-1] + (N,))
    qx = _maybe_q(x, fwd_a, axis=-1, seed=seed, site=0)
    qw = _maybe_q(w, fwd_w, axis=0, seed=seed, site=1)
    with jax.named_scope(scopes.DOT_FWD):
        y = jnp.matmul(qx, qw, preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def _fwd_rule(x, w, seed, cfg):
    return _forward(x, w, seed, cfg), (x, w, seed)


def _bwd_rule(cfg, res, g):
    x, w, seed = res
    K, N = w.shape
    g32 = g

    # [Backward] dX = Q_sr(g) @ Q_rtn(W)^T ; contraction over N.
    bwd_g = _if_divisible(cfg.bwd_g, N)
    bwd_w = _if_divisible(cfg.bwd_w, N)
    if _use_pallas(cfg, bwd_g, bwd_w, N):
        g2 = g32.reshape(-1, N)
        with jax.named_scope(scopes.DOT_DGRAD):
            dx = _pallas_gemm(g2, w.T, bwd_g, bwd_w, seed, 2, 3, x.dtype)
        dx = dx.reshape(x.shape)
    else:
        qg_b = _maybe_q(g32, bwd_g, axis=-1, seed=seed, site=2)
        qw_b = _maybe_q(w, bwd_w, axis=1, seed=seed, site=3)  # blocks on N
        with jax.named_scope(scopes.DOT_DGRAD):
            dx = jnp.matmul(qg_b, qw_b.T, preferred_element_type=jnp.float32)
        dx = dx.astype(x.dtype)

    # [Update] dW = Q_sr(a)^T @ Q_sr(g) ; contraction over tokens M.
    xf = x.reshape(-1, K)
    gf = g32.reshape(-1, N)
    M = xf.shape[0]
    upd_a, upd_g = cfg.upd_a, cfg.upd_g
    # Token count not divisible by the block (e.g. tiny eval batches): the
    # update GEMM falls back to bf16 rather than changing blocking semantics.
    if upd_a is not None and M % upd_a.block != 0:
        upd_a = None
    if upd_g is not None and M % upd_g.block != 0:
        upd_g = None
    if (_use_pallas(cfg, upd_a, upd_g, M) and upd_a is not None):
        rb_a = (_site_bits((M, K), seed, 4).T
                if upd_a.stochastic else None)           # align with jnp path
        with jax.named_scope(scopes.DOT_WGRAD):
            dw = _pallas_gemm(xf.T, gf, upd_a, upd_g, seed, 4, 5, w.dtype,
                              rb_a=rb_a)
    else:
        qx_u = _maybe_q(xf, upd_a, axis=0, seed=seed, site=4)
        qg_u = _maybe_q(gf, upd_g, axis=0, seed=seed, site=5)
        with jax.named_scope(scopes.DOT_WGRAD):
            dw = jnp.matmul(qx_u.T, qg_u, preferred_element_type=jnp.float32)
        dw = dw.astype(w.dtype)

    return dx, dw, _float0_zero(res[2])


_fp4_matmul.defvjp(_fwd_rule, _bwd_rule)


# ---- pre-quantized (packed) weights: the quantize-once serving path ----------


def packed_gemm(x: jax.Array, w: PackedQuantizedTensor,
                out_dtype=None) -> jax.Array:
    """(..., K) @ packed (K, N) -> (..., N), f32-accumulated, ``x`` as given.

    On TPU, for the shapes it takes, one Pallas kernel dequantizes the
    weight tile by tile in VMEM (``kernels/fp4_matmul.packed_block_matmul``);
    elsewhere (CPU, odd shapes) ``w.dequant()`` and ``jnp.matmul``.  Each
    call counts its route and the weight's packed bytes in ``PACKED_GEMMS``
    at trace time (a layer scan's body counts once).
    """
    from repro.kernels import ops as kops
    K, N = w.shape
    out_dtype = x.dtype if out_dtype is None else out_dtype
    lead = x.shape[:-1]
    m = int(np.prod(lead))
    route = "kernel" if kops.packed_kernel_takes(m, w) else "dequant"
    PACKED_GEMMS.inc(PACKED_GEMM + route)
    PACKED_GEMMS.inc(PACKED_GEMM + route + ".bytes", w.nbytes())
    if route == "kernel":
        # f32 out of the kernel and the cast in XLA, as on the dequant
        # route, so that XLA places (or, with excess precision, skips) the
        # bf16 rounding alike: a model forward then agrees bit for bit
        y = kops.packed_block_matmul(x.reshape(m, K), w,
                                     out_dtype=jnp.float32)
        y = y.reshape(lead + (N,))
    else:
        y = jnp.matmul(x, w.dequant(), preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def _packed_forward(x: jax.Array, w: PackedQuantizedTensor, seed: jax.Array,
                    cfg: QuantConfig) -> jax.Array:
    """[Forward] z = Q_rtn(a) @ dequant(w_packed): the weight was quantized
    ONCE (Engine init / checkpoint export) so only the activation is
    quantized per GEMM.  ``PackedQuantizedTensor.dequant`` reconstructs
    exactly the fake-quant grid values, so the dequant route is
    bit-identical to ``_forward`` with ``fwd_w`` set; the kernel route's
    product differs only in the order of the f32 accumulation.
    Inference-only (no custom_vjp; serving never backprops).
    """
    K = w.shape[0]
    qx = _maybe_q(x, _if_divisible(cfg.fwd_a, K), axis=-1, seed=seed, site=0)
    with jax.named_scope(scopes.DOT_FWD):
        return packed_gemm(qx, w)


def fp4_matmul(x: jax.Array, w: jax.Array, *, cfg: QuantConfig,
               seed: Optional[jax.Array] = None) -> jax.Array:
    """FQT matmul  (..., K) @ (K, N) -> (..., N)  per the paper's scheme.

    ``seed``: uint32/int32 scalar controlling SR draws (required if any point
    uses stochastic rounding; derive per layer+step via ``jax.random.fold_in``
    semantics on an integer counter).
    """
    if w.ndim != 2:
        raise ValueError(f"weight must be 2D, got {w.shape}")
    if x.shape[-1] != w.shape[0]:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    if seed is None:
        seed = jnp.zeros((), jnp.uint32)
    if isinstance(w, PackedQuantizedTensor):
        return _packed_forward(x, w, jnp.asarray(seed, jnp.uint32), cfg)
    if not cfg.enabled:
        return jnp.matmul(x, w,
                          preferred_element_type=jnp.float32).astype(x.dtype)
    return _fp4_matmul(x, w, jnp.asarray(seed, jnp.uint32), cfg)


def dense(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None, *,
          cfg: QuantConfig, seed: Optional[jax.Array] = None) -> jax.Array:
    """Linear layer through the FQT matmul (bias added in bf16)."""
    y = fp4_matmul(x, w, cfg=cfg, seed=seed)
    if b is not None:
        y = y + b
    return y
