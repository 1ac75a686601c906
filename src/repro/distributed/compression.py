"""SR-quantized gradient compression for the inter-pod all-reduce.

At multi-pod scale the gradient all-reduce crosses the (slow) inter-pod
links while everything else stays on intra-pod ICI.  We compress that hop
with the same machinery the paper builds for FP4 training: block-scaled
low-precision codes with *stochastic rounding*, which keeps the compressed
all-reduce **unbiased** — the paper's §4 analysis (SR noise only adds a
variance term σ_q²·tr(H), no bias floor) applies verbatim to gradient
compression noise, and the same √3 gradient-to-noise threshold tells you
when 8-bit compression stops being safe and the trainer should fall back to
bf16 reduction.

Default format: E4M3 codes + E4M3 block-32 scales (2× the bytes of FP4;
measured σ_q stays ~50× below the gradient threshold for the 7B run — see
EXPERIMENTS.md §Perf).  The collective itself is a ``psum`` inside a
``shard_map`` that is *manual only over the pod axis* — in-pod GSPMD
sharding (FSDP/TP) passes through untouched.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.quantize import BlockQuantSpec, fake_quant


# E4M3 codes + E4M3 block scales (two-level): the E8M0 floor rule would map
# block maxima into [256, 512) against e4m3's 448 ceiling — a clipping bias
# SR cannot remove.  Two-level amax scaling keeps the compressed all-reduce
# unbiased up to tail clipping only.
GRAD_FP8 = BlockQuantSpec(data_fmt="e4m3", scale_fmt="e4m3", block=32,
                          two_level=True, stochastic=True)
# Aggressive NVFP4 variant (the paper's own format) for bandwidth-starved
# inter-pod links; the √3 monitor decides whether it is safe.
GRAD_FP4 = BlockQuantSpec(data_fmt="e2m1", scale_fmt="e4m3", block=16,
                          two_level=True, stochastic=True)


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    enabled: bool = True
    spec: BlockQuantSpec = GRAD_FP8
    # quantize the *result* again after the psum so every pod holds
    # bit-identical gradients (determinism across elastic restarts)
    requantize_result: bool = False


def _leaf_compress_psum(g: jax.Array, key: jax.Array, axis: str,
                        spec: BlockQuantSpec, npods: int) -> jax.Array:
    """Quantize local gradient shard -> psum over pods -> mean."""
    orig_dtype, shape = g.dtype, g.shape
    flat = g.astype(jnp.float32).ravel()
    pad = (-flat.size) % spec.block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    # each pod uses a distinct SR draw (fold in its pod index) so noise
    # averages down across pods instead of adding coherently
    key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    q = fake_quant(flat[None], spec, axis=-1, key=key)[0]
    summed = jax.lax.psum(q, axis)
    out = (summed / npods)[: flat.size - pad if pad else flat.size]
    return out.reshape(shape).astype(orig_dtype)


def compressed_psum_mean(grads, key: jax.Array, axis: str,
                         spec: BlockQuantSpec, npods: int):
    """Compressed mean-all-reduce of a gradient pytree over ``axis``.

    Must run inside a shard_map manual over ``axis``.  Each leaf gets an
    independent SR stream derived from ``key``.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    out = []
    for i, g in enumerate(leaves):
        out.append(_leaf_compress_psum(g, jax.random.fold_in(key, i), axis,
                                       spec, npods))
    return jax.tree_util.tree_unflatten(treedef, out)


def pod_mean_grads(grads, key: jax.Array, mesh: Mesh,
                   cfg: Optional[CompressionConfig]):
    """Average per-pod gradients across the "pod" axis.

    ``grads`` are *per-pod local means* laid out with in-pod GSPMD sharding;
    this wraps the pod-axis reduction in shard_map (manual over "pod" only;
    "data"/"model" stay automatic) and compresses it per ``cfg``.
    Outside shard_map; call from the pjit'd train step.
    """
    if "pod" not in mesh.axis_names:
        return grads
    npods = mesh.devices.shape[mesh.axis_names.index("pod")]
    if npods == 1:
        return grads

    # manual ONLY over "pod": in-pod GSPMD axes stay automatic
    manual = frozenset({"pod"})
    specs = jax.tree_util.tree_map(lambda _: P(), grads)

    if cfg is None or not cfg.enabled:
        fn = lambda g: jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, "pod"), g)
        return jax.shard_map(fn, mesh=mesh, in_specs=(specs,),
                             out_specs=specs, axis_names=manual,
                             check_vma=False)(grads)

    fn = partial(compressed_psum_mean, axis="pod", spec=cfg.spec,
                 npods=npods)
    return jax.shard_map(
        lambda g, k: fn(g, k), mesh=mesh,
        in_specs=(specs, P()), out_specs=specs, axis_names=manual,
        check_vma=False)(grads, key)


def compression_ratio(spec: BlockQuantSpec, src_bits: int = 16) -> float:
    """Wire bytes ratio vs uncompressed (bf16) gradients."""
    bits = spec.data.nbits + spec.scale.nbits / spec.block
    return src_bits / bits


# ---- packed-weight collectives (serving FSDP gather) ---------------------------
#
# The gradient wire format above (low-bit codes + block scales) is ALSO the
# right wire format for gathering FSDP-sharded serving weights: a
# ``PackedQuantizedTensor`` already stores uint8 nibble codes + f8 block
# scales, so an all-gather of its leaves moves ~4.5 bits/param (NVFP4:
# 4 + 8/16) instead of 16 for a bf16 weight gather — the per-slice pow2
# tensor scale is replicated and never travels.


def packed_wire_bits_per_param(block: int = 16) -> float:
    """Bits/param an all-gather of packed NVFP4 weights moves (~4.5)."""
    from repro.distributed.specs import packed_wire_bits_per_param as f
    return f(block)


def packed_gather_ratio(block: int = 16, src_bits: int = 16) -> float:
    """bf16-gather bytes / packed-gather bytes (~3.56x for NVFP4)."""
    return src_bits / packed_wire_bits_per_param(block)


def allgather_packed(pt, axis: str, dim: int = 0):
    """All-gather a ``PackedQuantizedTensor`` shard along logical ``dim``
    inside a shard_map manual over ``axis`` — the FSDP-style weight gather
    of sharded serving.

    Only the wire format travels: the uint8 nibble codes directly, and the
    block scales bitcast to uint8 for the hop (f8 collectives are not
    portable across backends; the bytes are identical either way).  ``dim``
    must not be the nibble-packed last axis (shard FSDP on the contraction
    axis, as the sharding rules do).
    """
    import dataclasses as _dc

    import jax.numpy as _jnp
    from repro.core.quantize import PackedQuantizedTensor
    assert isinstance(pt, PackedQuantizedTensor), type(pt)
    dim = dim % pt.ndim
    if dim == pt.ndim - 1:
        raise ValueError("cannot gather along the nibble-packed last axis")
    packed = jax.lax.all_gather(pt.packed, axis, axis=dim, tiled=True)
    if _jnp.dtype(pt.scales.dtype).itemsize == 1:
        sc_u8 = jax.lax.bitcast_convert_type(pt.scales, _jnp.uint8)
        sc_u8 = jax.lax.all_gather(sc_u8, axis, axis=dim, tiled=True)
        scales = jax.lax.bitcast_convert_type(sc_u8, pt.scales.dtype)
    else:                     # non-f8 scale formats: gather as stored
        scales = jax.lax.all_gather(pt.scales, axis, axis=dim, tiled=True)
    return _dc.replace(pt, packed=packed, scales=scales)
