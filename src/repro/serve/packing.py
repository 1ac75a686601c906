"""Quantize-once packed NVFP4 weight preparation for serving.

The QAF phase keeps the forward pass in FP4 so the deployed model is
FP4-inference-compatible — yet a naive serving engine re-fake-quantizes the
full bf16 weights from HBM on every decoded token, paying bf16 weight
bandwidth for FP4 numerics.  ``pack_model_params`` converts every GEMM
weight of a model pytree into a ``PackedQuantizedTensor`` (uint8 nibble
codes + float8 block scales + pow2 tensor scale, ~0.56 bytes/param for
NVFP4): quantization happens ONCE at engine build / checkpoint export, and
the forward path (core/fqt.py ``_packed_forward``) consumes the packed
representation directly.

Correctness invariant: ``PackedQuantizedTensor.dequant`` reconstructs the
fake-quant grid values bit-exactly, and the per-slice tensor scale of
``pack_quantize(batch_dims=...)`` matches per-GEMM quantization under
lax.scan/vmap slicing — so packed serving is token-identical to the
fake-quant forward.  Leaves NOT packed here (router, norms, biases, embed,
gates) take the unchanged path; packing is purely a storage/bandwidth
optimization, never a numerics change.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np

from repro.core.quantize import (BlockQuantSpec, PackedQuantizedTensor,
                                 pack_quantize)
from repro.models.config import ModelConfig

# Leaf names consumed as the RHS of ``QCtx.dense`` (x @ w, contraction on
# axis -2) across the model zoo.  Everything else — routers and recurrence
# gates (dense_hp, precision-critical), embeddings (table lookups), norms,
# biases, smooth factors, convs — stays in bf16.
WEIGHT_KEYS = frozenset({
    # transformer / moe
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "w_in", "w_out",
    # mamba2 (hybrid)
    "in_proj", "out_proj",
    # xlstm (ssm)
    "w_q", "w_k", "w_v", "w_gates", "w_ff_gate", "w_ff_up", "w_ff_down",
})
HEAD_KEYS = frozenset({"lm_head"})

# Compiled, so XLA fuses the quantization of a weight: op by op, each step
# holds a temporary the size of the f32 weight on the device.
_pack_leaf = jax.jit(pack_quantize, static_argnames=("spec", "axis",
                                                     "batch_dims"))


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
        if isinstance(entry, jax.tree_util.GetAttrKey):
            return str(entry.name)
    return ""


def _packable(name: str, leaf, spec: BlockQuantSpec,
              quantize_lm_head: bool) -> bool:
    if name in HEAD_KEYS:
        if not quantize_lm_head:
            return False
    elif name not in WEIGHT_KEYS:
        return False
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    if not _is_float_leaf(leaf):
        return False
    # mirror fqt._if_divisible: irregular contraction dims stay bf16
    return leaf.shape[-2] % spec.block == 0 and leaf.shape[-1] % 2 == 0


def _is_float_leaf(leaf) -> bool:
    return np.issubdtype(np.dtype(leaf.dtype), np.floating) or \
        str(leaf.dtype) == "bfloat16"


def pack_model_params(cfg: ModelConfig, params: Any,
                      spec: Optional[BlockQuantSpec],
                      mesh: Optional[Any] = None) -> Any:
    """Pack every GEMM weight of ``params`` with ``spec`` (fwd_w).

    Stacked layer/expert weights keep their leading axes as batch dims
    (per-slice tensor scales), so scan/vmap layer application sees exactly
    the per-matrix quantization of the fake-quant forward.  Returns a new
    pytree; with ``spec=None`` the tree is returned unchanged (no packing).

    With ``mesh`` (a ``jax.sharding.Mesh``) the result is additionally
    placed under that mesh: every packed leaf's nibble-code / block-scale /
    tensor-scale arrays get the congruent partition specs of
    ``distributed/sharding.spec_for_packed`` (scale specs derived from code
    specs, so they can never diverge), and unpacked leaves follow the
    standard parameter rules.  A 1-device mesh is an identity placement —
    the unsharded engine is the degenerate case of the same path.
    """
    packed = params
    if spec is not None:
        def pack(path, leaf):
            name = _leaf_name(path)
            if not _packable(name, leaf, spec, cfg.quantize_lm_head):
                return leaf
            return _pack_leaf(leaf, spec, axis=-2, batch_dims=leaf.ndim - 2)

        packed = jax.tree_util.tree_map_with_path(pack, params)

    if mesh is not None:
        from repro.distributed.sharding import place_serve_params
        packed = place_serve_params(packed, mesh)
    return packed


def weight_store_bytes(params: Any) -> int:
    """Total stored bytes of a params pytree (packed leaves counted at their
    packed size) — the decode-path weight HBM traffic per full pass."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, PackedQuantizedTensor)):
        if isinstance(leaf, PackedQuantizedTensor):
            total += leaf.nbytes()
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total


def weight_wire_bytes(params: Any) -> int:
    """Bytes a full FSDP-style weight all-gather moves under the serving
    mesh: packed leaves travel as their wire format (uint8 nibble codes +
    f8 block scales, ~4.5 bits/param — ``PackedQuantizedTensor.
    wire_nbytes``; the replicated tscale never travels), unpacked leaves
    as stored."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, PackedQuantizedTensor)):
        if isinstance(leaf, PackedQuantizedTensor):
            total += leaf.wire_nbytes()
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
    return total


def param_count(params: Any) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, PackedQuantizedTensor)):
        total += int(np.prod(leaf.shape)) if hasattr(leaf, "shape") else 0
    return total
