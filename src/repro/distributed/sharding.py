"""GSPMD sharding rules for every model family in the zoo.

The production mesh (launch/mesh.py) is ("data", "model") per pod, with an
outer "pod" axis for multi-pod jobs.  We use a MaxText-style two-level
scheme expressed directly as PartitionSpecs:

  * TP   — the hidden/ffn/head/vocab dimension of each weight is sharded on
           "model" (16-way tensor parallelism inside a pod).
  * FSDP — the d_model dimension of each weight is sharded on "data"
           (ZeRO-3: weights, master copies and Adam moments are all sharded;
           GSPMD inserts the per-layer all-gathers / reduce-scatters).
  * DP   — the batch dimension of activations is sharded on ("pod", "data");
           weights are *replicated across pods* so the only inter-pod
           traffic is the gradient all-reduce (which is where the FP8+SR
           gradient compression of distributed/compression.py applies).
  * SP   — long-context decode shards the KV-cache sequence dimension on
           "model" (sequence parallelism; attention runs distributed flash
           over the cache).

Rules are name+rank based, resolved per parameter leaf, so one table covers
all six families (dense / moe / vlm / hybrid / ssm / encdec) including their
lax.scan-stacked layer dimensions (a leading L axis mapped to None).
"""
from __future__ import annotations

import contextlib
import contextvars
import logging
import re
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.quantize import PackedQuantizedTensor
from repro.distributed import specs as pspecs

logger = logging.getLogger(__name__)


# ---- axis helpers -------------------------------------------------------------


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel (batch) mesh axes: ('pod','data') or ('data',)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axis(mesh: Mesh) -> Optional[str]:
    return "data" if "data" in mesh.axis_names else None


def tp_axis(mesh: Mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


# ---- parameter rules ----------------------------------------------------------

# (regex on the slash-joined tree path, spec builder for the *trailing* 2
# dims).  IN = d_model-like input dim -> FSDP("data"); OUT = hidden-like
# output dim -> TP("model").  Leading stacked dims (scan L, experts E when
# not EP) map to None.
#   kind "io":  (..., IN, OUT)   e.g. wq, w_gate, in_proj
#   kind "oi":  (..., OUT, IN)   e.g. wo, w_down, out_proj
#   kind "vocab_d": (V, d)       embed tables
#   kind "d_vocab": (d, V)       lm_head
#   kind "vec_out": (..., OUT)   biases/smooth living in the hidden dim
#   kind "rep": replicated
_PARAM_RULES = (
    (r"(^|/)(wq|wk|wv|w_gate|w_up|w_in|w_ff_gate|w_ff_up|in_proj|w_gates"
     r"|r_gates|w_q|w_k|w_v|w_if)$", "io"),
    (r"(^|/)(wo|w_down|w_out|out_proj|w_ff_down)$", "oi"),
    (r"(^|/)router$", "d_rep"),          # (..., d, E): router stays tiny
    (r"(^|/)(embed|pos_dec|pos_enc)$", "vocab_d"),
    (r"(^|/)lm_head$", "d_vocab"),
    (r"(^|/)(bq|bk|bv|b_in|b_out|smooth)$", "vec_out"),
    (r"(^|/)(conv_w)$", "vec_out"),      # (..., K, C): C is hidden-like
    (r".*", "rep"),                      # norms, gates, A_log, dt_bias, ...
)


def _spec_for(kind: str, ndim: int, mesh: Mesh,
              expert_parallel: bool = False) -> P:
    fsdp, tp = fsdp_axis(mesh), tp_axis(mesh)
    lead = (None,) * (ndim - 2)
    if kind == "io":
        return P(*lead, fsdp, tp) if ndim >= 2 else P(tp)
    if kind == "oi":
        return P(*lead, tp, fsdp) if ndim >= 2 else P(tp)
    if kind == "d_rep":
        return P(*lead, fsdp) if ndim >= 2 else P()
    if kind == "vocab_d":
        return P(tp, fsdp)
    if kind == "d_vocab":
        return P(fsdp, tp)
    if kind == "vec_out":
        return P(*((None,) * (ndim - 1)), tp)
    return P()


def param_spec(path: str, ndim: int, mesh: Mesh) -> P:
    for pat, kind in _PARAM_RULES:
        if re.search(pat, path):
            return _spec_for(kind, ndim, mesh)
    return P()


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _divisible(spec: P, shape, mesh: Mesh, path: str = "",
               strict: bool = False) -> P:
    """Drop mesh axes that do not divide the corresponding dim (jit allows
    uneven shardings, but padded weight shards waste memory and make the
    roofline numbers lie — prefer replication for the odd dims).

    When ``path`` names the leaf (parameter/cache shardings do), every
    dropped axis is DIAGNOSED — logged, or raised with ``strict=True`` —
    instead of silently replicating: under nibble packing the blocking
    axis is halved, and a "sharded" deploy quietly holding full replicas
    is a correctness-adjacent perf bug.  Anonymous calls (activation
    constraints, where odd smoke-config dims are routine) stay silent.
    """
    drops: list = [] if path else None
    fixed = pspecs.divisible_axes(tuple(spec), tuple(shape),
                                  _mesh_axis_sizes(mesh), path=path,
                                  drops=drops)
    if drops:
        if strict:
            raise ValueError("; ".join(drops))
        for d in drops:
            logger.warning("sharding: %s", d)
    return P(*fixed)


def params_shardings(params, mesh: Mesh):
    """NamedSharding pytree for a parameter pytree (rank+name rules)."""

    def one(path, x):
        spec = param_spec(_path_str(path), x.ndim, mesh)
        return NamedSharding(mesh, _divisible(spec, x.shape, mesh,
                                              path=_path_str(path)))

    return jax.tree_util.tree_map_with_path(one, params)


def params_specs(params, mesh: Mesh):
    def one(path, x):
        return _divisible(param_spec(_path_str(path), x.ndim, mesh),
                          x.shape, mesh, path=_path_str(path))

    return jax.tree_util.tree_map_with_path(one, params)


# ---- activations / batch / optimizer ------------------------------------------


def batch_spec(mesh: Mesh) -> P:
    """(B, S) token batches: batch over DP axes."""
    return P(dp_axes(mesh))


def batch_shardings(batch_struct, mesh: Mesh):
    dp = dp_axes(mesh)

    def one(x):
        spec = P(dp) if x.shape and x.shape[0] % _axes_size(mesh, dp) == 0 \
            else P()
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(one, batch_struct)


def _axes_size(mesh: Mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes[a]
    return max(n, 1)


def opt_state_shardings(opt_state, params_shards, mesh: Mesh):
    """AdamW state: master/m/v follow the parameter sharding; step scalar
    replicated."""
    import dataclasses  # noqa: F401
    from repro.optim.adamw import AdamWState

    rep = NamedSharding(mesh, P())
    return AdamWState(
        step=rep,
        master=params_shards,
        m=params_shards,
        v=params_shards,
    )


# ---- activation sharding constraints -------------------------------------------
#
# Parameter shardings alone do not pin down the activation layout: the embed
# table's (vocab→TP, d→FSDP) sharding would otherwise leak `d→data` into the
# residual stream and kick the batch off the "data" axis (replicating every
# (B,S,·) tensor 16×).  Model code therefore calls ``constrain(x, kind)`` at
# the canonical points; it is a no-op unless a launcher opened an
# ``activation_sharding_scope`` (smoke tests / single-device runs unaffected).
#
# Modes:
#   "replicated" — residual stream (B,S,d) = P(dp, None, None): classic
#                  Megatron TP (norms/residual replicated across "model").
#   "sp"         — residual stream = P(dp, "model", None): Megatron-style
#                  sequence parallelism; 16× smaller saved activations, same
#                  wire bytes (all-gather+reduce-scatter replaces all-reduce).

_ACT_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_act_sharding", default=None)


@contextlib.contextmanager
def activation_sharding_scope(mesh: Mesh, mode: str = "sp"):
    if mode not in ("replicated", "sp"):
        raise ValueError(mode)
    tok = _ACT_CTX.set((mesh, mode))
    try:
        yield
    finally:
        _ACT_CTX.reset(tok)


def _constrain_spec(kind: str, shape, mesh: Mesh, mode: str) -> Optional[P]:
    dp, tp = dp_axes(mesh), tp_axis(mesh)
    nd = len(shape)
    if kind == "res":        # (B, S, d) residual stream
        seq = tp if (mode == "sp" and nd >= 3) else None
        spec = (dp, seq) + (None,) * (nd - 2)
    elif kind == "hidden":   # (..., f) TP on the trailing hidden dim
        spec = (dp,) + (None,) * (nd - 2) + (tp,)
    elif kind == "heads":    # (B, S, H, D) TP on heads
        spec = (dp,) + (None,) * (nd - 3) + (tp, None)
    elif kind == "logits":   # (B, S, V) TP on vocab
        spec = (dp,) + (None,) * (nd - 2) + (tp,)
    elif kind == "tokens":   # (T, d) flattened token table (MoE)
        spec = (dp,) + (None,) * (nd - 1)
    elif kind == "experts":  # (E, C, d) expert dispatch buffers
        spec = (None, dp) + (None,) * (nd - 2)
    elif kind == "groups":   # (G, ...) MoE group-limited dispatch: G -> dp
        spec = (dp,) + (None,) * (nd - 1)
    elif kind == "qblocks":  # (B, nq, qc, KVH, G, D) flash-attention blocks
        # TP on heads when divisible, else context-parallel on q blocks
        tp_size = _axes_size(mesh, (tp,)) if tp else 1
        if nd == 6 and tp and (shape[3] * shape[4]) % tp_size == 0:
            # shard the larger of (KVH, G) — one must absorb the axis
            if shape[3] % tp_size == 0:
                spec = (dp, None, None, tp, None, None)
            elif shape[4] % tp_size == 0:
                spec = (dp, None, None, None, tp, None)
            else:
                spec = (dp, tp, None, None, None, None)
        else:
            spec = (dp, tp) + (None,) * (nd - 2)
    else:
        raise ValueError(f"unknown constraint kind {kind!r}")
    return _divisible(P(*spec), shape, mesh)


def constrain(x, kind: str):
    """with_sharding_constraint(x, rule) under the active scope; else x."""
    ctx = _ACT_CTX.get()
    if ctx is None or not hasattr(x, "shape") or x.ndim < 2:
        return x
    mesh, mode = ctx
    spec = _constrain_spec(kind, x.shape, mesh, mode)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---- mesh-native serving -------------------------------------------------------
#
# The serving stack places everything under ONE explicit Mesh; a 1-device
# mesh is the degenerate case of the same code path (device_put with a
# replicated spec on one device is the identity), so the engines carry no
# ``if sharded:`` forks.  Axes: "model" is the serving TP axis (heads /
# hidden / vocab), optional "data" is an FSDP-style axis over which packed
# weights are gathered at ~4.5 bits/param (distributed/compression.py).


def make_serve_mesh(spec: Optional[str] = None, *, devices=None) -> Mesh:
    """Build the serving mesh from a ``--mesh`` CLI spec ("tp=2", ...).

    ``None``/empty means the degenerate 1-device mesh over the default
    device — the unsharded engine IS this mesh's special case.
    """
    sizes = pspecs.parse_mesh_spec(spec)
    axes = tuple(a for a in ("data", "model") if a in sizes)
    shape = tuple(sizes[a] for a in axes)
    need = int(np.prod(shape))
    devices = list(jax.devices()) if devices is None else list(devices)
    if need > len(devices):
        raise ValueError(
            f"mesh spec {spec!r} needs {need} devices, have "
            f"{len(devices)}; on CPU force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"(set BEFORE jax initializes)")
    return Mesh(np.array(devices[:need]).reshape(shape), axes)


def spec_for_packed(path: str, leaf: PackedQuantizedTensor,
                    mesh: Mesh) -> dict:
    """Partition specs for the three leaves of one packed weight.

    The code (``packed``) spec comes from the SAME rule table that shards
    the unpacked bf16 weight, re-validated against the nibble-halved and
    scale-blocked leaf shapes; the ``scales``/``tscale`` specs are DERIVED
    from the code spec (distributed/specs.packed_leaf_specs), so block-
    scale axes always shard congruently with code axes — they cannot
    diverge.  Returns ``{"packed": P, "scales": P, "tscale": P}``.
    """
    base = param_spec(path, leaf.ndim, mesh)
    drops: list = []
    out = pspecs.packed_leaf_specs(tuple(base), tuple(leaf.shape), leaf.axis,
                                   leaf.block, _mesh_axis_sizes(mesh),
                                   path=path, drops=drops)
    for d in drops:
        logger.warning("sharding: %s", d)
    return {k: P(*v) for k, v in out.items()}


def serve_params_shardings(params, mesh: Mesh):
    """The shardings ``place_serve_params`` puts a (possibly packed)
    parameter pytree under: packed leaves get ``spec_for_packed`` shardings
    on their nibble-code / block-scale / tensor-scale arrays; plain leaves
    follow ``param_spec``.  Leaves that are not arrays stay as they are."""

    def one(path, leaf):
        p = _path_str(path)
        if isinstance(leaf, PackedQuantizedTensor):
            sh = spec_for_packed(p, leaf, mesh)
            return leaf.map_leaves(
                lambda name, x: NamedSharding(mesh, sh[name]))
        if not hasattr(leaf, "ndim"):
            return leaf
        spec = _divisible(param_spec(p, leaf.ndim, mesh), leaf.shape, mesh,
                          path=p)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(
        one, params, is_leaf=lambda x: isinstance(x, PackedQuantizedTensor))


def place_serve_params(params, mesh: Mesh):
    """device_put a (possibly packed) parameter pytree under ``mesh``, with
    ``serve_params_shardings``.  On a 1-device mesh this is the identity
    placement."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, s) if hasattr(x, "ndim") else x,
        params, serve_params_shardings(params, mesh))


def serve_cache_shardings(cache_struct, mesh: Mesh):
    """Shardings for serving decode state under the serving mesh.

    ``PagedKVCache`` physical page pools (``(…, P, page, KVH, Dc)`` codes
    and scales) shard their KV-heads axis over the TP axis ("model") —
    each device holds the KV pages of its own heads, exactly the heads it
    attends with under Megatron TP.  Page-table rows and lengths are tiny
    int32 host-managed state and stay replicated (the host mutates them
    identically everywhere).  All other cache leaves are replicated.
    """
    tp = tp_axis(mesh)
    from repro.models.layers import PagedKVCache

    def pool_spec(x, path):
        spec = [None] * x.ndim
        if tp is not None and x.ndim >= 2:
            spec[PagedKVCache.HEADS_AXIS] = tp
            return _divisible(P(*spec), x.shape, mesh, path=path)
        return P(*spec)

    def one(path, leaf):
        p = _path_str(path)
        if isinstance(leaf, PagedKVCache):
            import dataclasses as _dc
            return _dc.replace(
                leaf,
                k_codes=NamedSharding(mesh, pool_spec(leaf.k_codes,
                                                      p + "/k_codes")),
                k_scales=NamedSharding(mesh, pool_spec(leaf.k_scales,
                                                       p + "/k_scales")),
                v_codes=NamedSharding(mesh, pool_spec(leaf.v_codes,
                                                      p + "/v_codes")),
                v_scales=NamedSharding(mesh, pool_spec(leaf.v_scales,
                                                       p + "/v_scales")),
                page_table=NamedSharding(mesh, P()),
                lengths=NamedSharding(mesh, P()))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(
        one, cache_struct, is_leaf=lambda x: isinstance(x, PagedKVCache))


def place_serve_cache(cache, mesh: Mesh):
    """device_put serving decode state under ``mesh`` (identity on 1 dev)."""
    shards = serve_cache_shardings(cache, mesh)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    shard_leaves = jax.tree_util.tree_leaves(shards)
    placed = [jax.device_put(x, s) for x, s in zip(leaves, shard_leaves)]
    return jax.tree_util.tree_unflatten(treedef, placed)


def constrain_serve_cache(cache, mesh: Mesh):
    """In-jit counterpart of ``place_serve_cache``: annotate the carry a
    compiled serving program RETURNS with the same shardings its inputs
    were placed under, so every subsequent call sees identical input
    shardings (the engines' no-recompile guarantee holds on any mesh).
    Pure layout annotation — leaf values are untouched."""
    shards = serve_cache_shardings(cache, mesh)
    leaves, treedef = jax.tree_util.tree_flatten(cache)
    shard_leaves = jax.tree_util.tree_leaves(shards)
    out = [jax.lax.with_sharding_constraint(x, s)
           for x, s in zip(leaves, shard_leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---- KV cache / decode state ---------------------------------------------------


def cache_specs(cache_struct, mesh: Mesh, batch: int):
    """Sharding for serving state.

    KV caches (B, S, KVH, D): batch on DP axes when divisible, sequence on
    "model" (SP — the 32k/500k caches dominate HBM).  SSM states
    (B, H, P, N): heads on "model".  Conv states and small tensors follow
    batch-only sharding.  Works on the registry's cache pytrees (stacked
    KVCache dataclasses, dicts of ssm/conv states, tuples).
    """
    dp = dp_axes(mesh)
    tp = tp_axis(mesh)
    dp_ok = batch % _axes_size(mesh, dp) == 0

    def one(x):
        shape = x.shape
        bspec = dp if (dp_ok and len(shape) and shape[0] in (batch,)) else None
        # stacked-by-layer caches have shape (L, B, ...) — detect batch pos
        bdim = 0
        if len(shape) >= 2 and shape[0] != batch and shape[1] == batch:
            bdim = 1
        spec = [None] * len(shape)
        if bspec is not None and len(shape) > bdim and shape[bdim] == batch:
            spec[bdim] = dp
        # shard the longest remaining dim on "model" if divisible (SP for
        # seq, head-parallel for SSM states)
        if tp is not None and len(shape) > bdim + 1:
            rest = [(d, s) for d, s in enumerate(shape) if d > bdim]
            d_best, s_best = max(rest, key=lambda t: t[1])
            if s_best % _axes_size(mesh, (tp,)) == 0 and s_best > 1:
                spec[d_best] = tp
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, cache_struct)
