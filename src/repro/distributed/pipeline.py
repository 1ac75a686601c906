"""Pipeline parallelism: GPipe schedule over a "pipe" mesh axis.

The production configs default to FSDP×TP (the scanned layer stack keeps
HLO size O(1) in depth), but at >512-chip scale the FSDP all-gather of
llama3-405B-class weights becomes the dominant collective.  This module
provides the alternative: split the layer stack into ``pipe`` stages held
on different devices and stream microbatches through with
``collective_permute`` — the inter-stage hop is a point-to-point transfer
of one microbatch's activations instead of an all-gather of weights.

Implementation: a ``shard_map`` manual over the "pipe" axis.  Each stage
holds ``L/S`` layers (the stacked-params leading axis is sharded on
"pipe"); a ``lax.scan`` over ``M + S - 1`` ticks advances the classic GPipe
diagonal: at tick t, stage s processes microbatch ``t - s`` (bubble ticks
compute garbage that is masked on collection).  Backward is ordinary
autodiff through the scan — reverse-mode turns each ppermute into its
inverse, which reproduces the backward pipeline schedule.

Composability: "data"/"model" axes stay automatic inside the shard_map, so
in-stage FSDP/TP sharding (distributed/sharding.py) passes through, giving
DP × PP × TP 3-D parallelism.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_stages: int
    n_microbatches: int
    axis: str = "pipe"


def _stage_specs(params, axis):
    """Stacked layer params (leading L axis) are split across stages."""
    return jax.tree_util.tree_map(
        lambda x: P(axis, *(None,) * (x.ndim - 1)), params)


def pipeline_apply(layer_fn: Callable, stacked_params, x: jax.Array,
                   mesh: Mesh, cfg: PipelineConfig) -> jax.Array:
    """Run ``x`` through L stacked layers split over ``cfg.n_stages`` stages.

    layer_fn(per_layer_params, h) -> h, applied ``L/S`` times per stage via
    an inner scan.  x: (B, ...) with B divisible by n_microbatches.
    Returns the transformed activations, same shape as x.
    """
    S, M, axis = cfg.n_stages, cfg.n_microbatches, cfg.axis
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M

    def stage_inner(stage_params, h):
        # apply this stage's L/S layers (scan keeps HLO size constant)
        def body(h, lp):
            return layer_fn(lp, h), None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    def pipelined(stage_params, xs):
        # xs: (M, mb, ...) this is per-pipe-shard full batch (batch is NOT
        # sharded on "pipe"; DP axes handle batch)
        sid = jax.lax.axis_index(axis)
        nticks = M + S - 1
        buf = jnp.zeros((mb,) + xs.shape[2:], xs.dtype)
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (clamped; bubbles masked later)
            feed = xs[jnp.minimum(t, M - 1)]
            h_in = jnp.where(sid == 0, feed, buf)
            h_out = stage_inner(stage_params, h_in)
            # pass to the next stage
            perm = [(i, (i + 1) % S) for i in range(S)]
            buf_next = jax.lax.ppermute(h_out, axis, perm)
            # last stage emits microbatch t - (S - 1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            emit = (t >= S - 1) & (sid == S - 1)
            outs = jax.lax.cond(
                emit, lambda o: o.at[out_idx].set(h_out), lambda o: o, outs)
            return (buf_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(nticks, dtype=jnp.int32))
        # every pipe shard returns outs; only the last stage's is real —
        # broadcast it back (psum of masked copies)
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    xs = x.reshape((M, mb) + x.shape[1:])
    out = jax.shard_map(
        pipelined, mesh=mesh,
        in_specs=(_stage_specs(stacked_params, axis), P()),
        out_specs=P(), axis_names=frozenset({axis}), check_vma=False,
    )(stacked_params, xs)
    return out.reshape(x.shape)


def make_pipeline_mesh(n_stages: int, total_devices: int | None = None):
    """A (pipe, data) mesh over the available devices (testing helper)."""
    n = total_devices or len(jax.devices())
    if n % n_stages:
        raise ValueError(f"{n} devices not divisible into {n_stages} stages")
    return jax.make_mesh((n_stages, n // n_stages), ("pipe", "data"))
