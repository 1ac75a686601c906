"""The FQT train step: loss, grads, optimizer, and the paper's §4 monitor.

One pjit-compiled function per (model cfg, quant cfg, mesh):

  1. loss/grads through ``registry.loss_fn`` — every matmul routes through
     ``fp4_matmul`` whose custom_vjp implements the paper's six quantization
     points (SR seeds derived from the step counter: deterministic,
     replayable after restart).
  2. gradient-to-noise monitor: σ_q is estimated from the actual SR
     quantization residual of the gradient tensors (paper Fig. 5 monitors
     ‖∇L‖/(σ_q·√d) against √3), EMA-tracked in ``ThresholdState``.
  3. optional inter-pod gradient compression (distributed/compression.py).
  4. AdamW with FP32 master weights + warmup/cosine LR.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fqt, threshold
from repro.core.quantize import NVFP4
from repro.distributed import compression as comp
from repro.distributed import sharding as shd
from repro.models import registry
from repro.models.config import ModelConfig
from repro.obs import scopes
from repro.optim import adamw, schedule


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt: adamw.AdamWState
    thr: threshold.ThresholdState


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: adamw.AdamWConfig = adamw.AdamWConfig()
    sched: schedule.ScheduleConfig = schedule.ScheduleConfig()
    thr: threshold.ThresholdConfig = threshold.ThresholdConfig()
    compression: Optional[comp.CompressionConfig] = None
    remat: bool = True
    probe_sigma: bool = True     # estimate σ_q each step (cheap, elementwise)
    sigma_spec: Any = None       # spec for the σ_q probe (default NVFP4-SR)
    layer_stats: bool = False    # add per-leaf ‖g‖ to metrics (telemetry:
                                 # the trainer's per-layer √3-floor series)


def init_state(cfg: ModelConfig, tcfg: TrainConfig, key) -> TrainState:
    params = registry.init_params(cfg, key)
    return TrainState(jnp.zeros((), jnp.int32), params,
                      adamw.init(params, tcfg.opt), threshold.init())


def n_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def _estimate_sigma_q(grads, step, spec=None) -> jax.Array:
    """σ_q from the SR residual of quantizing the gradients themselves with
    the paper's NVFP4-SR spec (the same noise the update GEMM injects)."""
    spec = spec if spec is not None else NVFP4.with_rounding(stochastic=True)
    num = jnp.zeros(())
    den = jnp.zeros(())
    for i, g in enumerate(jax.tree.leaves(grads)):
        if g.ndim < 2 or g.shape[-1] % spec.block:
            continue
        key = jax.random.fold_in(
            jax.random.PRNGKey(jnp.asarray(step, jnp.uint32)), i)
        from repro.core.quantize import fake_quant
        q = fake_quant(g.astype(jnp.float32), spec, axis=-1, key=key)
        r = (q - g.astype(jnp.float32)).ravel()
        num += jnp.sum(r * r)
        den += float(r.size)     # python float: leaf sizes exceed int32
    return jnp.sqrt(num / jnp.maximum(den, 1.0) + 1e-30)


def make_train_step(cfg: ModelConfig, qcfg: fqt.QuantConfig,
                    tcfg: TrainConfig, mesh: Optional[Mesh] = None):
    """Returns train_step(state, batch) -> (state, metrics); pure, jittable.

    When ``mesh`` is given the returned fn is jitted with full GSPMD
    shardings (params FSDP×TP, batch DP) and donated state.
    """
    d = None  # filled lazily from the state

    def train_step(state: TrainState, batch):
        step = state.step
        seed = jnp.asarray(step, jnp.uint32) * jnp.uint32(0x9E3779B1) + 1

        def loss_fn(p):
            return registry.loss_fn(p, cfg, qcfg, batch, seed=seed,
                                    remat=tcfg.remat)

        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params)

        if tcfg.compression is not None and mesh is not None \
                and "pod" in mesh.axis_names:
            ckey = jax.random.PRNGKey(jnp.asarray(step, jnp.uint32))
            grads = comp.pod_mean_grads(grads, ckey, mesh, tcfg.compression)

        # §4 monitor: ‖∇L‖ / (σ_q √d) vs √3
        gnorm = adamw.global_norm(grads)
        if tcfg.probe_sigma:
            with jax.named_scope(scopes.SIGMA_PROBE):
                sigma_q = _estimate_sigma_q(grads, step, tcfg.sigma_spec)
        else:
            sigma_q = state.thr.sigma_q
        dd = sum(x.size for x in jax.tree.leaves(grads))
        thr_state = threshold.update(state.thr, gnorm, dd, sigma_q, tcfg.thr)

        lr = schedule.lr_at(step, tcfg.sched)
        with jax.named_scope(scopes.OPTIM):
            params, opt, opt_metrics = adamw.apply(grads, state.opt,
                                                   tcfg.opt, lr)

        metrics = {
            "loss": loss.astype(jnp.float32),
            "nll": aux["nll"].astype(jnp.float32),
            "grad_norm": opt_metrics["grad_norm"],
            "lr": lr,
            "sigma_q": sigma_q,
            "gnr": thr_state.ratio_ema,          # gradient-to-noise ratio
            "thr_crossed": thr_state.crossed.astype(jnp.float32),
        }
        if tcfg.layer_stats:
            # per-leaf gradient norms, stacked in tree-leaf order — the
            # trainer pairs them with leaf paths/sizes on the host to
            # emit the per-layer ‖g_i‖/(σ_q·√d_i) trace series
            metrics["layer_gnorms"] = jnp.stack(
                [jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                 for g in jax.tree.leaves(grads)])
        return TrainState(step + 1, params, opt, thr_state), metrics

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0,))
    return train_step  # caller jits with explicit shardings (launch/train.py)


def state_shardings(state: TrainState, mesh: Mesh):
    pshard = shd.params_shardings(state.params, mesh)
    rep = NamedSharding(mesh, P())
    return TrainState(
        step=rep,
        params=pshard,
        opt=adamw.AdamWState(step=rep, master=pshard, m=pshard, v=pshard),
        thr=jax.tree.map(lambda _: rep, state.thr),
    )


def jit_train_step(cfg: ModelConfig, qcfg: fqt.QuantConfig,
                   tcfg: TrainConfig, mesh: Mesh, state_struct: TrainState):
    """Fully-sharded jitted train step for a production mesh."""
    fn = make_train_step(cfg, qcfg, tcfg, mesh)
    st_sh = state_shardings(state_struct, mesh)
    batch_sh = {"tokens": NamedSharding(mesh, shd.batch_spec(mesh))}
    rep = NamedSharding(mesh, P())
    mkeys = {"loss": 0, "nll": 0, "grad_norm": 0, "lr": 0, "sigma_q": 0,
             "gnr": 0, "thr_crossed": 0}
    if tcfg.layer_stats:
        mkeys["layer_gnorms"] = 0
    return jax.jit(
        fn,
        in_shardings=(st_sh, batch_sh),
        out_shardings=(st_sh, jax.tree.map(lambda _: rep, mkeys)),
        donate_argnums=(0,),
    )
