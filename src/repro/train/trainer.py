"""Trainer: the fault-tolerant training loop.

Production duties, scaled down to run on one host but structured for 1000+
nodes (DESIGN.md §6):

  * **checkpoint/restart** — atomic sharded checkpoints every
    ``ckpt_every`` steps; on start, ``Trainer.run`` resumes from the latest
    checkpoint (different mesh OK — elastic resharding in checkpoint/ckpt).
    The data pipeline is step-indexed, so the resumed run consumes exactly
    the token stream it would have seen.
  * **straggler mitigation** — per-step wall-time is tracked against a
    running median; a step slower than ``straggler_factor``× median is
    recorded (on a real cluster the event triggers hot-spare swap /
    re-slicing; the detection + accounting layer is what lives here).
  * **QAF auto-switch** (the paper's §4→§5 pipeline) — when the
    gradient-to-noise EMA crosses √3 (or at a fixed step), the trainer
    re-builds the step function with the QAF QuantConfig (FP4 forward, BF16
    backward) and re-warms the LR, continuing from the same state.
  * **preemption safety** — SIGTERM sets a flag; the loop checkpoints and
    exits cleanly at the next step boundary.
"""
from __future__ import annotations

import dataclasses
import os
import signal
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.core import fqt, qaf, quantize, threshold
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.config import ModelConfig
from repro.obs.trace import NULL_TRACER
from repro.optim import schedule
from repro.train import step as step_mod


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    log_every: int = 10
    straggler_factor: float = 3.0
    seed: int = 0                     # root key when run() gets no key
    qaf: qaf.QAFConfig = dataclasses.field(default_factory=qaf.QAFConfig)
    # emit the quantize-once packed NVFP4 serving artifact at the end of
    # the run (<ckpt_dir>/serve_packed) — deploys restore 4-bit weights
    # directly into the Engine and never touch the bf16 training params
    export_packed: bool = True


class Trainer:
    def __init__(self, cfg: ModelConfig, qcfg: fqt.QuantConfig,
                 tcfg: step_mod.TrainConfig, run_cfg: TrainerConfig,
                 data_cfg: DataConfig, mesh=None, tracer=None):
        # quant-health telemetry (obs/trace.py, clock = optimizer step):
        # per-layer √3-floor ratios, E4M3 scale saturation/underflow,
        # rounding-mode tallies — emitted every ``log_every`` steps.  A
        # live tracer turns on the step's per-leaf gradient norms.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if self.tracer.enabled and not tcfg.layer_stats:
            tcfg = dataclasses.replace(tcfg, layer_stats=True)
        self.cfg, self.qcfg, self.tcfg = cfg, qcfg, tcfg
        self.run_cfg, self.data_cfg = run_cfg, data_cfg
        self.mesh = mesh
        self.data = SyntheticLM(data_cfg)
        self.history: List[Dict[str, float]] = []
        self.events: List[Dict[str, Any]] = []
        self.in_qaf = False
        self._stop = False
        self._step_fn = None
        self._leaf_info = None          # [(path, size)] in grad-leaf order

    @property
    def step_fn(self):
        """The jitted train step in use (rebuilt when QAF switches on)."""
        return self._step_fn

    # ---- lifecycle -------------------------------------------------------

    def _install_sigterm(self):
        try:
            signal.signal(signal.SIGTERM, lambda *_: setattr(
                self, "_stop", True))
        except ValueError:
            pass  # not on the main thread (tests)

    def _build_step(self, start_step: int = 0):
        qcfg = qaf.qaf_quant_config(self.qcfg) if self.in_qaf else self.qcfg
        tcfg = self.tcfg
        if self.in_qaf:
            tcfg = dataclasses.replace(
                tcfg, sched=qaf.qaf_lr_schedule(self.tcfg.sched,
                                                self.run_cfg.qaf,
                                                start_step))
        self._step_fn = step_mod.make_train_step(self.cfg, qcfg, tcfg,
                                                 self.mesh)
        if self.mesh is not None:
            self._step_fn = jax.jit(self._step_fn, donate_argnums=(0,))

    def init_or_restore(self, key) -> step_mod.TrainState:
        state = step_mod.init_state(self.cfg, self.tcfg, key)
        if self.run_cfg.ckpt_dir:
            step, restored = ckpt.restore_latest(self.run_cfg.ckpt_dir, state)
            if restored is not None:
                self.events.append({"kind": "restore", "step": int(step)})
                return restored
        return state

    # ---- the loop --------------------------------------------------------

    def run(self, key=None) -> step_mod.TrainState:
        key = key if key is not None else jax.random.PRNGKey(
            self.run_cfg.seed)
        self._install_sigterm()
        state = self.init_or_restore(key)
        self._build_step()
        start_step = int(state.step)
        durations: List[float] = []

        for step in range(start_step, self.run_cfg.total_steps):
            if self._stop:
                self.events.append({"kind": "preempt", "step": step})
                break
            batch = {k: jax.numpy.asarray(v)
                     for k, v in self.data.batch(step).items()}

            t0 = time.perf_counter()
            state, metrics = self._step_fn(state, batch)
            host = jax.device_get(metrics)
            layer_gnorms = host.pop("layer_gnorms", None)
            metrics = {k: float(v) for k, v in host.items()}
            dt = time.perf_counter() - t0

            if (self.tracer.enabled
                    and step % self.run_cfg.log_every == 0):
                self._emit_telemetry(step, metrics, layer_gnorms, state)

            # straggler accounting (skip compile steps: first of each phase)
            if len(durations) >= 5:
                med = float(np.median(durations[-50:]))
                if dt > self.run_cfg.straggler_factor * med:
                    self.events.append({"kind": "straggler", "step": step,
                                        "dt": dt, "median": med})
            durations.append(dt)

            metrics["step"] = step
            metrics["dt"] = dt
            self.history.append(metrics)

            # QAF switch (paper §5): threshold crossing or fixed step
            if not self.in_qaf and qaf.should_switch(
                    step, metrics["thr_crossed"] > 0.5, self.run_cfg.qaf):
                self.in_qaf = True
                self.events.append({"kind": "qaf_switch", "step": step,
                                    "gnr": metrics["gnr"]})
                self._build_step(start_step=step + 1)

            if (self.run_cfg.ckpt_dir
                    and (step + 1) % self.run_cfg.ckpt_every == 0):
                ckpt.save(self.run_cfg.ckpt_dir, step + 1, state,
                          keep=self.run_cfg.keep_ckpts)

        if self.run_cfg.ckpt_dir and (self._stop or True):
            ckpt.save(self.run_cfg.ckpt_dir, int(state.step), state,
                      keep=self.run_cfg.keep_ckpts)
            if self.run_cfg.export_packed:
                self.export_serving_artifact(state)
        return state

    # ---- quant-health telemetry ------------------------------------------

    def _emit_telemetry(self, step: int, metrics: Dict[str, float],
                        layer_gnorms, state) -> None:
        """One trace entry per logged step (clock = optimizer step): the
        paper's §4 health signals, per layer.

          * ``gnr``/``sigma_q`` gauges — the global ‖g‖/(σ_q·√d) EMA and
            the SR-residual noise estimate the step computed;
          * per-layer ``ratio`` gauges + the ``layers_below_sqrt3``
            counter — layers whose OWN gradient signal is under the √3
            floor (the global EMA averages these out; they are the early
            warning the paper's switch rule reacts to);
          * E4M3 block-scale saturation/underflow counters from a probe
            weight quantized with the active forward spec;
          * rounding-mode tallies — how many of the six quantization
            points ran SR vs RtN this step (flips when QAF switches).
        """
        trc = self.tracer
        trc.set_time(step)
        trc.gauge("loss", metrics["loss"])
        trc.gauge("grad_norm", metrics["grad_norm"])
        trc.gauge("sigma_q", metrics["sigma_q"])
        trc.gauge("gnr", metrics["gnr"])
        if metrics["thr_crossed"] > 0.5:
            trc.counter("sqrt3_crossed_steps")

        qcfg = qaf.qaf_quant_config(self.qcfg) if self.in_qaf else self.qcfg
        specs = [getattr(qcfg, p) for p in fqt.POINTS]
        trc.counter("rounding_sr_points",
                    sum(1 for s in specs if s is not None and s.stochastic))
        trc.counter("rounding_rtn_points",
                    sum(1 for s in specs
                        if s is not None and not s.stochastic))

        if layer_gnorms is not None:
            if self._leaf_info is None:
                leaves = jax.tree_util.tree_flatten_with_path(
                    state.params)[0]
                self._leaf_info = [(jax.tree_util.keystr(p), x.size)
                                   for p, x in leaves]
            below = 0
            for (name, size), g in zip(self._leaf_info,
                                       np.asarray(layer_gnorms)):
                r = threshold.layer_ratio(float(g), metrics["sigma_q"],
                                          size)
                trc.gauge(f"ratio{name}", r, track="layers")
                below += r < threshold.SQRT3
            if below:
                trc.counter("layers_below_sqrt3", below)

        spec = qcfg.fwd_w
        if spec is not None:
            probe = None
            for leaf in jax.tree.leaves(state.params):
                if leaf.ndim >= 2 and leaf.shape[-1] % spec.block == 0:
                    if probe is None or leaf.size > probe.size:
                        probe = leaf
            if probe is not None:
                h = quantize.scale_health(probe, spec)
                trc.counter("scale_blocks", h["blocks"])
                trc.counter("scale_saturated", h["saturated"])
                trc.counter("scale_underflow", h["underflow"])

    def export_serving_artifact(self, state) -> Optional[str]:
        """Quantize-once export: pack every GEMM weight with THIS run's
        forward weight spec (its QAF/serving numerics) and checkpoint the
        packed tree under ``<ckpt_dir>/serve_packed`` — 4-bit on disk,
        restored directly into ``serve.Engine(..., pack_weights=False)``
        so deploys never touch the bf16 training weights.  Runs with no
        quantized forward (the bf16 baseline) export nothing: there is no
        packed-serving story for them."""
        if not self.run_cfg.ckpt_dir:
            return None
        spec = qaf.qaf_quant_config(self.qcfg).fwd_w
        if spec is None:
            return None
        from repro.serve.packing import pack_model_params
        packed = pack_model_params(self.cfg, state.params, spec)
        path = ckpt.save(os.path.join(self.run_cfg.ckpt_dir,
                                      "serve_packed"),
                         int(state.step), packed,
                         keep=self.run_cfg.keep_ckpts)
        self.events.append({"kind": "export_packed",
                            "step": int(state.step)})
        return path

    # ---- reporting -------------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        h = self.history
        return {
            "steps": len(h),
            "final_loss": h[-1]["loss"] if h else None,
            "final_gnr": h[-1]["gnr"] if h else None,
            "qaf": self.in_qaf,
            "events": self.events,
        }
