"""Training launcher.

  python -m repro.launch.train --arch tinyllama-1.1b --smoke \\
      --steps 100 --quant nvfp4 --ckpt-dir /tmp/run1

On a real TPU cluster the same entry point runs under
``jax.distributed.initialize()`` with the production mesh; on this host it
runs the reduced config on the local device mesh.  Restart the same command
after a kill and it resumes from the latest checkpoint (the data pipeline
is step-indexed, so the token stream continues exactly).
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs import get_config
from repro.core import fqt, qaf
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw, schedule
from repro.train import TrainConfig, Trainer, TrainerConfig


QUANT = {
    "nvfp4": fqt.nvfp4_paper_config,
    "mxfp4": fqt.mxfp4_config,
    "bf16": fqt.bf16_config,
    "qaf": fqt.qaf_config,
    "nvfp4_pallas": lambda: fqt.nvfp4_paper_config(impl="pallas"),
}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-350m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--quant", default="nvfp4", choices=sorted(QUANT))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=40)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--qaf-at", type=int, default=0,
                    help=">0: fixed-step QAF switch; 0: √3-threshold auto")
    ap.add_argument("--no-qaf", action="store_true")
    ap.add_argument("--log-json", default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="root PRNG seed (init + data stream)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome-trace-event JSON of quant-health "
                         "telemetry (optimizer-step clock: per-layer "
                         "√3-floor ratios, E4M3 scale saturation/underflow, "
                         "SR/RtN rounding tallies, one entry per log_every "
                         "steps) to PATH — open in Perfetto")
    return ap.parse_args(argv)


def build_trainer(args: argparse.Namespace) -> Trainer:
    """The Trainer that ``main`` runs, built from parsed arguments."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()

    tcfg = TrainConfig(
        opt=adamw.AdamWConfig(lr_peak=args.lr),
        sched=schedule.ScheduleConfig(peak_lr=args.lr,
                                      warmup_steps=args.warmup,
                                      total_steps=args.steps),
        remat=not args.smoke,
    )
    run_cfg = TrainerConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        qaf=qaf.QAFConfig(enabled=not args.no_qaf,
                          auto_switch=args.qaf_at == 0,
                          fixed_switch_step=args.qaf_at),
    )
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer(clock="step", process="train")

    return Trainer(cfg, QUANT[args.quant](), tcfg, run_cfg, data_cfg,
                   tracer=tracer)


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    trainer = build_trainer(args)
    run_cfg, tracer = trainer.run_cfg, trainer.tracer
    trainer.run(jax.random.PRNGKey(args.seed))
    if args.trace:
        tracer.export(args.trace)
        print(f"trace: {tracer.n_events} events "
              f"(clock=step, every {run_cfg.log_every} steps) -> "
              f"{args.trace} (open in Perfetto: ui.perfetto.dev)")

    for h in trainer.history[:: max(1, len(trainer.history) // 20)]:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"gnr {h['gnr']:.2f}  lr {h['lr']:.2e}  dt {h['dt']*1e3:.0f}ms")
    print("summary:", json.dumps(trainer.summary(), default=str)[:2000])
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump({"history": trainer.history,
                       "events": trainer.events}, f)


if __name__ == "__main__":
    main()
