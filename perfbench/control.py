"""Readings that set a cell's correctness limits, on the chip, in one
process.

  python3 perfbench/control.py --workload <cell> --seeds 1 2 3 1 2 3 \\
      [--more-seeds 7 8] [--control-seeds 4 5 6] [--seconds 30] \\
      [--control-seconds 2] [--out readings.json]

For each seed it runs the cell as the benchmark does and records the
numbers the check reads: the lower readings.  For the control seeds
it records what the control gives (the upper readings):

  * training: the program with its MXFP4 path switched on (the next
    precision below the configuration's NVFP4), and the planted fault of
    half of the batch left out (the mean over the rest); a step that hands
    back its state unchanged reads 1 and needs no run;
  * serving: the reference in MXFP4 picks its own best token at each
    served position, and those tokens go through the run's own reading
    and verdict in the served tokens' place (the served tokens' readings
    come from the same runs).  The planted fault of one slot's tokens
    altered is read in perfbench/tests at a size the CPU holds.

The benchmark's own runs never run this.  Limits go into
cells/<cell>.json by hand, between the two readings (PERF.md gives both).
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import common  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="window of the sound runs of --seeds")
    ap.add_argument("--more-seeds", type=int, nargs="*", default=[],
                    help="further sound runs, with --control-seconds")
    ap.add_argument("--control-seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    common.setup_env()
    import run
    run.configure_jax()
    spec = common.cell_spec(args.workload)
    kind = spec["traffic"]["kind"]
    rows = []

    def record(row):
        rows.append(row)
        print(json.dumps(row, default=str), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rows, f, default=str)

    if kind == "train":
        import train_cell
        long, short = args.seconds, args.control_seconds
        plan = [(s, "none", None, long) for s in args.seeds]
        plan += [(s, "none", None, short) for s in args.more_seeds]
        plan += [(s, "none", "mxfp4", short) for s in args.control_seeds]
        plan += [(s, "half_batch", None, short) for s in args.control_seeds]
        for seed, fault, quant, secs in plan:
            t = time.perf_counter()
            out, e2e, _, checks, detail, _ = train_cell.run(
                spec, seed, secs, False, t, fault=fault, quant=quant)
            record({"seed": seed, "fault": fault, "quant": quant or "nvfp4",
                    "seconds_window": secs,
                    "correct": out["correct"],
                    "checks": detail["readings"],
                    "leaves": detail["leaves"],
                    "losses": detail["losses"],
                    "ref_losses": detail["ref_losses"],
                    "excluded": detail["excluded"], "e2e": e2e,
                    "reference_s": detail["reference_s"],
                    "peak": out["device"]["memory_peak_bytes"],
                    "seconds": time.perf_counter() - t})
            gc.collect()
    else:
        import serve_cell
        long, short = args.seconds, args.control_seconds
        plan = [(s, "none", short) for s in dict.fromkeys(
            args.control_seeds + args.more_seeds)]
        plan += [(s, "none", long) for s in args.seeds]
        for seed, fault, secs in plan:
            t = time.perf_counter()
            out, e2e, ctx, checks, detail, _, (key, seqs, logits) = \
                serve_cell.run(spec, seed, secs, False, t, fault=fault)
            row = {"seed": seed, "fault": fault, "correct": out["correct"],
                   "seconds_window": secs, "window_s": ctx["window_s"],
                   "stats": detail["stats"], "e2e": e2e,
                   "peak": out["device"]["memory_peak_bytes"],
                   "ticks": detail["ticks"], "t_tick": detail["t_tick"],
                   "reference_s": detail["reference_s"]}
            if fault == "none" and seed in args.control_seeds \
                    and secs == short:
                ctl = serve_cell.control_stats(ctx["arch"], ctx["model"],
                                               key, seqs, logits)
                row["control"] = ctl
                row["control_correct"] = serve_cell.judge(
                    ctl, spec["cell"]["limits"])[1]
            row["seconds"] = time.perf_counter() - t
            record(row)
            del logits, seqs
            gc.collect()
    import jax
    common.memory_note(jax.devices())


if __name__ == "__main__":
    main()
