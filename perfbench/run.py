"""Run one benchmark cell once on the accelerator this process finds.

  python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

The cell is looked up by name in BENCHMARK.json; its configuration,
traffic and settings come from files under perfbench/ (see common.py).
With --trace 0 the result carries the cell's end-to-end metrics, with
--trace 1 its per-layer metrics, read from a profiler trace of the window
and the program's own counters.  The last line of standard output is the
result as one JSON object; without an accelerator, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax():
    import jax
    jax.config.update("jax_compilation_cache_dir", str(common.CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def add_trace(ctx: dict, path) -> None:
    """What the per-layer readers read of the window's trace file: op kinds
    and programs (``ctx["trace"]``), device seconds by the program's scopes
    and idle seconds by its host phases (``ctx["scopes"]``)."""
    import scopes
    import xplane
    ctx["trace"] = xplane.reduce(path)
    ctx["scopes"] = scopes.reduce(path)


def readings(detail: dict) -> dict:
    """Every reading of the correctness check, compared or not."""
    keep = ("readings", "leaves", "stats", "reference_s")
    return {k: detail[k] for k in keep if k in detail}


def run_cell(args):
    """One run; returns (result, checks, detail)."""
    spec = common.cell_spec(args.workload)
    kind = spec["traffic"]["kind"]
    if kind == "train":
        import train_cell
        out, e2e, ctx, checks, detail, tdir = train_cell.run(
            spec, args.seed, args.seconds, bool(args.trace), T_START)
    elif kind == "serve":
        import serve_cell
        out, e2e, ctx, checks, detail, tdir, _ = serve_cell.run(
            spec, args.seed, args.seconds, bool(args.trace), T_START)
    else:
        raise SystemExit(f"perfbench: unknown traffic kind {kind!r}")
    if args.trace:
        import scopes
        import xplane
        t = common.now()
        add_trace(ctx, xplane.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        red, sc = ctx["trace"], ctx["scopes"]
        # device seconds by scope in each program that holds 1% of busy
        detail["scopes"] = {
            "by_scope": {p: scopes.by_scope(sc, p) for p, v in
                         sc.get("module_s", {}).items()
                         if v >= 0.01 * sc["busy_s"]},
            "gap_s": sc.get("gap_s"), "parse_s": common.now() - t}
        out["metrics"] = common.read_per_layer(spec, ctx)
        out["device"]["busy_s"] = red.get("busy_s", 0.0)
        out["device"]["window_s"] = ctx["window_s"]
        out["breakdown"] = xplane.breakdown(red)
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        out["metrics"] = {k: {"value": float(e2e[k]), "unit": units[k]}
                          for k in units if k in e2e}
    return out, checks, detail


def main(argv=None) -> int:
    args = parse(argv)
    common.setup_env()
    configure_jax()
    out, checks, detail = run_cell(args)
    print("readings " + json.dumps(readings(detail)), file=sys.stderr,
          flush=True)
    if "scopes" in detail:
        print("scopes " + json.dumps(detail["scopes"]), file=sys.stderr,
              flush=True)
    import jax
    common.memory_note(jax.devices())
    common.emit(out, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
