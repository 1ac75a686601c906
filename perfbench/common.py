"""What every cell shares: the manifest, the device, clocks and the result.

The harness is driven by data.  ``BENCHMARK.json`` names each cell's
configuration and traffic; ``configs/<config>.json`` holds the sizes as
run, ``arch/<arch>.py`` the model those sizes describe (the configuration
file's ``"arch"``), ``traffic/<traffic>.json`` the traffic parameters,
``cells/<cell>.json`` the cell's run settings and correctness limits,
``metrics/<metric>.py`` one reader per per-layer metric, and
``peaks.json`` the device peaks.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
OUT = ROOT / ".perfbench_out"          # traces and scratch (git-ignored)
CACHE = ROOT / ".jax_cache"            # the persistent compile cache
ARCH = HERE / "arch"                   # one module per architecture

# What an architecture module provides (arch/dense.py is the example), by
# the traffic kind of the cells that use it.  ``model_of(config)`` gives
# the hashable sizes, with ``vocab`` and ``layers`` among them; every other
# function takes those sizes first.
ARCH_API = {
    "any": ("model_of", "program_config"),
    "train": ("make_params", "reference_params", "seq_loss", "train_flops",
              "train_gemm_flops_executed"),
    "serve": ("pack_params", "make_embed", "make_layer", "serve_layer",
              "serve_head", "prefill_flops", "served_flops",
              "decode_step_bytes"),
}


class NoDevice(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def setup_env() -> None:
    """Environment the run needs before JAX is imported: the compile cache
    at a fixed path in the checkout, and no TPU logs under a fixed /tmp
    path."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_spec(name: str) -> dict:
    """Everything about one cell, by name: its manifest entry, its
    configuration, traffic and run settings, and the metrics it reports."""
    man = manifest()
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]

    def reported(section):
        return [m for m in man[section]
                if name in m.get("workloads", [name])]

    return {"name": name, "chips": w["chips"],
            "config": load_json(ROOT / conf["file"]),
            "traffic": load_json(HERE / "traffic" / f"{w['traffic']}.json"),
            "cell": load_json(HERE / "cells" / f"{name}.json"),
            "end_to_end": reported("end_to_end"),
            "per_layer": reported("per_layer")}


def require_devices(n: int):
    """The accelerator, or exit non-zero with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoDevice(f"perfbench: needs an accelerator, JAX found "
                       f"{devs[0].platform!r}")
    if len(devs) < n:
        raise NoDevice(f"perfbench: the cell needs {n} chips, JAX found "
                       f"{len(devs)}")
    return devs


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes(devs)}


def memory_note(devs) -> None:
    """The allocator's own counters of every chip, on standard error."""
    for d in devs:
        st = d.memory_stats() or {}
        print(f"memory {d.id}: " + ", ".join(
            f"{k} {v}" for k, v in sorted(st.items())), file=sys.stderr,
            flush=True)


def peak_bytes(devs) -> int:
    """The high-water mark of the fullest chip since the process began:
    its buffers at their peak plus the scratch its programs reserved at
    theirs.  A TPU keeps a program's temporaries in a reserved region that
    ``peak_bytes_in_use`` leaves out, so the sum is the bound that counts
    both."""
    def one(d):
        st = d.memory_stats() or {}
        return (int(st.get("peak_bytes_in_use", 0))
                + int(st.get("peak_bytes_reserved", 0)))
    return max(one(d) for d in devs)


def peaks(kind: str) -> dict:
    """Published peaks of the device; an unknown device is an error."""
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"perfbench: no peaks for device {kind!r}; add it "
                         f"to perfbench/peaks.json with its source")
    return table[kind]


class Clock:
    """XLA backend-compile seconds and counts, from JAX's own events (the
    method of chip_smoke.Clock), plus time spent reading the persistent
    cache."""
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_read_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.COMPILE:
            self.compile_s += duration
            self.compiles += 1
        elif event == self.CACHE_READ:
            self.cache_read_s += duration


def arch(config: dict, kind: str = "any"):
    """The architecture module the configuration names, ``arch/<arch>.py``,
    checked for what cells of traffic ``kind`` call."""
    mod = _load_arch(ARCH / f"{config['arch']}.py")
    missing = [n for n in ARCH_API["any"] + ARCH_API[kind]
               if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"perfbench: arch {config['arch']!r} lacks "
                         f"{missing}")
    return mod


@functools.cache
def _load_arch(path: pathlib.Path):
    """One module object per file, so that its jitted functions keep their
    compiled programs across the runs of one process."""
    name = f"perfbench_arch_{path.stem.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The reader of one per-layer metric: ``metrics/<metric>.py``'s
    ``read(ctx)``, which returns a number or None."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(spec: dict, ctx: dict) -> dict:
    out = {}
    for m in spec["per_layer"]:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers with their limits as the last lines of
    standard error, and the result as the last line of standard output,
    with the checks as its last key."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def now() -> float:
    return time.perf_counter()
