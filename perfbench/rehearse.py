"""Compile each cell's programs for a described TPU v5e, with no chip.

  JAX_PLATFORMS=cpu PYTHONPATH=src python3 perfbench/rehearse.py \\
      [--cell <cell>] [--batch <n> ...]

Run by hand, outside the tests: it compiles at the real sizes (minutes,
tens of GB of host memory).  For the training cell it compiles the
program's train step at each batch asked for and prints XLA's memory
analysis, which sets the cell's batch; for a serving cell it compiles the
engine's prefill and decode programs with the packed weights and the
paged cache of the cell's slot count.  Nothing runs, so nothing is timed.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import common  # noqa: E402

GB = 1e9


def describe(ma) -> str:
    return (f"arguments {ma.argument_size_in_bytes / GB:.2f} GB, outputs "
            f"{ma.output_size_in_bytes / GB:.2f} GB, aliased "
            f"{ma.alias_size_in_bytes / GB:.2f} GB, temporaries "
            f"{ma.temp_size_in_bytes / GB:.2f} GB, code "
            f"{ma.generated_code_size_in_bytes / GB:.3f} GB")


def topology():
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return topo.devices[0]


def on(dev, tree):
    import jax
    from jax.sharding import SingleDeviceSharding
    sh = SingleDeviceSharding(dev)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sh), tree)


def train(spec, batches):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import fqt, threshold
    from repro.optim import adamw, schedule
    from repro.train.step import TrainConfig, TrainState, make_train_step
    dev = topology()
    arch = common.arch(spec["config"], "train")
    m = arch.model_of(spec["config"])
    cfg = arch.program_config(spec["config"])
    o = spec["cell"]["optimizer"]
    tcfg = TrainConfig(
        opt=adamw.AdamWConfig(lr_peak=o["lr"], weight_decay=o["weight_decay"],
                              clip_norm=o["clip_norm"]),
        sched=schedule.ScheduleConfig(peak_lr=o["lr"], warmup_steps=0,
                                      total_steps=o["total_steps"]),
        remat=spec["cell"]["remat"])
    params = jax.eval_shape(lambda: arch.make_params(
        m, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda p: TrainState(
        jnp.zeros((), jnp.int32), p, adamw.init(p, tcfg.opt),
        threshold.init()), params)
    # with a mesh the program hands back the step unjitted
    step = make_train_step(cfg, fqt.nvfp4_paper_config(), tcfg,
                           Mesh(np.array([dev]), ("data",)))
    S = spec["traffic"]["seq_len"]
    for b in batches:
        batch = {"tokens": jax.ShapeDtypeStruct((b, S + 1), jnp.int32)}
        c = jax.jit(step, donate_argnums=0).lower(
            on(dev, state), on(dev, batch)).compile()
        print(f"train batch {b} x {S}: {describe(c.memory_analysis())}",
              flush=True)


def serve(spec):
    import jax
    import jax.numpy as jnp
    from repro.core import fqt
    from repro.distributed import sharding as shd
    from repro.models import registry
    from repro.serve import ContinuousEngine
    from repro.serve.engine import ServeConfig
    dev = topology()
    arch = common.arch(spec["config"], "serve")
    m = arch.model_of(spec["config"])
    cfg = arch.program_config(spec["config"])
    e, t = spec["cell"]["engine"], spec["traffic"]
    pad = -(-t["prompt"]["max"] // e["page_size"]) * e["page_size"]
    qcfg = fqt.qaf_config()
    params = jax.eval_shape(lambda k: arch.pack_params(
        m, k, qcfg.fwd_w), jax.random.PRNGKey(0))
    scfg = ServeConfig(batch_size=e["slots"], max_len=e["max_len"],
                       kv_cache_format=e["kv_format"],
                       page_size=e["page_size"], max_slots=e["slots"],
                       prefill_len=pad, decode_chunk=e["decode_chunk"])
    # an engine shell holding what the two programs read, on the
    # described device (the real constructor places weights on a device)
    eng = object.__new__(ContinuousEngine)
    eng.cfg, eng.scfg, eng.qcfg = cfg, scfg, qcfg
    eng.mesh = shd.make_serve_mesh(None, devices=[dev])
    eng._rep = jax.sharding.NamedSharding(eng.mesh,
                                          jax.sharding.PartitionSpec())
    eng._root = jax.random.PRNGKey(0)
    slots = e["slots"]
    n_pages = e["max_len"] // e["page_size"]
    carry = jax.eval_shape(lambda: registry.make_decode_state(
        cfg, slots, e["max_len"], kv_cache_format=e["kv_format"],
        page_size=e["page_size"], total_pages=1 + slots * n_pages))
    i32 = jnp.int32
    sc = lambda shape, dt=i32: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    dec = jax.jit(eng._decode_impl, donate_argnums=(2,)).lower(
        on(dev, params), on(dev, sc((slots,))), on(dev, carry),
        on(dev, sc((slots,))), on(dev, sc((slots,))),
        on(dev, sc((slots,), jnp.bool_))).compile()
    print(f"serve decode, {slots} slots x {e['max_len']}: "
          f"{describe(dec.memory_analysis())}", flush=True)
    pre = jax.jit(eng._prefill_impl, donate_argnums=(5,)).lower(
        on(dev, params), on(dev, sc((1, pad))), on(dev, sc(())),
        on(dev, sc(())), on(dev, sc(())), on(dev, carry), {}).compile()
    print(f"serve prefill, pad {pad}: {describe(pre.memory_analysis())}",
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", action="append")
    ap.add_argument("--batch", type=int, action="append")
    args = ap.parse_args(argv)
    common.setup_env()
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    names = args.cell or [w["name"] for w in common.manifest()["workloads"]]
    for name in names:
        spec = common.cell_spec(name)
        if spec["traffic"]["kind"] == "train":
            train(spec, args.batch or [spec["traffic"]["batch"]])
        else:
            serve(spec)


if __name__ == "__main__":
    main()
