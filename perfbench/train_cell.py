"""A training cell: the program's jitted FQT train step, repeated.

Set-up makes the weights on the device from the seed (in the layout of
the configuration's architecture module, ``arch/<arch>.py``), builds the
program's step (``train/step.py:make_train_step``) and its state, and
drives that one object through its first three steps on batches 0-2 of
the program's synthetic stream (``data/pipeline.py``), recording what the
correctness check compares: each step's loss, the first gradient as the
optimizer holds it (its first moment over 1 - beta1) and the master
weights' change after the three.  The window then keeps stepping the
same state on new batches, one step in flight while the host makes the
next batch, and is closed by blocking on the last step.  Once the window
is read and the program's state freed, the plain reference runs the same
three steps from the same weights and batches.
"""
from __future__ import annotations

import gc
import math
from functools import partial

import numpy as np

import common
import reference as ref
import weights

B1 = 0.9


def leaf_name(path) -> str:
    """A leaf by its outermost and innermost key (``layers.<leaf>``), the
    same in the program's tree and the reference's view of it."""
    keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
    return ".".join([keys[0], keys[-1]] if len(keys) > 1 else keys)


def named(tree) -> dict:
    import jax
    return {leaf_name(p): float(v)
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def gaps(prog: dict, refv: dict, keep) -> tuple:
    """Worst leaf's |‖prog‖ - ‖ref‖| over max(‖ref‖, median leaf ‖ref‖)."""
    med = float(np.median([refv[k] for k in keep]))
    worst, name = 0.0, None
    for k in keep:
        g = abs(prog[k] - refv[k]) / max(refv[k], med)
        if g > worst:
            worst, name = g, k
    return worst, name


def run(spec: dict, seed: int, seconds: float, trace: bool, t_start: float,
        *, fault: str = "none", quant: str | None = None,
        check_device: bool = True) -> tuple:
    import jax
    import jax.numpy as jnp
    from repro.core import fqt, threshold
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import registry
    from repro.optim import adamw, schedule
    from repro.train.step import TrainConfig, TrainState, make_train_step

    devs = common.require_devices(spec["chips"]) if check_device \
        else jax.devices()
    clock = common.Clock()
    cell, traffic = spec["cell"], spec["traffic"]
    arch = common.arch(spec["config"], "train")
    m = arch.model_of(spec["config"])
    cfg = arch.program_config(spec["config"])
    B, S = traffic["batch"], traffic["seq_len"]
    o = cell["optimizer"]
    quant = quant or cell["quant"]
    qcfg = {"nvfp4": fqt.nvfp4_paper_config, "mxfp4": fqt.mxfp4_config}[
        quant]()
    tcfg = TrainConfig(
        opt=adamw.AdamWConfig(lr_peak=o["lr"], betas=(B1, o["beta2"]),
                              eps=o["eps"], weight_decay=o["weight_decay"],
                              clip_norm=o["clip_norm"]),
        sched=schedule.ScheduleConfig(peak_lr=o["lr"],
                                      warmup_steps=o["warmup_steps"],
                                      total_steps=o["total_steps"]),
        remat=cell["remat"])

    key = weights.root_key(seed)
    params = arch.make_params(m, key)
    want = jax.eval_shape(lambda k: registry.init_params(cfg, k), key)
    if jax.tree.structure(want) != jax.tree.structure(params) or any(
            a.shape != b.shape or a.dtype != b.dtype for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(params))):
        raise SystemExit("perfbench: weight layout differs from the "
                         "program's parameter tree")
    state = TrainState(jnp.zeros((), jnp.int32), params,
                       adamw.init(params, tcfg.opt), threshold.init())
    del params
    step_fn = make_train_step(cfg, qcfg, tcfg)
    data = SyntheticLM(DataConfig(vocab_size=m.vocab, seq_len=S,
                                  global_batch=B, seed=seed))

    def feed(i):
        toks = data.batch(i)["tokens"]
        if fault == "half_batch":          # planted fault: half the rows
            toks = toks[: B // 2]
        return {"tokens": jax.device_put(toks, devs[0])}

    if fault == "frozen":                  # planted fault: state unchanged
        # with a mesh the program returns the step unjitted, so it can be
        # jitted here without donating the state it hands back
        inner = jax.jit(make_train_step(cfg, qcfg, tcfg, jax.sharding.Mesh(
            np.array(devs[:1]), ("data",))))

        def step(st, b):
            return st, inner(st, b)[1]
    else:
        step = step_fn

    leaf_norms = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), t))
    change_norms = jax.jit(lambda master, k: leaf_norms(jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), master,
        arch.make_params(m, k))))

    # set-up: the first three steps, read for the check
    n_check = 3
    losses = []
    for i in range(n_check):
        state, met = step(state, feed(i))
        losses.append(float(met["loss"]))
        if i == 0:
            g_prog = {k: v / (1.0 - B1) for k, v in
                      named(leaf_norms(state.opt.m)).items()}
    d_prog = named(change_norms(state.opt.master, key))
    nxt = feed(n_check)
    jax.block_until_ready((state, nxt))
    setup_s = common.now() - t_start
    compile_setup = (clock.compile_s, clock.compiles, clock.cache_read_s)

    # the window
    if trace:
        common.OUT.mkdir(exist_ok=True)
        tdir = common.OUT / f"trace-{spec['name']}"
        jax.profiler.start_trace(str(tdir))
    n_compiles = clock.compiles
    t0 = common.now()
    i, win_losses = n_check, []
    while True:
        state, met = step(state, nxt)
        nxt = feed(i + 1)
        win_losses.append(met["loss"])
        jax.block_until_ready(met["loss"])
        i += 1
        if common.now() - t0 >= seconds:
            break
    jax.block_until_ready(state)
    window_s = common.now() - t0
    if trace:
        jax.profiler.stop_trace()
    if clock.compiles != n_compiles:
        raise SystemExit(f"perfbench: {clock.compiles - n_compiles} "
                         f"compiles inside the window")
    steps = len(win_losses)
    win_losses = [float(x) for x in win_losses]
    failed = sum(1 for x in win_losses if not math.isfinite(x))
    dev = common.device_info(devs)
    del state, met, nxt, step, step_fn
    gc.collect()

    # the reference: three steps from the same weights and batches
    t_ref = common.now()
    ref_params = arch.reference_params(
        jax.device_get(arch.make_params(m, key)))
    batches = [data.batch(j)["tokens"] for j in range(n_check)]
    adam = ref.Adam(lr=o["lr"], b1=B1, b2=o["beta2"], eps=o["eps"],
                    wd=o["weight_decay"], clip=o["clip_norm"])
    if o["warmup_steps"] != 0:
        raise SystemExit("perfbench: the reference runs a constant rate; "
                         "set warmup_steps 0")
    r_losses, r_g, r_graw, r_d = ref.train_reference(
        partial(arch.seq_loss, m, cell["quant"]), adam, ref_params, batches,
        jax.random.fold_in(key, 0x5EED))
    del ref_params
    reference_s = common.now() - t_ref
    r_g, r_graw, r_d = named(r_g), named(r_graw), named(r_d)
    med_raw = float(np.median(list(r_graw.values())))
    keep = sorted(k for k in r_graw if r_graw[k] >= 1e-3 * med_raw)
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, r_losses))
    g_gap, g_leaf = gaps(g_prog, r_g, keep)
    d_gap, d_leaf = gaps(d_prog, r_d, keep)
    readings = {
        "loss_gap": {"value": loss_gap},
        "grad_norm_gap": {"value": g_gap, "leaf": g_leaf},
        "change_norm_gap": {"value": d_gap, "leaf": d_leaf},
    }
    # only numbers with a limit are compared (PERF.md: the loss gap has no
    # reading of the control or a fault that sets one)
    checks = {k: dict(readings[k], limit=v)
              for k, v in cell["limits"].items()}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    detail = {"readings": {k: r["value"] for k, r in readings.items()},
              "leaves": {"grad_norm_gap": g_leaf, "change_norm_gap": d_leaf},
              "losses": losses, "ref_losses": r_losses,
              "excluded": sorted(set(r_graw) - set(keep)),
              "reference_s": reference_s}

    tokens = steps * B * S
    ctx = {"arch": arch, "model": m, "batch": B, "seq": S,
           "remat": cell["remat"], "window_s": window_s, "steps": steps,
           "compile": compile_setup,
           "peaks": common.peaks(dev["kind"]) if trace else None}
    e2e = {"train_tokens_per_s": tokens / window_s, "setup_s": setup_s}
    out = {"correct": bool(correct), "attempted": steps, "failed": failed,
           "device": dev}
    return out, e2e, ctx, checks, detail, (tdir if trace else None)

