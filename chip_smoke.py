"""Start FQT training and packed-NVFP4 serving on a TPU, at full widths.

  python chip_smoke.py                # phases A, B, C on one chip
  python chip_smoke.py --four-chips   # phase D alone, on a four-chip host

  A  FQT training, jnp path: llama2-350m (24 layers, d_model 1024, d_ff
     2816, vocab 32000) built as ``python -m repro.launch.train --quant
     nvfp4`` builds it, 5 steps of 8 x 1024 tokens from random weights
  B  the same with ``--quant nvfp4_pallas``: the fused quantize+matmul
     Pallas kernel at all six FQT points, with no jnp or bf16 fallback
  C  serving: tinyllama-1.1b (22 layers, d_model 2048, 4 KV heads, d_ff
     5632) through ``ContinuousEngine`` as ``launch/serve.py --queue``
     builds it, with packed NVFP4 weights and the paged nvfp4 KV cache;
     the packed engine's greedy tokens must equal the unpacked engine's
  D  tinyllama-1.1b under ``--mesh tp=4`` (one KV head per chip); its
     tokens must equal the same requests served on one device

Each phase prints its compile and wall times and the device's peak memory
(``peak_bytes_in_use``, the high-water mark since the process started).
A failed check raises, so the script exits non-zero.  The script refuses
to run anywhere but a TPU.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

import jax
import numpy as np

TRAIN_ARGS = ["--arch", "llama2-350m", "--steps", "5", "--batch", "8",
              "--seq", "1024", "--seed", "0"]
SERVE_ARGS = ["--arch", "tinyllama-1.1b", "--queue", "16",
              "--prompt-len", "128", "--max-new", "32", "--max-slots", "8",
              "--max-len", "512", "--seed", "0"]
# Step-0 loss, jnp path against Pallas path.  Both quantize identical
# operands (RtN forward), and the kernel's GEMM agrees with XLA's dot to
# rtol/atol 2e-5 (tests/test_kernels.py: accumulation order only).  Each
# GEMM output is then rounded to bf16 (2^-8 relative) before the next FP4
# requantization, so that gap flips a few bf16 roundings and FP4 codes per
# layer; over a loss averaged on 8192 tokens their effects are of mixed
# sign.  Allowed: a quarter of one bf16 ulp of the loss.  (The gap measured
# on the CPU at llama2-350m.smoke(), 1024 tokens, was 8.4e-5 relative.)
LOSS_RTOL = 2.0 ** -10
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
GB = 1e9


class Clock:
    """XLA backend-compile seconds and counts, from JAX's own events."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compile_s += duration
            self.compiles += 1

    def mark(self):
        return time.perf_counter(), self.compile_s, self.compiles

    def since(self, mark):
        t, c, n = mark
        return time.perf_counter() - t, self.compile_s - c, self.compiles - n


def _peak_gb() -> float:
    return max(d.memory_stats()["peak_bytes_in_use"]
               for d in jax.devices()) / GB


def _report(phase: str, clock: Clock, mark, extra: str = "") -> None:
    wall, comp, n = clock.since(mark)
    print(f"[{phase}] wall {wall:.1f} s, XLA compile {comp:.1f} s "
          f"({n} programs), peak device memory {_peak_gb():.2f} GB"
          + (f"; {extra}" if extra else ""), flush=True)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


# ---- A/B: FQT training ------------------------------------------------------


def _sub_jaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(getattr(x, "jaxpr", None), "eqns"):
                yield x.jaxpr


def gemm_census(closed_jaxpr, weight_shapes):
    """(Pallas calls, their (a, b) operand shapes, jnp dots on a weight).

    A quantized GEMM that fell back to the jnp path (a dimension not
    divisible by the block) shows up as a ``dot_general`` with a 2-D
    operand or result of a weight's shape or its transpose; the attention
    dots are batched, so they never match.  Kernel bodies are not entered.
    """
    shapes = set(weight_shapes) | {s[::-1] for s in weight_shapes}
    calls, operands, fallbacks = 0, set(), 0
    stack = [closed_jaxpr.jaxpr]
    while stack:
        for eqn in stack.pop().eqns:
            if eqn.primitive.name == "pallas_call":
                calls += 1
                operands.add(tuple(tuple(v.aval.shape)
                                   for v in eqn.invars[:2]))
                continue
            if eqn.primitive.name == "dot_general":
                avals = [v.aval for v in (*eqn.invars, *eqn.outvars)]
                fallbacks += any(tuple(a.shape) in shapes for a in avals)
            stack.extend(_sub_jaxprs(eqn.params))
    return calls, operands, fallbacks


def _weight_shapes(params):
    """(K, N) of every GEMM weight: the stacked layer matrices + lm_head."""
    out = {tuple(x.shape[1:]) for x in jax.tree.leaves(params["layers"])
           if x.ndim == 3}
    if "lm_head" in params:
        out.add(tuple(params["lm_head"].shape))
    return out


def train_phase(phase: str, quant: str, clock: Clock):
    from repro.launch import train

    args = train.parse_args(TRAIN_ARGS + ["--quant", quant])
    trainer = train.build_trainer(args)
    cfg = trainer.cfg
    print(f"[{phase}] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
          f"--quant {quant}, batch {args.batch} x seq {args.seq}, "
          f"remat {trainer.tcfg.remat}", flush=True)
    mark = clock.mark()
    state = trainer.run(jax.random.PRNGKey(args.seed))
    hist = trainer.history
    for h in hist:
        print(f"[{phase}] step {h['step']}  loss {h['loss']:.6f}  "
              f"{h['dt']:.3f} s", flush=True)
    losses = [h["loss"] for h in hist]
    _check(len(hist) == args.steps and all(map(math.isfinite, losses)),
           f"{phase}: losses {losses}")
    later = [h["dt"] for h in hist[1:]]
    _report(phase, clock, mark,
            f"step 0 {hist[0]['dt']:.2f} s incl. compile, steps 1-"
            f"{len(later)} mean {np.mean(later):.3f} s")
    batch = {k: jax.numpy.asarray(v)
             for k, v in trainer.data.batch(0).items()}
    return trainer, state, batch, losses


def check_pallas_step(trainer, state, batch) -> None:
    """Phase B's step runs the fused kernel at every FQT point."""
    weights = _weight_shapes(state.params)
    calls, operands, fallbacks = gemm_census(
        jax.make_jaxpr(trainer.step_fn)(state, batch), weights)
    M = trainer.data_cfg.global_batch * trainer.data_cfg.seq_len   # tokens
    want = set()
    for K, N in weights:
        want |= {((M, K), (K, N)),          # forward: x @ W
                 ((M, N), (N, K)),          # backward: g @ W^T
                 ((K, M), (M, N))}          # update: x^T @ g
    hlo = trainer.step_fn.lower(state, batch).compile().as_text()
    print(f"[B] {calls} Pallas calls in the step's jaxpr over "
          f"{len(operands)} GEMM shapes; {fallbacks} jnp dots on a weight "
          f"shape; tpu_custom_call in compiled HLO: "
          f"{'tpu_custom_call' in hlo}", flush=True)
    _check("tpu_custom_call" in hlo, "B: no Pallas kernel in compiled step")
    _check(fallbacks == 0, f"B: {fallbacks} GEMMs fell back to jnp")
    _check(want <= operands,
           f"B: GEMMs without a kernel: {sorted(want - operands)}")


# ---- C/D: serving -----------------------------------------------------------


def _serve_setup(extra_args=()):
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models import registry

    args = serve.parse_args(SERVE_ARGS + list(extra_args))
    cfg = get_config(args.arch)
    params = registry.init_params(cfg, jax.random.PRNGKey(args.seed))
    reqs = serve.queued_requests(cfg, args, np.random.default_rng(0))
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads / {cfg.n_kv_heads} KV heads, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; {len(reqs)} requests, "
          f"prompt {args.prompt_len}, max_new {args.max_new}, max_slots "
          f"{args.max_slots}, max_len {args.max_len}", flush=True)
    return cfg, params, serve.serve_config(args), reqs


def serve_run(phase: str, eng, reqs, clock: Clock):
    mark = clock.mark()
    res = eng.run(reqs)
    ntok = sum(len(v) for v in res.values())
    _report(phase, clock, mark,
            f"{ntok} tokens for {len(res)} requests; engine compiles: "
            f"prefill {eng.prefill_compiles}, decode {eng.decode_compiles}")
    _check(len(res) == len(reqs), f"{phase}: {len(res)} of {len(reqs)} "
           f"requests completed")
    return res


def check_same_tokens(phase: str, eng, reqs, got, want, want_margins):
    """``got`` must equal ``want`` token for token.  On a mismatch the
    engine is rerun teacher-forced on ``want`` (the repo's tool for near-
    tied logits), so the failure says whether only near-ties flipped."""
    bad = [r for r in want if not np.array_equal(got[r], want[r])]
    print(f"[{phase}] token streams identical: {not bad} "
          f"({len(want)} requests)", flush=True)
    if not bad:
        return
    forced = eng.run(reqs, forced=want)
    for rid in bad[:4]:
        n = min(len(forced[rid]), len(want[rid]))
        neq = np.flatnonzero(np.asarray(forced[rid][:n]) != want[rid][:n])
        print(f"[{phase}] rid {rid}: teacher-forced picks differ at "
              f"{neq.tolist()}; reference margins there "
              f"{np.asarray(want_margins[rid])[neq].tolist()}", flush=True)
    raise RuntimeError(f"{phase}: token streams differ for requests {bad}")


def serve_phase(clock: Clock) -> None:
    from repro.serve import ContinuousEngine

    cfg, params, scfg, reqs = _serve_setup()
    ref = ContinuousEngine(cfg, params, scfg, pack_weights=False)
    want = serve_run("C unpacked", ref, reqs, clock)
    margins = ref.margins
    del ref
    gc.collect()
    eng = ContinuousEngine(cfg, params, scfg)
    got = serve_run("C packed", eng, reqs, clock)
    check_same_tokens("C", eng, reqs, got, want, margins)
    warm = serve_run("C packed, warm rerun", eng, reqs, clock)
    _check(eng.prefill_compiles == 1 and eng.decode_compiles == 1,
           "C: the warm rerun compiled again")
    check_same_tokens("C warm", eng, reqs, warm, want, margins)


def mesh_phase(clock: Clock) -> None:
    from repro.serve import ContinuousEngine

    cfg, params, scfg, reqs = _serve_setup(["--mesh", "tp=4"])
    one = ContinuousEngine(cfg, params, dataclasses.replace(scfg, mesh=None))
    want = serve_run("D 1 device", one, reqs, clock)
    margins = one.margins
    del one
    gc.collect()
    eng = ContinuousEngine(cfg, params, scfg)
    print(f"[D] mesh {dict(zip(eng.mesh.axis_names, eng.mesh.devices.shape))}"
          f" over {eng.mesh.devices.size} devices", flush=True)
    got = serve_run("D tp=4", eng, reqs, clock)
    check_same_tokens("D", eng, reqs, got, want, margins)


# ---- driver -----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run phase D (tinyllama-1.1b, tp=4 against one "
                         "device) and nothing else")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {dev.platform!r}")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        sys.exit(f"chip_smoke: needs {need} chips, found {len(devices)}")

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {dev.device_kind} x {len(devices)}, jax "
          f"{jax.__version__}, compile cache {enable_compile_cache()}",
          flush=True)
    clock = Clock()
    if args.four_chips:
        mesh_phase(clock)
    else:
        _, _, _, loss_a = train_phase("A", "nvfp4", clock)
        gc.collect()
        trainer, state, batch, loss_b = train_phase("B", "nvfp4_pallas",
                                                    clock)
        check_pallas_step(trainer, state, batch)
        rel = abs(loss_b[0] - loss_a[0]) / abs(loss_a[0])
        print(f"[B] step-0 loss {loss_b[0]:.6f} vs A {loss_a[0]:.6f}: "
              f"relative gap {rel:.3e} (allowed {LOSS_RTOL:.3e})",
              flush=True)
        _check(rel <= LOSS_RTOL, "B: step-0 loss differs from A")
        del trainer, state, batch
        gc.collect()
        serve_phase(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
