"""Serving launcher: batched generation with the FP4 forward path.

  python -m repro.launch.serve --arch tinyllama-1.1b --smoke \\
      --batch 4 --max-new 32

  # continuous batching: 12 queued requests over 4 slots, 16-token pages
  python -m repro.launch.serve --arch tinyllama-1.1b --smoke \\
      --queue 12 --max-slots 4 --page-size 16

Initializes (or restores ``--ckpt-dir``) parameters, builds the Engine and
runs synthetic prompts through prefill + decode, reporting tokens/s.  With
``--queue`` the ContinuousEngine serves a staggered arrival trace through
the scheduler (admission queue, paged NVFP4 KV cache, slot reuse); without
it the lockstep Engine serves one static batch.  The forward GEMMs run in
NVFP4 RtN — the exact deployed numeric path the paper's QAF phase
preserves.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.configs import get_config
from repro.core import fqt
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.serve import ContinuousEngine, Engine, Request, ServeConfig


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-cache-format", default=None,
                    choices=("bf16", "nvfp4", "fp8"),
                    help="KV cache storage (nvfp4: 0.5625 bytes/elem; "
                         "bf16: unquantized escape hatch).  Default: nvfp4, "
                         "or bf16 when --bf16 is set")
    ap.add_argument("--bf16", action="store_true",
                    help="serve in bf16 instead of FP4 forward (also "
                         "defaults the KV cache to bf16)")
    ap.add_argument("--queue", type=int, default=0,
                    help="serve N queued requests through the continuous-"
                         "batching engine (staggered synthetic arrivals); "
                         "0 = lockstep batch")
    ap.add_argument("--max-slots", type=int, default=None,
                    help="continuous engine decode slots (default: --batch)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged cache pool)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: admit long prompts N tokens "
                         "per scheduler tick, interleaved with decode "
                         "(bit-exact; dense/moe, non-SWA; implies "
                         "--queue).  Default: full prefill at admission")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="exact shared-prefix cache: admissions that share "
                         "cached full-page prompt prefixes point at the "
                         "shared physical pages and prefill only the "
                         "suffix (dense/moe, non-SWA; implies --queue)")
    ap.add_argument("--prefix-cache-pages", type=int, default=None,
                    help="cap on cached prefix pages (LRU-evicted; "
                         "default: bounded by pool pressure only)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="with --queue: give every synthetic request the "
                         "same N-token system prompt (exercises the "
                         "prefix cache)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative decoding: a shallow self-draft "
                         "(the first --draft-layers layers of the SAME "
                         "packed weights) proposes tokens, one verify "
                         "pass accepts the longest greedy-agreeing "
                         "prefix + 1 — bit-exact vs sequential decode "
                         "(greedy, dense/moe, non-SWA; implies --queue)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="with --spec-decode: verify block size (the "
                         "draft proposes k-1 tokens; 1..k committed "
                         "per slot per tick)")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="with --spec-decode: self-draft depth in layers "
                         "(default: n_layers // 2)")
    ap.add_argument("--mesh", default=None,
                    help="serving mesh spec, e.g. 'tp=2' or 'dp=2,tp=4': "
                         "packed weights and KV page pools are sharded "
                         "under an explicit device mesh (tp -> 'model' "
                         "shards heads/hidden/vocab, dp/fsdp -> 'data'); "
                         "default is the degenerate 1-device mesh — the "
                         "SAME code path, not a fork.  On CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N first")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for weight init (ignored with "
                         "--ckpt-dir when a checkpoint is restored)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome-trace-event JSON of the serve "
                         "run (simulated tick clock: request spans, page/"
                         "prefix-cache counters, jit-compile instants) "
                         "and write it to PATH — open in Perfetto "
                         "(ui.perfetto.dev).  Host-side only: tokens are "
                         "bit-identical to an untraced run (implies "
                         "--queue)")
    args = ap.parse_args(argv)
    if (args.prefix_cache or args.prefill_chunk or args.spec_decode
            or args.trace) and not args.queue:
        args.queue = 8          # continuous-engine knobs imply --queue
    return args


def serve_config(args: argparse.Namespace) -> ServeConfig:
    kv_fmt = args.kv_cache_format or ("bf16" if args.bf16 else "nvfp4")
    return ServeConfig(batch_size=args.batch, max_len=args.max_len,
                       temperature=args.temperature,
                       kv_cache_format=kv_fmt,
                       page_size=args.page_size, max_slots=args.max_slots,
                       prefix_cache=args.prefix_cache,
                       prefix_cache_pages=args.prefix_cache_pages,
                       prefill_chunk=args.prefill_chunk,
                       spec_k=args.spec_k if args.spec_decode else None,
                       draft_layers=(args.draft_layers
                                     if args.spec_decode else None),
                       mesh=args.mesh)


def queued_requests(cfg, args: argparse.Namespace, rng) -> list:
    """The ``--queue`` trace: two arrivals per tick, each prompt the
    shared ``--shared-prefix`` tokens plus ``--prompt-len`` random ones."""
    shared = rng.integers(0, cfg.vocab_size, args.shared_prefix)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [shared, rng.integers(0, cfg.vocab_size,
                                              args.prompt_len)]),
                    max_new=args.max_new, arrival=i // 2)
            for i in range(args.queue)]


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        step, restored = ckpt.restore_latest(args.ckpt_dir, params)
        if restored is not None:
            params = restored
            print(f"restored step-{step} checkpoint")

    scfg = serve_config(args)
    if args.mesh:
        from repro.distributed import sharding as shd
        from repro.distributed.specs import (packed_gather_ratio,
                                             packed_wire_bits_per_param)
        mesh = shd.make_serve_mesh(args.mesh)   # fail fast on device count
        print(f"serving mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}"
              f" over {mesh.devices.size} of {jax.device_count()} devices; "
              f"packed weight collectives move "
              f"{packed_wire_bits_per_param():.2f} bits/param "
              f"({packed_gather_ratio():.2f}x less than bf16)")
    qcfg = fqt.bf16_config() if args.bf16 else None
    rng = np.random.default_rng(0)

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer(clock="tick", process="serve")

    if args.queue:
        # continuous batching: staggered arrivals through the scheduler
        eng = ContinuousEngine(cfg, params, scfg, qcfg=qcfg, tracer=tracer)
        reqs = queued_requests(cfg, args, rng)
        t0 = time.perf_counter()
        res = eng.run(reqs)
        dt = time.perf_counter() - t0
        ntok = sum(len(o) for o in res.values())
        st = eng.scheduler.stats
        print(f"{ntok} tokens / {st['completed']} requests in {dt:.2f}s "
              f"({ntok / dt:.1f} tok/s incl. compile; slot util "
              f"{eng.scheduler.slot_utilization:.2f}; compiles: "
              f"prefill {eng.prefill_compiles}+"
              f"{eng.prefill_suffix_compiles}, decode "
              f"{eng.decode_compiles}, verify {eng.verify_compiles})")
        print(f"paging: {st['private_pages']} private + "
              f"{st['shared_pages']} shared + {st['demand_pages']} on-"
              f"demand pages; {st['preemptions']} preemptions")
        ms = eng.metrics.summary()
        print(f"latency (simulated ticks): TTFT p50 "
              f"{ms['ttft_ticks']['p50']:.0f} / p95 "
              f"{ms['ttft_ticks']['p95']:.0f}, TPOT p50 "
              f"{ms['tpot_ticks']['p50']:.2f}, goodput "
              f"{ms['goodput']:.2f}"
              + (f"; {len(eng.scheduler.prefill_log)} prefill chunks "
                 f"(<= {args.prefill_chunk} tok/slot/tick)"
                 if args.prefill_chunk else ""))
        if eng.scheduler.prefix_cache is not None:
            print(f"prefix cache: hit rate "
                  f"{eng.scheduler.prefix_hit_rate:.2f}, "
                  f"{st['prefix_tokens_skipped']} prefill tokens skipped, "
                  f"{st['prefilled_tokens']} prefilled")
        if args.spec_decode and "spec_accepted_per_tick_slot" in ms:
            acc, rate = (ms["spec_accepted_per_tick_slot"],
                         ms["spec_acceptance_rate"])
            print(f"speculative (k={args.spec_k}, draft "
                  f"{eng.draft_layers}/{cfg.n_layers} layers): "
                  f"{acc['mean']:.2f} accepted tokens/tick/slot "
                  f"(p50 {acc['p50']:.0f}, p95 {acc['p95']:.0f}), "
                  f"acceptance rate {rate['mean']:.2f} over "
                  f"{acc['n']} verify samples")
        if tracer is not None:
            tracer.export(args.trace)
            print(f"trace: {tracer.n_events} events "
                  f"({tracer.spans_opened} spans, "
                  f"{len(tracer.open_spans())} unclosed) -> {args.trace} "
                  f"(open in Perfetto: ui.perfetto.dev)")
        for rid in sorted(res)[:4]:
            print(f"req {rid}: {res[rid][:16].tolist()} ...")
        return

    eng = Engine(cfg, params, scfg, qcfg=qcfg)
    prompts = [rng.integers(0, cfg.vocab_size, args.prompt_len)
               for _ in range(args.batch)]
    extras = {}
    if cfg.family == "encdec":
        extras["frames"] = jax.numpy.asarray(
            rng.standard_normal((args.batch, cfg.enc_seq, cfg.d_model)),
            jax.numpy.bfloat16)
    if cfg.family == "vlm":
        extras["prefix_embeds"] = jax.numpy.asarray(
            rng.standard_normal((args.batch, cfg.vision_tokens, cfg.d_model)),
            jax.numpy.bfloat16)

    t0 = time.perf_counter()
    out = eng.generate(prompts, max_new=args.max_new, extras=extras)
    dt = time.perf_counter() - t0
    ntok = sum(len(o) for o in out)
    print(f"{ntok} tokens in {dt:.2f}s  ({ntok / dt:.1f} tok/s, "
          f"incl. compile)")
    for i, o in enumerate(out[:4]):
        print(f"seq {i}: {o[:16].tolist()} ...")


if __name__ == "__main__":
    main()
