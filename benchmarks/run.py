"""Benchmark harness driver.

  PYTHONPATH=src python -m benchmarks.run              # quick set
  PYTHONPATH=src python -m benchmarks.run --full       # every paper figure
  PYTHONPATH=src python -m benchmarks.run --bench fig3 # one artifact

Prints ``bench,name,metric`` CSV (one row group per paper table/figure) and
a kernel micro-timing section.  Roofline numbers come from the dry-run
(launch/dryrun.py) — see benchmarks/roofline_report.py for the table.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import paper_figs as pf
from repro.launch.compile_cache import enable_compile_cache


def kernel_microbench(reps: int = 5):
    """Wall-time of the jnp fake-quant FQT matmul vs plain bf16 matmul on
    this host (CPU — relative numbers only; TPU perf comes from §Roofline)."""
    import jax
    import jax.numpy as jnp
    from repro.core import fqt

    rows = []
    M = K = N = 1024
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.bfloat16)

    def timeit(fn, *args):
        jax.tree.leaves(fn(*args))[0].block_until_ready()   # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
            jax.tree.leaves(out)[0].block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e6

    mm = jax.jit(lambda a, b: a @ b)
    rows.append(("kernel_us", "bf16_matmul_1k", timeit(mm, x, w)))
    fq = jax.jit(lambda a, b: fqt.fp4_matmul(
        a, b, cfg=fqt.nvfp4_paper_config(), seed=jnp.uint32(1)))
    rows.append(("kernel_us", "fqt_fwd_matmul_1k", timeit(fq, x, w)))

    # quantize-once packed weight: activation-only quantization per GEMM
    from repro.core.quantize import NVFP4, pack_quantize
    pw = pack_quantize(w, NVFP4, axis=-2)
    pq = jax.jit(lambda a, pw: fqt.fp4_matmul(a, pw, cfg=fqt.qaf_config()))
    rows.append(("kernel_us", "packed_fwd_matmul_1k", timeit(pq, x, pw)))
    return rows


def serving_weight_store():
    """Decode-path weight bytes: bf16 store vs quantize-once packed NVFP4.

    The decode step is weight-bandwidth-bound; every generated token
    streams the full weight store from HBM, so stored bytes/param IS the
    bandwidth ratio of the serving hot loop."""
    import jax
    from repro.configs import get_config
    from repro.core import fqt
    from repro.models import registry
    from repro.serve.packing import pack_model_params, weight_store_bytes

    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    packed = pack_model_params(cfg, params, fqt.qaf_config().fwd_w)
    bf16 = weight_store_bytes(params)
    pk = weight_store_bytes(packed)
    from repro.core.quantize import PackedQuantizedTensor
    import numpy as np
    pleaves = [l for l in jax.tree_util.tree_leaves(
        packed, is_leaf=lambda x: isinstance(x, PackedQuantizedTensor))
        if isinstance(l, PackedQuantizedTensor)]
    gemm_bytes = sum(l.nbytes() for l in pleaves)
    gemm_params = sum(int(np.prod(l.shape)) for l in pleaves)
    return [
        ("serve_weight_bytes", "bf16_store", float(bf16)),
        ("serve_weight_bytes", "packed_nvfp4_store", float(pk)),
        ("serve_weight_bytes", "decode_traffic_ratio", bf16 / pk),
        ("serve_weight_bytes", "packed_bytes_per_gemm_param",
         gemm_bytes / gemm_params),
    ]


def kv_cache_bench():
    """Decode-attention cache traffic: bf16 vs block-quantized KV cache.

    Long-context decode attention is bound by KV cache HBM reads (every
    token streams the whole cache), so stored bytes/token IS the bandwidth
    ratio of the attention term.  Reports bytes/token per format plus the
    greedy-token agreement vs the bf16 cache on the smoke config."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core import fqt
    from repro.models import registry

    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    qcfg = fqt.qaf_config()
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    max_len, steps = 96, 24

    def cache_bytes_per_token(fmt):
        carry = registry.make_decode_state(cfg, 1, max_len,
                                           kv_cache_format=fmt)
        total = sum(int(l.size * l.dtype.itemsize)
                    for l in jax.tree_util.tree_leaves(carry))
        return total / max_len

    # teacher-forced greedy agreement: both caches see the SAME token
    # stream (the bf16 run's), so one early argmax flip on a near-flat
    # random-init logit row cannot cascade — the per-step agreement is the
    # bounded-divergence measure of the cache approximation itself.
    def greedy_stream(fmt, forced=None):
        """Decode `steps` greedy picks; with ``forced`` the next input is
        the bf16 run's pick (teacher forcing), else the own pick."""
        carry = registry.make_decode_state(cfg, 2, max_len,
                                           kv_cache_format=fmt)
        last, carry = registry.prefill(params, cfg, qcfg, toks, carry)
        picks, lgs = [], []
        tok = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
        for t in range(steps):
            logits, carry = registry.decode_step(params, cfg, qcfg, tok,
                                                 carry)
            pick = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            picks.append(np.asarray(pick))
            lgs.append(np.asarray(logits[:, -1], np.float32))
            tok = (pick if forced is None else forced[t])[:, None]
        return np.stack(picks), np.stack(lgs)

    rows, bpt = [], {}
    for fmt in ("bf16", "nvfp4", "fp8"):
        bpt[fmt] = cache_bytes_per_token(fmt)
        rows.append(("kv_cache_bytes_per_token", fmt, bpt[fmt]))
    # bf16 pass records the forced token stream + reference logits
    ref_picks, ref_lgs = greedy_stream("bf16")
    forced = [jnp.asarray(p) for p in ref_picks]
    for fmt in ("nvfp4", "fp8"):
        picks, lgs = greedy_stream(fmt, forced)
        rows.append(("kv_cache_traffic_ratio", fmt,
                     bpt["bf16"] / bpt[fmt]))
        rows.append(("kv_cache_token_agreement_vs_bf16", fmt,
                     float(np.mean(picks == ref_picks))))
        # the bounded-divergence measure proper: relative logit error (the
        # token flips above happen on near-tied random-init logit rows)
        rows.append(("kv_cache_rel_logit_rmse", fmt,
                     float(np.sqrt(np.mean((lgs - ref_lgs) ** 2))
                           / np.sqrt(np.mean(ref_lgs ** 2)))))
    return rows


def serve_throughput_bench():
    """Continuous batching vs lockstep on a seeded synthetic arrival trace.

    Requests with mixed prompt lengths / budgets / arrival ticks stream
    through the ContinuousEngine's scheduler (paged NVFP4 KV cache, slot
    reuse).  Reports tokens/s (wall clock, informational only — nothing
    asserts on it), slot utilization, page-pool size and cache bytes per
    token; the trace itself is deterministic (tick-indexed arrivals, fixed
    PRNG seed — no wall-clock dependence anywhere in the numbers that
    matter)."""
    import time

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core.quantize import kv_bytes_per_elem
    from repro.models import registry
    from repro.serve import ContinuousEngine, Request, ServeConfig

    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    slots, max_len = 4, 96
    scfg = ServeConfig(batch_size=slots, max_len=max_len, eos_id=-1,
                       kv_cache_format="nvfp4", page_size=16,
                       decode_chunk=8)
    eng = ContinuousEngine(cfg, params, scfg)

    rng = np.random.default_rng(0)
    n_req = 10
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(4, 16))),
                    max_new=int(rng.integers(6, 20)),
                    arrival=int(i // 3))
            for i in range(n_req)]
    eng.run(reqs)                                   # warm-up: compiles
    t0 = time.perf_counter()
    res = eng.run(reqs)
    dt = time.perf_counter() - t0
    ntok = sum(len(o) for o in res.values())
    sched = eng.scheduler
    kv_elems = 2 * cfg.n_kv_heads * cfg.hd * cfg.n_layers
    return [
        ("serve_throughput", "requests_completed",
         float(sched.stats["completed"])),
        ("serve_throughput", "tokens_generated", float(ntok)),
        ("serve_throughput", "tokens_per_s", ntok / dt),
        ("serve_throughput", "slot_utilization", sched.slot_utilization),
        ("serve_throughput", "decode_steps", float(sched.stats["decode_steps"])),
        ("serve_throughput", "page_pool_pages", float(sched.total_pages)),
        ("serve_throughput", "cache_bytes_per_token",
         kv_bytes_per_elem(scfg.kv_cache_format) * kv_elems),
        ("serve_throughput", "prefill_compiles", float(eng.prefill_compiles)),
        ("serve_throughput", "decode_compiles", float(eng.decode_compiles)),
    ]


def spec_decode_bench():
    """Speculative decoding acceptance trajectory: k in {2, 4} x draft
    depth {1, full}.  Every speculative stream is asserted BIT-identical
    to the non-speculative engine before its numbers are recorded — the
    trajectory measures pure throughput movement, never token drift.
    The accepted-tokens/tick/slot metric is the speedup story: mean > 1
    means the verify program advances more than one committed token per
    tick per slot (full-depth self-draft pins the ceiling at exactly k,
    acceptance rate 1.0)."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import registry
    from repro.serve import ContinuousEngine, Request, ServeConfig

    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(8, 24))),
                    max_new=int(rng.integers(8, 16)),
                    arrival=int(i // 2))
            for i in range(6)]

    def scfg(**kw):
        return ServeConfig(batch_size=2, max_len=96, eos_id=-1,
                           kv_cache_format="nvfp4", page_size=16, **kw)

    def run(sc):
        eng = ContinuousEngine(cfg, params, sc)
        res = eng.run([Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new,
                               arrival=r.arrival) for r in reqs])
        return res, eng

    want, _ = run(scfg())
    rows = []
    for k in (2, 4):
        for dl in (1, cfg.n_layers):
            res, eng = run(scfg(spec_k=k, draft_layers=dl))
            for rid in want:
                np.testing.assert_array_equal(
                    res[rid], want[rid],
                    err_msg=f"spec k={k} dl={dl} drifted from sequential")
            s = eng.metrics.summary()
            tag = f"k{k}_draft{dl}"
            acc = s["spec_accepted_per_tick_slot"]
            rows += [
                ("serve_spec", f"{tag}_accepted_per_tick_slot_mean",
                 float(acc["mean"])),
                ("serve_spec", f"{tag}_accepted_per_tick_slot_p50",
                 float(acc["p50"])),
                ("serve_spec", f"{tag}_accepted_per_tick_slot_p95",
                 float(acc["p95"])),
                ("serve_spec", f"{tag}_acceptance_rate_mean",
                 float(s["spec_acceptance_rate"]["mean"])),
                ("serve_spec", f"{tag}_verify_ticks", float(acc["n"])),
                ("serve_spec", f"{tag}_verify_compiles",
                 float(eng.verify_compiles)),
            ]
    return rows


def prefix_cache_bench():
    """Exact shared-prefix cache: warm admissions skip the shared pages.

    A seeded arrival trace where requests share one of two long system
    prompts (the multi-user serving shape) streams through the continuous
    engine twice — prefix cache on vs off.  Reports the prefix hit rate,
    prefill tokens skipped vs prefilled, pages shared vs private vs
    allocated on demand, preemptions, and cache bytes/token.  Everything
    asserted-on elsewhere is tick/accounting-based — no wall clock (the
    interpret-mode caveat)."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core.quantize import kv_bytes_per_elem
    from repro.models import registry
    from repro.serve import ContinuousEngine, Request, ServeConfig

    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    slots, max_len, psz = 4, 96, 16
    rng = np.random.default_rng(0)
    sys_prompts = [rng.integers(0, cfg.vocab_size, 40) for _ in range(2)]
    n_req = 10
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [sys_prompts[i % 2],
                         rng.integers(0, cfg.vocab_size,
                                      int(rng.integers(2, 8)))]),
                    max_new=int(rng.integers(6, 16)),
                    arrival=int(i // 2))
            for i in range(n_req)]

    def serve(prefix_cache):
        scfg = ServeConfig(batch_size=slots, max_len=max_len, eos_id=-1,
                           kv_cache_format="nvfp4", page_size=psz,
                           decode_chunk=8, prefix_cache=prefix_cache)
        eng = ContinuousEngine(cfg, params, scfg)
        eng.run(reqs)
        return eng.scheduler

    warm, cold = serve(True), serve(False)
    ws, cs = warm.stats, cold.stats
    kv_elems = 2 * cfg.n_kv_heads * cfg.hd * cfg.n_layers
    return [
        ("prefix_cache", "requests_completed", float(ws["completed"])),
        ("prefix_cache", "hit_rate", warm.prefix_hit_rate),
        ("prefix_cache", "prefill_tokens_skipped",
         float(ws["prefix_tokens_skipped"])),
        ("prefix_cache", "prefill_tokens_warm", float(ws["prefilled_tokens"])),
        ("prefix_cache", "prefill_tokens_cold", float(cs["prefilled_tokens"])),
        ("prefix_cache", "pages_shared", float(ws["shared_pages"])),
        ("prefix_cache", "pages_private", float(ws["private_pages"])),
        ("prefix_cache", "pages_on_demand", float(ws["demand_pages"])),
        ("prefix_cache", "preemptions", float(ws["preemptions"])),
        ("prefix_cache", "cache_bytes_per_token",
         kv_bytes_per_elem("nvfp4") * kv_elems),
        ("prefix_cache", "slot_utilization", warm.slot_utilization),
    ]


def serve_sharded_bench():
    """Mesh-native serving: packed-weight wire accounting + engine trace.

    Sharded serving moves weights in the SAME wire format it stores them:
    uint8 nibble codes + f8 block scales (~4.5 bits/param for NVFP4 block
    16) instead of 16-bit bf16 gathers — the accounting here is exact byte
    counts over the packed model, checked against the closed-form
    ``distributed/specs`` numbers.  The engine trace runs on the default
    1-device mesh, which is the SAME code path TP=N serving takes
    (benchmarks run without forced host device counts)."""
    import time

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core import fqt
    from repro.core.quantize import PackedQuantizedTensor
    from repro.distributed.specs import (packed_gather_ratio,
                                         packed_wire_bits_per_param)
    from repro.models import registry
    from repro.serve import ContinuousEngine, Request, ServeConfig
    from repro.serve.packing import pack_model_params, weight_wire_bytes

    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    packed = pack_model_params(cfg, params, fqt.qaf_config().fwd_w)
    pleaves = [l for l in jax.tree_util.tree_leaves(
        packed, is_leaf=lambda x: isinstance(x, PackedQuantizedTensor))
        if isinstance(l, PackedQuantizedTensor)]
    gemm_params = sum(int(np.prod(l.shape)) for l in pleaves)
    wire = sum(l.wire_nbytes() for l in pleaves)
    bf16_wire = 2 * gemm_params
    rows = [
        ("serve_sharded", "gemm_params", float(gemm_params)),
        ("serve_sharded", "wire_bytes_packed", float(wire)),
        ("serve_sharded", "wire_bytes_bf16", float(bf16_wire)),
        ("serve_sharded", "wire_bits_per_param", wire * 8 / gemm_params),
        ("serve_sharded", "wire_bits_per_param_model",
         packed_wire_bits_per_param()),
        ("serve_sharded", "gather_ratio_vs_bf16", bf16_wire / wire),
        ("serve_sharded", "gather_ratio_model", packed_gather_ratio()),
        ("serve_sharded", "tree_wire_bytes", float(weight_wire_bytes(packed))),
    ]

    scfg = ServeConfig(batch_size=4, max_len=96, eos_id=-1,
                       kv_cache_format="nvfp4", page_size=16,
                       decode_chunk=8, mesh=None)
    eng = ContinuousEngine(cfg, params, scfg)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(4, 16))),
                    max_new=int(rng.integers(6, 16)),
                    arrival=int(i // 3))
            for i in range(8)]
    eng.run(reqs)                                   # warm-up: compiles
    t0 = time.perf_counter()
    res = eng.run(reqs)
    dt = time.perf_counter() - t0
    ntok = sum(len(o) for o in res.values())
    rows += [
        ("serve_sharded", "mesh_devices", float(eng.mesh.devices.size)),
        ("serve_sharded", "tokens_per_s", ntok / dt),
        ("serve_sharded", "prefill_compiles", float(eng.prefill_compiles)),
        ("serve_sharded", "decode_compiles", float(eng.decode_compiles)),
    ]
    return rows


def traffic_bench():
    """Multi-tenant traffic trajectory: TTFT/TPOT/goodput percentiles.

    A seeded three-tenant workload (serve/workload.py: Poisson + burst
    arrivals, per-tenant prompt mixes, shared system prompts, aborts and
    timeouts) streams through the continuous engine with chunked prefill
    + the prefix cache; serve/metrics.py records the lifecycle in
    SIMULATED TICKS.  Every number here is tick/accounting-based and
    deterministic — the recorded trajectory is comparable across PRs
    (no wall clock anywhere)."""
    import jax
    from repro.configs import get_config
    from repro.models import registry
    from repro.serve import (ContinuousEngine, ServeConfig, TenantSpec,
                             WorkloadConfig, as_requests,
                             generate_workload)

    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    wcfg = WorkloadConfig(tenants=(
        TenantSpec("chat", rate=0.45, prompt_lens=(6, 12, 20),
                   prompt_probs=(0.5, 0.3, 0.2), system_prompt_len=16,
                   max_new=10, deadline_slack=24),
        TenantSpec("batch", rate=0.15, prompt_lens=(40,), max_new=6,
                   timeout=12, burst_every=10, burst_size=2),
        # long prompts + a tight abort window: the aborts land MID-
        # chunked-prefill, so the recorded trajectory exercises the
        # cancellation path, not just happy completions
        TenantSpec("flaky", rate=0.2, prompt_lens=(60,), max_new=8,
                   abort_prob=0.6, abort_after=2),
    ), ticks=24, seed=11, vocab=cfg.vocab_size)
    reqs = as_requests(generate_workload(wcfg))
    scfg = ServeConfig(batch_size=4, max_len=96, eos_id=-1,
                       kv_cache_format="nvfp4", page_size=16,
                       decode_chunk=8, prefix_cache=True,
                       prefill_chunk=16)
    eng = ContinuousEngine(cfg, params, scfg)
    eng.run(reqs)
    s = eng.metrics.summary()
    rows = [
        ("traffic", "requests_submitted", float(s["submitted"])),
        ("traffic", "requests_completed", float(s["completed"])),
        ("traffic", "requests_cancelled", float(s["cancelled"])),
        ("traffic", "goodput", float(s["goodput"])),
        ("traffic", "ticks", float(s["ticks"])),
    ]
    for met in ("ttft_ticks", "tpot_ticks"):
        for p in ("p50", "p95", "p99"):
            rows.append(("traffic", f"{met}_{p}", float(s[met][p])))
    rows += [
        ("traffic", "queue_depth_p95", float(s["queue_depth"]["p95"])),
        ("traffic", "queue_depth_max", float(s["queue_depth"]["max"])),
        ("traffic", "preemptions", float(s["counters"]["preemptions"])),
        ("traffic", "prefix_hit_rate", eng.scheduler.prefix_hit_rate),
        ("traffic", "prefill_chunks_issued",
         float(len(eng.scheduler.prefill_log))),
        ("traffic", "chunk_compiles", float(eng.chunk_compiles)),
        ("traffic", "suffix_compiles",
         float(eng.prefill_suffix_compiles)),
        ("traffic", "decode_compiles", float(eng.decode_compiles)),
    ]
    return rows


def lint_stats_bench():
    """fp4lint counters for the artifact: per-rule finding counts, files
    scanned, pragma suppressions and runtime.  Recording them per PR makes
    the suppressed-vs-fixed trajectory legible — a rising suppressed count
    with a flat finding count means violations are being pragma'd away
    instead of fixed.  Jax-free (repro.analysis is pure stdlib)."""
    import os

    from repro.analysis import RULES, lint_paths

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings, stats = lint_paths(root=root)
    rows = [
        ("lint", "files_scanned", float(stats.files_scanned)),
        ("lint", "findings_total", float(stats.findings)),
        ("lint", "suppressed", float(stats.suppressed)),
        ("lint", "parse_errors", float(stats.parse_errors)),
        ("lint", "runtime_ms", stats.runtime_s * 1e3),
    ]
    for rule in sorted(RULES):
        rows.append(("lint", f"findings_{rule.replace('-', '_')}",
                     float(stats.per_rule.get(rule, 0))))
    return rows


def obs_bench():
    """Observability cost + trace volume: the three-tenant traffic
    workload (same seeded config as ``traffic``) runs with a live tracer
    attached, recording how many events / spans / counter series the
    serve path emits and how many bytes the exported Chrome trace weighs.
    The two timing rows are host microbenchmarks: recording-tracer
    emission throughput (events/s) and the disabled ``NULL_TRACER``
    per-call cost in ns — the "zero when off" claim, measured."""
    import os
    import tempfile
    import time as _time

    import jax
    from repro.configs import get_config
    from repro.models import registry
    from repro.obs import NULL_TRACER, Tracer
    from repro.serve import (ContinuousEngine, ServeConfig, TenantSpec,
                             WorkloadConfig, as_requests,
                             generate_workload)

    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    wcfg = WorkloadConfig(tenants=(
        TenantSpec("chat", rate=0.45, prompt_lens=(6, 12, 20),
                   prompt_probs=(0.5, 0.3, 0.2), system_prompt_len=16,
                   max_new=10, deadline_slack=24),
        TenantSpec("batch", rate=0.15, prompt_lens=(40,), max_new=6,
                   timeout=12, burst_every=10, burst_size=2),
        TenantSpec("flaky", rate=0.2, prompt_lens=(60,), max_new=8,
                   abort_prob=0.6, abort_after=2),
    ), ticks=24, seed=11, vocab=cfg.vocab_size)
    reqs = as_requests(generate_workload(wcfg))
    scfg = ServeConfig(batch_size=4, max_len=96, eos_id=-1,
                       kv_cache_format="nvfp4", page_size=16,
                       decode_chunk=8, prefix_cache=True,
                       prefill_chunk=16)
    trc = Tracer(clock="tick", process="serve")
    eng = ContinuousEngine(cfg, params, scfg, tracer=trc)
    eng.run(reqs)

    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        trc.export(path)
        trace_bytes = os.path.getsize(path)
    finally:
        os.unlink(path)

    # recording-path emission throughput (pure host, no engine)
    mtrc = Tracer()
    n = 50_000
    t0 = _time.perf_counter()
    for _ in range(n):
        mtrc.counter("x")
    emit_per_s = n / (_time.perf_counter() - t0)

    # disabled path: the no-op singleton's per-call cost
    n = 200_000
    t0 = _time.perf_counter()
    for _ in range(n):
        NULL_TRACER.counter("x")
    noop_ns = (_time.perf_counter() - t0) / n * 1e9

    return [
        ("obs", "trace_events", float(trc.n_events)),
        ("obs", "spans_opened", float(trc.spans_opened)),
        ("obs", "spans_unclosed", float(len(trc.open_spans()))),
        ("obs", "counter_series", float(len(trc.counters))),
        ("obs", "trace_bytes", float(trace_bytes)),
        ("obs", "emit_events_per_s", emit_per_s),
        ("obs", "disabled_noop_ns_per_call", noop_ns),
    ]


BENCHES = {
    "fig1": pf.fig1_scale_formats,
    "fig2": pf.fig2_block_sizes,
    "fig3": pf.fig3_rounding_modes,
    "fig4": pf.fig4_quadratic,
    "fig5": pf.fig5_threshold_model,
    "fig6": pf.fig6_fqt_vs_bf16,
    "table2": pf.table2_settings,
    "kernels": kernel_microbench,
    "serve_weights": serving_weight_store,
    "kv_cache": kv_cache_bench,
    "serve_throughput": serve_throughput_bench,
    "spec_decode": spec_decode_bench,
    "prefix_cache": prefix_cache_bench,
    "serve_sharded": serve_sharded_bench,
    "traffic": traffic_bench,
    "lint": lint_stats_bench,
    "obs": obs_bench,
}

QUICK = ("table2", "fig4", "kernels", "fig5", "fig6", "serve_weights",
         "kv_cache", "serve_sharded", "traffic", "lint")

# the serving artifact (BENCH_serve.json): throughput, cache bytes/token,
# speculative acceptance trajectory, prefix-cache hit rate, sharded-
# weights wire accounting, the multi-tenant TTFT/TPOT/goodput
# trajectory, lint trajectory, observability cost/volume
SERVE_BENCHES = ("serve_weights", "kv_cache", "serve_throughput",
                 "spec_decode", "prefix_cache", "serve_sharded", "traffic",
                 "lint", "obs")


def _merge_bench_json(existing: dict, new_groups: dict) -> dict:
    """Merge freshly collected per-bench groups into an existing
    BENCH_serve.json payload: replaced at GROUP granularity, every other
    recorded group kept verbatim — a partial re-run (``--bench traffic
    --json``) can never clobber the rest of the recorded trajectory."""
    benches = dict(existing.get("benches", {}) or {})
    benches.update(new_groups)
    out = dict(existing)
    out["generated_by"] = "benchmarks.run --json"
    out["benches"] = benches
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="run every paper figure (hours on CPU)")
    ap.add_argument("--bench", default=None, choices=sorted(BENCHES))
    ap.add_argument("--json", nargs="?", const="BENCH_serve.json",
                    default=None, metavar="PATH",
                    help="also run the serving benches and write their rows "
                         "as JSON (default path: BENCH_serve.json)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    names = ([args.bench] if args.bench
             else sorted(BENCHES) if args.full
             else list(SERVE_BENCHES) if args.json else list(QUICK))
    if args.json and not args.bench:
        # an explicit --bench stays a PARTIAL run: only that bench's
        # group is (re)written, the merge below keeps the rest
        names += [n for n in SERVE_BENCHES if n not in names]
    collected, failed = {}, []
    print("bench,name,value")
    for name in names:
        t0 = time.time()
        try:
            rows = BENCHES[name]()
        except Exception as e:                                # noqa: BLE001
            # report and go on to the next bench; the exit code says so
            traceback.print_exc()
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            failed.append(name)
            continue
        for group, key, val in rows:
            print(f"{group},{key},{val:.6g}")
            collected.setdefault(group, {})[key] = float(f"{val:.6g}")
        print(f"# {name} took {time.time() - t0:.1f}s", file=sys.stderr)
    if args.json:
        import json
        import os
        serve_groups = {g: v for g, v in collected.items()
                        if g.startswith(("serve", "kv_cache", "prefix",
                                         "traffic", "lint", "obs"))}
        existing = {}
        if os.path.exists(args.json):
            try:
                with open(args.json) as f:
                    existing = json.load(f)
            except (ValueError, OSError):
                existing = {}        # unreadable artifact: rewrite fresh
        with open(args.json, "w") as f:
            json.dump(_merge_bench_json(existing, serve_groups), f,
                      indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json} ({len(serve_groups)} group(s) "
              f"updated)", file=sys.stderr)
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
