"""A serving cell: ``ContinuousEngine.run`` over an open-loop tick trace.

Set-up makes the weights on the device from the seed and packs them layer
by layer with the program's quantize-once packer (the whole model in bf16
does not fit a chip; ``pack_params`` of the configuration's architecture
module, ``arch/<arch>.py``), the way an exported packed artifact is served
(``pack_weights=False``).  It warms both engine programs on a short trace,
times a tick and a prefill there, and sizes the window's trace in ticks to
fill ``--seconds``: a backlog at tick 0 and Poisson arrivals per tick, all
aborted at the last tick so that slots stay full to the end.  The window
is one ``run`` call; per-request wall times come from the program's own
tracer (``obs/trace.Tracer(wall=True)``: per-tick stamps on the host).

Once the window is read and the engine freed, the plain reference runs
once over a seeded sample of the served requests (prompt plus served
tokens, teacher-forced) and reads, for every served token, how far its
logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import math

import numpy as np

import common
import reference as ref
import scopes
import traffic as tr
import weights

FAR = 1.0          # logits: a served token this far below the best is far
MIN_TOKENS = 8     # a request's own far share counts from this many tokens


def warm_host_ops(eng, slots: int, chunk: int) -> None:
    """Compile the engine's per-tick host-side stacks for every count the
    window can ask for: the first tokens and margins of 1..slots
    admissions, and the picks and margins of 1..decode_chunk steps (each
    count is its own small program)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    rep = NamedSharding(eng.mesh, PartitionSpec())
    for dt in (jnp.int32, jnp.float32):
        one = jax.device_put(jnp.zeros((), dt), rep)
        vec = jax.device_put(jnp.zeros((slots,), dt), rep)
        for k in range(1, slots + 1):
            jax.block_until_ready(jnp.stack([one] * k))
        for k in range(1, chunk + 1):
            jax.block_until_ready(jnp.stack([vec] * k, 0))


def tick_walls(events):
    """{tick: (begin wall, end wall)} from the engine's tick spans."""
    beg, end = {}, {}
    for e in events:
        if e.get("name") == "tick" and "args" in e:
            (beg if e["ph"] == "B" else end)[e["ts"]] = e["args"]["wall"]
    return {t: (beg[t], end[t]) for t in beg if t in end}


def request_times(met, walls, n_tok):
    """Per request: TTFT (wall start of its arrival tick to the end of its
    first-token tick) and TPOT ((end of its last-token tick - end of its
    first-token tick) / (tokens - 1)); None where it does not apply."""
    out = {}
    for rid, r in met.requests.items():
        n = n_tok.get(rid, 0)
        ttft = tpot = None
        if r["first"] is not None:
            ttft = walls[r["first"]][1] - walls[r["arrival"]][0]
            if r["done"] is not None:
                last = r["done"]
            elif r["cancelled"] is not None:
                last = r["cancelled"]["tick"] - 1
            else:
                last = max(walls)
            if n >= 2:
                tpot = (walls[last][1] - walls[r["first"]][1]) / (n - 1)
        out[rid] = (r["arrival"], ttft, tpot, n)
    return out


def served_tokens(sched) -> dict:
    toks = {rid: np.asarray(v) for rid, v in sched.results.items()}
    for rid, c in sched.cancelled.items():
        if len(c["tokens"]):
            toks[rid] = np.asarray(c["tokens"])
    return toks


def pick_sample(served: dict, seed: int, want_tokens: int, most: int):
    """Seeded sample of served requests: the longest, then others drawn
    from the seed, until ``want_tokens`` served tokens or ``most``."""
    rids = sorted(served)
    longest = max(rids, key=lambda r: (len(served[r]), -r))
    rng = np.random.default_rng([seed % 2 ** 63, 7])
    rest = [r for r in rng.permutation(rids) if r != longest]
    pick, n = [longest], len(served[longest])
    for r in rest:
        if n >= want_tokens or len(pick) >= most:
            break
        pick.append(int(r))
        n += len(served[r])
    return pick


def reference_logits(arch, m, key, seqs, fmt: str, kv_fmt: str,
                     group: int = 8):
    """Teacher-forced reference of the architecture module ``arch`` over
    ``seqs`` [(prompt, served)], layer by layer, ``group`` sequences at a
    time.  Returns, per sequence, float32 logits at the positions whose
    next token was served (the last prompt position, then each fed served
    token)."""
    import jax
    import jax.numpy as jnp
    f, kf = ref.FORMATS[fmt], ref.FORMATS[kv_fmt]
    T = -(-max(len(p) + len(s) - 1 for p, s in seqs) // 128) * 128
    G = min(group, len(seqs))
    nb = -(-len(seqs) // G)
    toks = np.zeros((nb * G, T), np.int32)
    plens = np.ones((nb * G,), np.int32)
    for i, (p, s) in enumerate(seqs):
        full = np.concatenate([p, s[:-1]])
        toks[i, :len(full)] = full
        plens[i] = len(p)
    toks, plens = toks.reshape(nb, G, T), plens.reshape(nb, G)
    embed = jax.jit(lambda k: arch.make_embed(m, k))(key)
    x = embed[jnp.asarray(toks)]                     # (nb, G, T, d)
    del embed

    @jax.jit
    def layer(x, k, i):
        lp = arch.make_layer(m, k, i)
        one = jax.vmap(lambda xs, pl: arch.serve_layer(m, f, kf, lp, xs,
                                                       pl))
        return jax.lax.map(lambda a: one(*a), (x, jnp.asarray(plens)))

    for i in range(m.layers):
        x = layer(x, key, i)
    x = x.reshape(nb * G, T, -1)
    rows = np.concatenate([np.stack([np.full(len(s), i),
                                     len(p) - 1 + np.arange(len(s))], 1)
                           for i, (p, s) in enumerate(seqs)])
    h = x[rows[:, 0], rows[:, 1]]
    del x
    head = jax.jit(lambda k, h: arch.serve_head(m, f, k, h))
    logits = jnp.concatenate([head(key, h[a:a + 512])
                              for a in range(0, h.shape[0], 512)])
    out, at = [], 0
    for p, s in seqs:
        out.append(logits[at:at + len(s)])
        at += len(s)
    return out


def token_gaps(logits, toks) -> np.ndarray:
    """How far each chosen token's logit lies below the row's best."""
    import jax.numpy as jnp
    lg = jnp.asarray(logits)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)


def run(spec: dict, seed: int, seconds: float, trace: bool, t_start: float,
        *, fault: str = "none", check_device: bool = True) -> tuple:
    import jax
    from repro.core import fqt
    from repro.core.quantize import PackedQuantizedTensor
    from repro.models import registry
    from repro.obs.trace import Tracer
    from repro.serve import ContinuousEngine, packing
    from repro.serve.engine import ServeConfig
    from repro.serve.scheduler import Request

    devs = common.require_devices(spec["chips"]) if check_device \
        else jax.devices()
    clock = common.Clock()
    cell, t = spec["cell"], spec["traffic"]
    arch = common.arch(spec["config"], "serve")
    m = arch.model_of(spec["config"])
    cfg = arch.program_config(spec["config"])
    e = cell["engine"]
    slots, chunk = e["slots"], e["decode_chunk"]
    pad = -(-t["prompt"]["max"] // e["page_size"]) * e["page_size"]
    qcfg = fqt.qaf_config()
    key = weights.root_key(seed)
    params = arch.pack_params(m, key, qcfg.fwd_w)
    want = jax.eval_shape(lambda k: packing.pack_model_params(
        cfg, registry.init_params(cfg, k), qcfg.fwd_w), key)
    is_p = lambda x: isinstance(x, PackedQuantizedTensor)   # noqa: E731
    if jax.tree.structure(want, is_leaf=is_p) != \
            jax.tree.structure(params, is_leaf=is_p):
        raise SystemExit("perfbench: packed layout differs from the "
                         "program's packed parameter tree")
    eos = weights.padded_vocab(m.vocab) + 1    # outside the vocabulary
    scfg = ServeConfig(batch_size=slots, max_len=e["max_len"],
                       temperature=0.0, eos_id=eos,
                       seed=seed % 2 ** 31, kv_cache_format=e["kv_format"],
                       page_size=e["page_size"], max_slots=slots,
                       prefill_len=pad, decode_chunk=chunk)
    eng = ContinuousEngine(cfg, params, scfg, pack_weights=False,
                           tracer=Tracer(wall=True))
    del params
    rng = np.random.default_rng([seed % 2 ** 63, 3])

    def warm_req(rid, n_new, arrival=0):
        plen = int(t["prompt"]["min"])
        return Request(rid=rid, prompt=rng.integers(0, m.vocab, plen).astype(
            np.int32), max_new=n_new, arrival=arrival)

    # warm-up: compile both programs, then time ticks and a prefill
    eng.run([warm_req(0, 2 * chunk + 1)])
    eng.tracer = Tracer(wall=True)
    # ticks 0 and 2 admit a request (prefill + decode), 1, 3-5 only decode
    eng.run([warm_req(1, 4 * chunk + 1),
             warm_req(2, 4 * chunk + 1, arrival=2)])
    w = tick_walls(eng.tracer.trace_events())
    dur = {k: b - a for k, (a, b) in w.items()}
    t_tick = float(np.median([dur[1], dur[3], dur[4], dur[5]]))
    t_prefill = max(float(np.median([dur[0], dur[2]])) - t_tick, 0.0)
    warm_host_ops(eng, slots, chunk)
    n_compiles = (eng.prefill_compiles, eng.decode_compiles, clock.compiles)

    # the trace: tick 0 admits the backlog (its prefills are set-up, as a
    # warm server would have done them), then ticks enough to fill the
    # seconds; every request is cut at the last tick
    ticks = 1 + max(1, int(seconds / t_tick))
    for _ in range(8):
        n = len(tr.arrivals(t, slots, chunk, ticks)) \
            - int(round(t["backlog"] * slots))
        ticks = 1 + max(1, int((seconds - n * t_prefill) / t_tick))
    reqs = [Request(rid=i, prompt=p, max_new=o, arrival=a, abort_at=ticks)
            for i, (a, p, o) in enumerate(tr.serve_requests(
                t, seed, m.vocab, slots, chunk, ticks))]
    eng.tracer = Tracer(wall=True)
    if trace:
        common.OUT.mkdir(exist_ok=True)
        tdir = common.OUT / f"trace-{spec['name']}"
        jax.profiler.start_trace(str(tdir))
    t0 = common.now()
    eng.run(reqs)
    run_s = common.now() - t0
    if trace:
        jax.profiler.stop_trace()
    if (eng.prefill_compiles, eng.decode_compiles, clock.compiles) \
            != n_compiles:
        raise SystemExit("perfbench: a program compiled inside the window")

    sched, met = eng.scheduler, eng.metrics
    served = served_tokens(sched)
    if fault == "token":
        # planted fault: every token the engine produced for one slot is
        # altered (the longest request's, which the sample always holds)
        bad = max(served, key=lambda r: (len(served[r]), -r))
        served[bad] = (served[bad] + 1) % m.vocab
    n_tok = {r: len(v) for r, v in served.items()}
    events = eng.tracer.trace_events()
    walls = tick_walls(events)
    # the window: from the start of tick 1 to the end of the last tick
    w0, w1 = walls[1][0], walls[max(walls)][1]
    window_s = w1 - w0
    setup_s = w0 - t_start
    compile_setup = (clock.compile_s, clock.compiles, clock.cache_read_s)
    first = {r: q["first"] for r, q in met.requests.items()}
    at0 = [r for r in n_tok if first[r] == 0]
    t0_steps = min([chunk] + [q.max_new - 1 for q in reqs if q.rid in at0])
    in_window = sum(n_tok.values()) - sum(min(n_tok[r], 1 + t0_steps)
                                          for r in at0)
    times = request_times(met, walls, n_tok)
    arrived = [r for r, (a, *_rest) in times.items() if a >= 1]
    tpots = [v[2] for v in times.values() if v[2] is not None]
    ttfts = []
    for r in arrived:
        a, ttft, _, _ = times[r]
        ttfts.append(ttft if ttft is not None else w1 - walls[a][0])
    dev = common.device_info(devs)
    # per-request work for the per-layer readers, over the whole traced run
    prompts = {q.rid: len(q.prompt) for q in reqs}
    ctx = {"arch": arch, "model": m, "slots": slots, "window_s": run_s,
           "compile": compile_setup,
           "peaks": common.peaks(dev["kind"]) if trace else None,
           "occupancy": float(np.mean(met.active_depth[1:])) / slots,
           "prompts": prompts, "served": n_tok,
           "decode_steps": int(sched.stats["decode_steps"])}
    if trace:
        ctx["tick_phases"] = scopes.tick_phases(events)
    e2e = {"serve_tokens_per_s": in_window / window_s, "setup_s": setup_s}
    if tpots:
        e2e["tpot_p95_s"] = tr.percentile(tpots, 95)
    if ttfts:
        e2e["ttft_p95_s"] = tr.percentile(ttfts, 95)
    del eng, sched
    gc.collect()

    # the reference over a seeded sample of what was served
    t_ref = common.now()
    sample = pick_sample(served, seed, cell["check"]["tokens"],
                         cell["check"]["most_requests"])
    byid = {q.rid: q for q in reqs}
    seqs = [(np.asarray(byid[r].prompt), served[r]) for r in sample]
    logits = reference_logits(arch, m, key, seqs, "nvfp4", e["kv_format"])
    stats = gap_stats([token_gaps(lg, s) for lg, (_, s) in
                       zip(logits, seqs)])
    checks, correct = judge(stats, cell["limits"])
    out = {"correct": correct, "attempted": len(arrived), "failed": 0,
           "device": dev}
    detail = {"stats": stats, "ticks": ticks, "t_tick": t_tick,
              "t_prefill": t_prefill, "window_s": window_s,
              "requests": len(reqs), "reference_s": common.now() - t_ref}
    return (out, e2e, ctx, checks, detail, (tdir if trace else None),
            (key, seqs, logits))


def gap_stats(gaps) -> dict:
    """Readings of the served tokens' gaps below the reference's best, one
    array of gaps per request.  Compared (PERF.md): ``token_far_share``,
    the share of all sampled tokens more than FAR logits below the best,
    and ``request_far_share_max``, the same share in the worst request of
    MIN_TOKENS or more, so that a fault confined to one slot shows.  The
    widest and the mean gap do not separate the control from sound runs."""
    g = np.concatenate(gaps).astype(np.float64)
    per = sorted(float(np.mean(x > FAR)) for x in gaps
                 if len(x) >= MIN_TOKENS)
    return {"token_far_share": float(np.mean(g > FAR)),
            "request_far_share_max": per[-1] if per else math.nan,
            "token_gap_max": float(np.max(g)),
            "token_gap_mean": float(np.mean(g)),
            "token_gap_rms": float(np.sqrt(np.mean(g * g))),
            "token_miss_share": float(np.mean(g > 0)),
            "tokens": int(g.size), "requests": len(gaps),
            "request_far_shares": per,
            "over": {str(t): int(np.sum(g > t))
                     for t in (0.05, 0.1, 0.2, 0.3, 0.5, 1.0, 2.0)}}


def judge(stats: dict, limits: dict) -> tuple:
    """(checks, correct): each compared number beside its limit; correct
    when every one is a number within its limit."""
    checks = {k: {"value": stats[k], "limit": v} for k, v in limits.items()}
    return checks, all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values())


def control_stats(arch, m, key, seqs, logits_ref):
    """The control's readings: the reference in MXFP4 (the next precision
    below NVFP4, weights, activations and cache rows) puts its own best
    token first at every served position; those tokens take the served
    tokens' place and are read as they are (``gap_stats``, then
    ``judge``)."""
    low = reference_logits(arch, m, key, seqs, "mxfp4", "mxfp4")
    return gap_stats([token_gaps(hi, np.asarray(np.argmax(
        np.asarray(lo), axis=-1))) for lo, hi in zip(low, logits_ref)])
