"""Multi-tenant traffic harness: chunked prefill + request lifecycle (PR 8).

Layers of evidence:
  * EXACTNESS: chunked prefill is quantize-then-attend — every chunk
    writes its RtN pages first and attends THROUGH the paged cache, so
    the greedy token streams are BIT-identical to unchunked admission
    for every chunk size (straddling page boundaries) and every KV
    format nvfp4/fp8/bf16 (strict equality, no margin gate);
  * chunk budget: no tick ever feeds more than ``prefill_chunk`` prompt
    tokens into a slot (``Scheduler.prefill_log`` is the evidence), and
    the jit caches stay at EXACTLY one compile per program (the fourth,
    chunk program included; the plain prefill program is never used);
  * LIFECYCLE: abort/timeout cancels at EVERY stage — queued, mid-
    chunked-prefill, decoding, after completion (a no-op) — leak
    nothing: page/slot refcount conservation holds after every tick, no
    live row aliases a page or points at TRASH early, and a slot reused
    after a cancel produces the same stream as a fresh admission;
  * prefix-cache persistence: with ``prefix_cache=True`` the scheduler
    (pool + radix cache + device pages) survives across ``run()``
    traces — a warm rerun is bit-identical to the first trace and to a
    genuinely cold fresh engine;
  * the workload generator end-to-end: a seeded two-tenant trace with
    aborts/timeouts runs to completion with every request accounted for
    exactly once and simulated-clock metrics that reconcile with the
    scheduler's own counters.
"""
import dataclasses

import jax
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import registry
from repro.models.layers import TRASH_PAGE
from repro.serve import (ContinuousEngine, Request, Scheduler, ServeConfig,
                         TenantSpec, WorkloadConfig, as_requests,
                         generate_workload)

FMTS = ("nvfp4", "fp8", "bf16")
NO_EOS = -1
# chunk sizes vs the 16-token page: mid-page, exactly one page, page+1
# (every chunk boundary crosses a page boundary), and mid-second-page
CHUNKS = (5, 16, 17, 31)
PROMPT_LENS = (37, 12, 33)      # straddle 2 pages / sub-page / straddle 2

# module-level lazy singletons instead of fixtures: the hypothesis sweep
# below cannot take function-scoped pytest fixtures as arguments
_STATE = {}


def _tiny():
    if "cfg" not in _STATE:
        _STATE["cfg"] = get_config("llama2-60m").smoke()
        _STATE["params"] = registry.init_params(_STATE["cfg"],
                                                jax.random.PRNGKey(0))
    return _STATE["cfg"], _STATE["params"]


def _scfg(fmt, **kw):
    return ServeConfig(batch_size=2, max_len=96, eos_id=NO_EOS,
                       kv_cache_format=fmt, page_size=16, decode_chunk=4,
                       **kw)


def _requests(cfg):
    rng = np.random.default_rng(7)
    return [Request(i, rng.integers(0, cfg.vocab_size, n), max_new=8)
            for i, n in enumerate(PROMPT_LENS)]


_BASELINE = {}      # fmt -> {rid: tokens}: UNCHUNKED suffix-path reference


def _baseline(fmt):
    if fmt not in _BASELINE:
        cfg, params = _tiny()
        # prefix_cache=True routes every admission through the quantize-
        # then-attend suffix program — the exactness-preserving baseline
        eng = ContinuousEngine(cfg, params, _scfg(fmt, prefix_cache=True))
        _BASELINE[fmt] = eng.run(_requests(cfg))
    return _BASELINE[fmt]


def _assert_chunk_budget(log, C):
    """prefill_log evidence: <= C tokens per slot per tick, at most one
    chunk per (tick, slot), and every prompt fully streamed.  Pass one
    trace's slice of the log — ticks restart at 0 every ``run()``."""
    seen = set()
    fed = {}
    for tick, slot, rid, clen in log:
        assert 1 <= clen <= C, (tick, slot, rid, clen)
        assert (tick, slot) not in seen, "two chunks for one slot in a tick"
        seen.add((tick, slot))
        fed[rid] = fed.get(rid, 0) + clen
    return fed


# ---- exactness: chunked == unchunked, every chunk size x format ---------------


@settings(max_examples=4, deadline=None)
@given(C=st.sampled_from(CHUNKS))
def _sweep_chunked_exactness(fmt, C):
    """Property body for the fmt x chunk-size sweep (called by the
    parametrized test below: the hypothesis wrapper hides its signature
    from pytest, so fmt rides in as a plain positional argument)."""
    cfg, params = _tiny()
    want = _baseline(fmt)
    eng = ContinuousEngine(cfg, params, _scfg(fmt, prefill_chunk=C))
    res = eng.run(_requests(cfg))
    assert set(res) == set(want)
    for rid in sorted(want):
        np.testing.assert_array_equal(
            res[rid], want[rid],
            err_msg=f"rid {rid} diverged at chunk={C} fmt={fmt}")
    # the four-program contract: exactly one compile each, and the plain
    # prefill-into-slot program is never traced in chunked mode
    assert eng.prefill_compiles == 0
    assert eng.prefill_suffix_compiles == 1
    assert eng.chunk_compiles == 1        # every CHUNKS value < max plen
    assert eng.decode_compiles == 1
    fed = _assert_chunk_budget(eng.scheduler.prefill_log, C)
    assert fed == {i: n for i, n in enumerate(PROMPT_LENS)}
    assert eng.scheduler.pool.pages_in_use == 0


@pytest.mark.parametrize("fmt", FMTS)
def test_chunked_prefill_bit_identical(fmt):
    _sweep_chunked_exactness(fmt)


def test_chunk_covering_whole_prompt_skips_chunk_program():
    """C >= every prompt: admission still defers to prefill_work, but the
    single (final) chunk rides the suffix program alone — the chunk
    program never compiles."""
    cfg, params = _tiny()
    eng = ContinuousEngine(cfg, params, _scfg("nvfp4", prefill_chunk=48))
    res = eng.run(_requests(cfg))
    want = _baseline("nvfp4")
    for rid in sorted(want):
        np.testing.assert_array_equal(res[rid], want[rid])
    assert eng.chunk_compiles == 0
    assert eng.prefill_suffix_compiles == 1


def test_chunked_rejects_unsupported_configs():
    cfg, params = _tiny()
    swa = dataclasses.replace(cfg, sliding_window=32)
    with pytest.raises(NotImplementedError, match="prefill_chunk"):
        ContinuousEngine(swa, params, _scfg("nvfp4", prefill_chunk=8))
    with pytest.raises(ValueError, match="out of range"):
        ContinuousEngine(cfg, params, _scfg("nvfp4", prefill_chunk=1000))
    with pytest.raises(ValueError, match="prefill_chunk"):
        Scheduler(n_slots=1, max_len=32, page_size=8, prefill_chunk=0)


# ---- lifecycle: cancellation at EVERY stage conserves pages (host-side) -------


@settings(max_examples=8, deadline=None)
@given(abort_tick=st.integers(min_value=0, max_value=6))
def test_lifecycle_conservation_at_any_stage(abort_tick):
    """One victim aborted at every possible tick of its life — queued
    (tick 0), mid-chunked-prefill (1-2), decoding (3), or after it
    already finished (>= 4, a no-op).  After every tick: pool refcounts
    conserve, no live row aliases a page, and at the end nothing leaks
    and every rid is accounted for exactly once."""
    sched = Scheduler(n_slots=2, max_len=32, page_size=4, prefill_chunk=3)
    usable = sched.total_pages - 1
    sched.submit(Request(0, np.arange(10, dtype=np.int32), max_new=4))
    sched.submit(Request(1, np.arange(9, dtype=np.int32), max_new=4,
                         abort_at=abort_tick))
    sched.submit(Request(2, np.arange(8, dtype=np.int32), max_new=3,
                         arrival=1))
    for tick in range(30):
        sched.expire(tick)
        sched.admit(tick)
        for _, _, _, clen, _ in sched.prefill_work(tick):
            assert clen <= 3
        T = sched.tick_steps(2)
        sched.ensure_capacity(T)
        if T:
            for slot in sched.decoding_slots():
                sched.commit(slot, np.full((T,), 7, np.int32), NO_EOS)
        # conservation + no-aliasing after EVERY tick, not just at the end
        assert sched.pool.free_pages + sched.pool.pages_in_use == usable
        live = []
        for slot in sched.active_slots():
            row = sched._rows[slot]
            pages = [p for p in row.tolist() if p != TRASH_PAGE]
            npg = sched._npages[slot]
            assert (row[:npg] != TRASH_PAGE).all()     # allocated prefix
            assert (row[npg:] == TRASH_PAGE).all()     # nothing beyond it
            live += pages
        assert len(live) == len(set(live))     # no cross/intra-slot alias
        if not sched.has_work():
            break
    assert not sched.has_work()
    assert sched.pool.pages_in_use == 0
    assert set(sched.results) | set(sched.cancelled) == {0, 1, 2}
    assert set(sched.results) & set(sched.cancelled) == set()
    # rid 1 (plen 9, C=3): final chunk tick 2, decodes 2+2 tokens over
    # ticks 2-3 -> finishes during tick 3; aborts from tick 4 on are no-ops
    stage = {0: "queued", 1: "prefill", 2: "prefill", 3: "decode"}
    if abort_tick in stage:
        assert sched.cancelled[1]["reason"] == "abort"
        assert sched.cancelled[1]["stage"] == stage[abort_tick]
        assert 1 not in sched.results
    else:
        assert 1 in sched.results and 1 not in sched.cancelled
    assert sched.cancelled.get(1, {}).get("tokens", np.zeros(0)).size == \
        (2 if abort_tick == 3 else 0)


def test_cancel_unknown_or_finished_rid_is_false():
    sched = Scheduler(n_slots=1, max_len=16, page_size=4)
    sched.submit(Request(0, np.arange(4, dtype=np.int32), max_new=2))
    sched.admit(0)
    sched.commit(0, np.asarray([5, 6]), eos_id=NO_EOS)     # finishes
    assert not sched.cancel(0)         # already finished
    assert not sched.cancel(99)        # never existed
    assert sched.stats["cancelled"] == 0


# ---- lifecycle through the engine ---------------------------------------------


def test_abort_mid_chunked_prefill_engine_no_leak():
    """An abort landing while the victim is mid-chunked-prefill frees its
    pages and never perturbs the surviving slot's stream (strict token
    equality vs a solo trace — the suffix path is exact)."""
    cfg, params = _tiny()
    eng = ContinuousEngine(cfg, params, _scfg("nvfp4", prefill_chunk=8))
    rng = np.random.default_rng(11)
    long_p = rng.integers(0, cfg.vocab_size, 40)   # 5 chunks: prefills 0-4
    other = rng.integers(0, cfg.vocab_size, 12)
    res = eng.run([Request(0, long_p, max_new=8, abort_at=2),
                   Request(1, other, max_new=8)])
    sched = eng.scheduler
    assert set(res) == {1}
    assert sched.cancelled[0]["reason"] == "abort"
    assert sched.cancelled[0]["stage"] == "prefill"
    assert sched.cancelled[0]["tokens"].size == 0      # never decoded
    assert sched.pool.pages_in_use == 0
    solo = eng.run([Request(1, other, max_new=8)])
    np.testing.assert_array_equal(res[1], solo[1])
    assert eng.scheduler is not sched      # no prefix cache: fresh trace
    assert eng.chunk_compiles == 1 and eng.decode_compiles == 1


def test_timeout_mid_decode_records_partial_stream():
    cfg, params = _tiny()
    eng = ContinuousEngine(cfg, params, _scfg("nvfp4", prefill_chunk=16))
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, cfg.vocab_size, 12)
    res = eng.run([Request(0, prompt, max_new=20, timeout=3)])
    sched = eng.scheduler
    assert res == {}
    c = sched.cancelled[0]
    assert c["reason"] == "timeout" and c["stage"] == "decode"
    assert 0 < c["tokens"].size < 20       # died mid-decode, partial tokens
    assert sched.pool.pages_in_use == 0
    ms = eng.metrics.summary()
    assert ms["cancelled"] == 1 and ms["completed"] == 0
    assert ms["ttft_ticks"]["n"] == 1      # first token DID reach the host


def test_slot_reuse_after_cancel_matches_fresh_admission():
    """A slot freed by an abort admits the next queued request the same
    tick; its stream is bit-identical to running that request alone
    (PRNG keyed by rid, pages scrubbed via the release path)."""
    cfg, params = _tiny()
    eng = ContinuousEngine(cfg, params, _scfg("nvfp4", prefill_chunk=16))
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (20, 18, 24)]
    res = eng.run([Request(0, prompts[0], max_new=16, abort_at=2),
                   Request(1, prompts[1], max_new=16),
                   Request(2, prompts[2], max_new=8, arrival=1)])
    sched = eng.scheduler
    assert set(res) == {1, 2} and 0 in sched.cancelled
    assert sched.pool.pages_in_use == 0
    solo = eng.run([Request(2, prompts[2], max_new=8)])
    np.testing.assert_array_equal(res[2], solo[2])


# ---- prefix-cache persistence across run() traces -----------------------------


def test_prefix_cache_persists_across_runs():
    cfg, params = _tiny()
    scfg = _scfg("nvfp4", prefix_cache=True)
    eng = ContinuousEngine(cfg, params, scfg)
    rng = np.random.default_rng(21)
    system = rng.integers(0, cfg.vocab_size, 36)       # 2 full pages + 4
    prompts = [np.concatenate([system,
                               rng.integers(0, cfg.vocab_size, 4 + i)])
               for i in range(3)]
    first = eng.run([Request(i, prompts[i], max_new=6, arrival=i)
                     for i in range(3)])
    sched = eng.scheduler
    hits = sched.prefix_cache.stats["hits"]
    assert hits == 2                       # rids 1, 2 shared rid 0's pages
    second = eng.run([Request(7, prompts[2], max_new=6)])
    assert eng.scheduler is sched          # SAME scheduler across traces
    assert sched.prefix_cache.stats["hits"] == hits + 1    # still warm
    assert set(second) == {7}              # per-trace results were cleared
    # warm rerun == the first trace == a genuinely cold fresh engine
    np.testing.assert_array_equal(second[7], first[2])
    cold = ContinuousEngine(cfg, params, scfg).run(
        [Request(7, prompts[2], max_new=6)])
    np.testing.assert_array_equal(second[7], cold[7])
    # both traces rode the same compiled programs
    assert eng.prefill_suffix_compiles == 1 and eng.decode_compiles == 1
    assert eng.prefill_compiles == 0


def test_prefix_cache_composes_with_chunked_prefill():
    """prefix_cache + prefill_chunk: the warm request skips its cached
    full pages, streams only the suffix in chunks, and its tokens are
    bit-identical to the unchunked warm admission."""
    cfg, params = _tiny()
    rng = np.random.default_rng(22)
    system = rng.integers(0, cfg.vocab_size, 36)
    prompts = [np.concatenate([system,
                               rng.integers(0, cfg.vocab_size, 9 + i)])
               for i in range(2)]
    reqs = [Request(i, prompts[i], max_new=6, arrival=i) for i in range(2)]
    want = ContinuousEngine(cfg, params,
                            _scfg("nvfp4", prefix_cache=True)).run(reqs)
    eng = ContinuousEngine(cfg, params,
                           _scfg("nvfp4", prefix_cache=True,
                                 prefill_chunk=8))
    res = eng.run(reqs)
    sched = eng.scheduler
    for rid in (0, 1):
        np.testing.assert_array_equal(res[rid], want[rid])
    # DEFERRED insert: rid 1 arrived while rid 0 was still mid-chunked-
    # prefill, so rid 0's (partially unwritten) pages were NOT yet
    # registered — a later admission can never share unwritten pages
    assert sched.stats["prefix_tokens_skipped"] == 0
    fed = _assert_chunk_budget(sched.prefill_log, 8)
    assert fed == {0: len(prompts[0]), 1: len(prompts[1])}
    # second trace, SAME engine: the prefixes registered when their final
    # chunks issued — the warm rerun skips the 2 cached full pages,
    # streams only the suffix in chunks, and stays bit-identical
    mark = len(sched.prefill_log)
    warm = eng.run([Request(9, prompts[1], max_new=6)])
    assert eng.scheduler is sched
    assert sched.stats["prefix_tokens_skipped"] == 32
    fed2 = _assert_chunk_budget(sched.prefill_log[mark:], 8)
    assert fed2 == {9: len(prompts[1]) - 32}
    np.testing.assert_array_equal(warm[9], want[1])
    # the persisted scheduler keeps ONLY the cache's pages alive — every
    # slot-held page went back to the pool
    assert sched.active_slots() == []
    assert sched.pool.pages_in_use == sched.prefix_cache.cached_pages


# ---- the generated workload end-to-end ----------------------------------------


def test_workload_trace_end_to_end_reconciles():
    cfg, params = _tiny()
    wl = WorkloadConfig(tenants=(
        TenantSpec("chat", rate=0.6, prompt_lens=(6, 12),
                   system_prompt_len=16, max_new=6, deadline_slack=20),
        TenantSpec("flaky", rate=0.3, prompt_lens=(24,), max_new=6,
                   abort_prob=0.5, abort_after=2, timeout=30),
    ), ticks=10, seed=5, vocab=cfg.vocab_size)
    reqs = as_requests(generate_workload(wl))
    assert len(reqs) >= 4                  # seeded: the trace is non-trivial
    eng = ContinuousEngine(cfg, params,
                           _scfg("nvfp4", prefix_cache=True,
                                 prefill_chunk=16))
    res = eng.run(reqs)
    sched, ms = eng.scheduler, eng.metrics.summary()
    # every request accounted for exactly once, metrics == scheduler truth
    assert set(res) | set(sched.cancelled) == {r.rid for r in reqs}
    assert set(res) & set(sched.cancelled) == set()
    assert ms["submitted"] == len(reqs)
    assert ms["completed"] == len(res) == sched.stats["completed"]
    assert ms["cancelled"] == len(sched.cancelled) == \
        sched.stats["cancelled"]
    assert 0.0 <= ms["goodput"] <= 1.0
    assert ms["ttft_ticks"]["n"] >= ms["completed"]
    if ms["completed"]:
        assert ms["ttft_ticks"]["p50"] <= ms["ttft_ticks"]["p95"] \
            <= ms["ttft_ticks"]["p99"]
    assert ms["ticks"] == len(eng.metrics.queue_depth) > 0
    # the chat tenant's shared system prompt fed the prefix cache
    assert sched.stats["prefix_tokens_skipped"] > 0
    _assert_chunk_budget(sched.prefill_log, 16)
    # nothing leaked: only the prefix cache's own pages stay alive
    assert sched.active_slots() == []
    assert sched.pool.pages_in_use == sched.prefix_cache.cached_pages
