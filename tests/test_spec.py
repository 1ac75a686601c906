"""Speculative decoding on the bit-exact paged engine (PR 9).

Layers of evidence:
  * EXACTNESS: speculative greedy streams are BIT-identical to the
    non-speculative engine across every KV format (nvfp4/fp8/bf16),
    every draft depth, and composed with chunked prefill + the prefix
    cache — greedy verify accepts exactly the longest prefix the target
    would have produced sequentially, so acceptance only moves
    throughput, never tokens (strict equality, no margin gate);
  * the cache primitives underneath: ``write_tokens`` lands the same
    RtN rows as sequential ``write_token`` calls, and ``truncate_to``
    rolls rejected rows back exactly (the next append overwrites them
    in place — no zeroing pass to diverge bit-wise);
  * the FIVE-program contract: spec mode compiles the verify program
    exactly once, never touches the plain decode program, and the jit
    caches all stay at one entry across admissions and preemptions;
  * LIFECYCLE: cancel/expire/preempt landing on any tick of the
    draft -> verify -> rollback cycle leak nothing — page/slot refcount
    conservation holds after every tick, no live row aliases a page or
    points at TRASH early, and partial-suffix preemption resumes
    mid-stream bit-identically (spec and non-spec);
  * metrics: the accepted-tokens/tick/slot trajectory reconciles with
    the committed streams, and a full-depth draft (the draft IS the
    target) accepts everything — acceptance rate exactly 1.0.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import registry
from repro.models.layers import TRASH_PAGE, PagedKVCache
from repro.serve import ContinuousEngine, Request, Scheduler, ServeConfig

FMTS = ("nvfp4", "fp8", "bf16")
NO_EOS = -1
PROMPT_LENS = (33, 12, 37)      # straddle 2 pages / sub-page / straddle 2

_STATE = {}


def _tiny():
    if "cfg" not in _STATE:
        _STATE["cfg"] = get_config("llama2-60m").smoke()
        _STATE["params"] = registry.init_params(_STATE["cfg"],
                                                jax.random.PRNGKey(0))
    return _STATE["cfg"], _STATE["params"]


def _scfg(fmt, **kw):
    return ServeConfig(batch_size=2, max_len=96, eos_id=NO_EOS,
                       kv_cache_format=fmt, page_size=16, **kw)


def _requests(cfg, max_new=12):
    rng = np.random.default_rng(7)
    return [Request(i, rng.integers(0, cfg.vocab_size, n), max_new=max_new)
            for i, n in enumerate(PROMPT_LENS)]


_BASELINE = {}      # fmt -> {rid: tokens}: NON-speculative reference


def _baseline(fmt):
    if fmt not in _BASELINE:
        cfg, params = _tiny()
        eng = ContinuousEngine(cfg, params, _scfg(fmt))
        _BASELINE[fmt] = eng.run(_requests(cfg))
    return _BASELINE[fmt]


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape)
                       .astype(np.float32)).astype(dtype)


# ---- cache primitives: batched write + exact rollback -------------------------


@pytest.mark.parametrize("fmt", FMTS)
def test_write_tokens_matches_sequential_write_token(fmt):
    """The S-row verify write lands bit-identical pool contents and
    lengths to S sequential decode writes (same RtN grid, same rows)."""
    B, S, KVH, D = 2, 5, 2, 32
    k, v = _rand((B, S, KVH, D), 1), _rand((B, S, KVH, D), 2)
    base = PagedKVCache.init(B, 32, KVH, D, fmt=fmt, page_size=8)
    perm = np.random.default_rng(0).permutation(np.arange(1, 9)).reshape(2, 4)
    base = dataclasses.replace(base, page_table=jnp.asarray(perm, jnp.int32),
                               lengths=jnp.asarray([3, 7], jnp.int32))
    blk = base.write_tokens(k, v)
    seq = base
    for t in range(S):
        seq = seq.write_token(k[:, t:t + 1], v[:, t:t + 1])
    np.testing.assert_array_equal(np.asarray(blk.lengths),
                                  np.asarray(seq.lengths))
    for a, b in zip((blk.k_codes, blk.k_scales, blk.v_codes, blk.v_scales),
                    (seq.k_codes, seq.k_scales, seq.v_codes, seq.v_scales)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_write_tokens_masked_slots_touch_nothing():
    """Masked-off slots (mid-chunked-prefill) write only the TRASH page
    and keep their length — their real pages are bit-untouched."""
    B, S, KVH, D = 2, 4, 2, 32
    k, v = _rand((B, S, KVH, D), 3), _rand((B, S, KVH, D), 4)
    base = PagedKVCache.init(B, 32, KVH, D, fmt="nvfp4", page_size=8)
    perm = np.random.default_rng(1).permutation(np.arange(1, 9)).reshape(2, 4)
    base = dataclasses.replace(base, page_table=jnp.asarray(perm, jnp.int32),
                               lengths=jnp.asarray([6, 9], jnp.int32))
    out = base.write_tokens(k, v, mask=jnp.asarray([True, False]))
    assert np.asarray(out.lengths).tolist() == [10, 9]
    live1 = np.asarray(perm[1])             # slot 1's pages: untouched
    np.testing.assert_array_equal(np.asarray(out.k_codes[live1]),
                                  np.asarray(base.k_codes[live1]))
    np.testing.assert_array_equal(np.asarray(out.v_codes[live1]),
                                  np.asarray(base.v_codes[live1]))


@pytest.mark.parametrize("fmt", FMTS)
def test_truncate_to_rollback_is_exact(fmt):
    """write k rows -> roll back r -> rewrite: the final cache is
    bit-identical to one that never saw the rejected rows (stale codes
    beyond ``lengths`` are invisible to dequant and overwritten in
    place by the next append)."""
    B, KVH, D, kk = 2, 2, 32, 4
    base = PagedKVCache.init(B, 32, KVH, D, fmt=fmt, page_size=8)
    base = dataclasses.replace(base,
                               page_table=jnp.asarray(
                                   np.arange(1, 9).reshape(2, 4), jnp.int32),
                               lengths=jnp.asarray([5, 11], jnp.int32))
    drafted = _rand((B, kk, KVH, D), 5), _rand((B, kk, KVH, D), 6)
    accepted = jnp.asarray([2, 4], jnp.int32)     # n_emit per slot
    rolled = base.write_tokens(*drafted).truncate_to(
        None, base.lengths + accepted)
    np.testing.assert_array_equal(np.asarray(rolled.lengths),
                                  np.asarray(base.lengths + accepted))
    # a cache that only ever appended the accepted rows reads identically
    ref = base
    for t in range(kk):
        m = accepted > t
        ref = ref.write_token(drafted[0][:, t:t + 1], drafted[1][:, t:t + 1],
                              mask=m)
    kd_r, vd_r = rolled.dequant(jnp.float32)
    kd_w, vd_w = ref.dequant(jnp.float32)
    for s in range(B):
        n = int(base.lengths[s] + accepted[s])
        np.testing.assert_array_equal(np.asarray(kd_r[s, :n]),
                                      np.asarray(kd_w[s, :n]))
        np.testing.assert_array_equal(np.asarray(vd_r[s, :n]),
                                      np.asarray(vd_w[s, :n]))
    # truncate can never extend
    again = rolled.truncate_to(None, rolled.lengths + 100)
    np.testing.assert_array_equal(np.asarray(again.lengths),
                                  np.asarray(rolled.lengths))


# ---- exactness: speculative == sequential, every format x draft depth ---------


@pytest.mark.parametrize("fmt", FMTS)
def test_spec_bit_identical_every_format(fmt):
    cfg, params = _tiny()
    want = _baseline(fmt)
    eng = ContinuousEngine(cfg, params, _scfg(fmt, spec_k=3, draft_layers=1))
    res = eng.run(_requests(cfg))
    assert set(res) == set(want)
    for rid in sorted(want):
        np.testing.assert_array_equal(
            res[rid], want[rid],
            err_msg=f"rid {rid} diverged under spec decoding fmt={fmt}")
    # the five-program contract: verify compiled once, plain decode NEVER
    assert eng.verify_compiles == 1
    assert eng.decode_compiles == 0
    assert eng.prefill_compiles == 1
    ms = eng.metrics.summary()
    acc = ms["spec_accepted_per_tick_slot"]
    assert acc["n"] > 0 and 1.0 <= acc["mean"] <= 3.0
    assert 0.0 <= ms["spec_acceptance_rate"]["mean"] <= 1.0


@pytest.mark.parametrize("draft_layers", (1, 2))
def test_spec_draft_depth_sweep_bit_identical(draft_layers):
    cfg, params = _tiny()
    want = _baseline("nvfp4")
    eng = ContinuousEngine(cfg, params,
                           _scfg("nvfp4", spec_k=4,
                                 draft_layers=draft_layers))
    res = eng.run(_requests(cfg))
    for rid in sorted(want):
        np.testing.assert_array_equal(res[rid], want[rid])


def test_spec_full_depth_draft_accepts_everything():
    """draft_layers == n_layers: the draft IS the target, so greedy
    verify agrees on every proposal — acceptance rate exactly 1.0 and
    k tokens per slot per verify tick (the speculative speedup
    ceiling, and the sharpest exactness probe: ANY draft/verify
    divergence would show up as acceptance < 1)."""
    cfg, params = _tiny()
    k = 4
    eng = ContinuousEngine(cfg, params,
                           _scfg("nvfp4", spec_k=k,
                                 draft_layers=cfg.n_layers))
    res = eng.run(_requests(cfg))
    want = _baseline("nvfp4")
    for rid in sorted(want):
        np.testing.assert_array_equal(res[rid], want[rid])
    ms = eng.metrics.summary()
    assert ms["spec_acceptance_rate"]["mean"] == 1.0
    assert ms["spec_accepted_per_tick_slot"]["mean"] == float(k)
    assert ms["spec_accepted_per_tick_slot"]["p99"] == float(k)


def test_spec_composes_with_chunked_prefill_and_prefix_cache():
    # baseline shares the admission path (suffix prefill attends THROUGH
    # quantized pages — a different, equally exact stream from the plain
    # prefill program); only spec on/off differs
    cfg, params = _tiny()
    want = ContinuousEngine(
        cfg, params, _scfg("nvfp4", prefill_chunk=5,
                           prefix_cache=True)).run(_requests(cfg))
    eng = ContinuousEngine(cfg, params,
                           _scfg("nvfp4", spec_k=3, draft_layers=1,
                                 prefill_chunk=5, prefix_cache=True))
    res = eng.run(_requests(cfg))
    for rid in sorted(want):
        np.testing.assert_array_equal(res[rid], want[rid])
    assert eng.verify_compiles == 1
    assert eng.chunk_compiles == 1
    assert eng.prefill_suffix_compiles == 1
    assert eng.prefill_compiles == 0 and eng.decode_compiles == 0
    assert eng.scheduler.pool.pages_in_use == \
        eng.scheduler.prefix_cache.cached_pages


def test_spec_metrics_reconcile_with_streams():
    """The accepted-tokens trajectory reconciles with the committed
    streams: every committed token beyond each request's prefill-sampled
    first one was emitted by a verify tick, and the only slack is the
    final tick's overshoot past max_new (at most k-1 per request, which
    ``commit`` clamps off the stream)."""
    cfg, params = _tiny()
    k = 3
    eng = ContinuousEngine(cfg, params, _scfg("nvfp4", spec_k=k,
                                              draft_layers=1))
    res = eng.run(_requests(cfg))
    met = eng.metrics
    committed = sum(len(t) for t in res.values())
    from_verify = committed - len(res)        # first tokens come from prefill
    assert from_verify <= sum(met.spec_accepted) \
        <= from_verify + len(res) * (k - 1)
    assert all(1 <= n <= k for n in met.spec_accepted)
    assert len(met.spec_accepted) == len(met.spec_rate)


# ---- config surface -----------------------------------------------------------


def test_spec_config_validation():
    cfg, params = _tiny()
    with pytest.raises(ValueError, match="spec_k"):
        ContinuousEngine(cfg, params, _scfg("nvfp4", spec_k=1))
    with pytest.raises(ValueError, match="draft_layers"):
        ContinuousEngine(cfg, params, _scfg("nvfp4", draft_layers=1))
    with pytest.raises(ValueError, match="draft_layers"):
        ContinuousEngine(cfg, params,
                         _scfg("nvfp4", spec_k=2,
                               draft_layers=cfg.n_layers + 1))
    with pytest.raises(NotImplementedError, match="greedy"):
        ContinuousEngine(cfg, params,
                         _scfg("nvfp4", spec_k=2, temperature=0.7))
    swa = dataclasses.replace(cfg, sliding_window=16)
    with pytest.raises(NotImplementedError, match="SWA"):
        ContinuousEngine(swa, registry.init_params(swa, jax.random.PRNGKey(0)),
                         _scfg("nvfp4", spec_k=2))


def test_spec_rejects_teacher_forcing():
    cfg, params = _tiny()
    eng = ContinuousEngine(cfg, params, _scfg("nvfp4", spec_k=2))
    reqs = _requests(cfg)
    with pytest.raises(NotImplementedError, match="forced"):
        eng.run(reqs, forced={0: np.zeros(4, np.int32)})


# ---- partial-suffix preemption: resume mid-stream, bit-identical --------------


@pytest.mark.parametrize("extra", ({}, {"spec_k": 3, "draft_layers": 1}),
                         ids=("plain", "spec"))
def test_partial_suffix_preemption_resumes_bit_identical(extra):
    """An 8-page pool forces preemption mid-decode.  With the prefix
    cache on, the victim's computed pages are adopted and it resumes
    from its partial stream (prefilling only the suffix) — the final
    streams are bit-identical to an unconstrained pool, spec and
    non-spec.  The requeued effective prompt carries written + 1 tokens
    (the last committed token's row is written by the resume prefill)."""
    cfg, params = _tiny()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (33, 37)]

    def run(total_pages):
        eng = ContinuousEngine(
            cfg, params, _scfg("nvfp4", total_pages=total_pages,
                               prefix_cache=True, **extra))
        res = eng.run([Request(i, p, max_new=24) for i, p in
                       enumerate(prompts)])
        return res, eng

    want, _ = run(None)
    got, eng = run(8)
    sched = eng.scheduler
    assert sched.stats["preemptions"] >= 1
    for rid in (0, 1):
        np.testing.assert_array_equal(
            got[rid], want[rid],
            err_msg=f"rid {rid} diverged across preemption")
    # jit caches still exactly one entry each — preemption/resume reuses
    # the compiled programs
    if extra:
        assert eng.verify_compiles == 1 and eng.decode_compiles == 0
    else:
        assert eng.decode_compiles == 1
    assert eng.prefill_suffix_compiles == 1
    assert sched.active_slots() == []
    assert sched.pool.pages_in_use == sched.prefix_cache.cached_pages


# ---- lifecycle: cancel/expire/preempt across the draft/verify cycle -----------


@settings(max_examples=8, deadline=None)
@given(abort_tick=st.integers(min_value=0, max_value=5),
       accepted_seed=st.integers(min_value=0, max_value=7))
def test_spec_lifecycle_conservation_at_any_stage(abort_tick, accepted_seed):
    """Host-side sweep of the spec-mode scheduler protocol
    (ensure_capacity(k, advance=False) -> advance_written(n) -> commit)
    with a victim aborted at every tick and RANDOM accepted lengths
    1..k per slot per tick.  After every tick: pool refcounts conserve
    (free + in_use == usable), no live row aliases a page or holds
    TRASH inside its allocated prefix, and at the end nothing leaks."""
    k = 3
    sched = Scheduler(n_slots=2, max_len=32, page_size=4)
    usable = sched.total_pages - 1
    rng = np.random.default_rng(accepted_seed)
    sched.submit(Request(0, np.arange(10, dtype=np.int32), max_new=6))
    sched.submit(Request(1, np.arange(9, dtype=np.int32), max_new=6,
                         abort_at=abort_tick))
    sched.submit(Request(2, np.arange(8, dtype=np.int32), max_new=5,
                         arrival=1))
    for tick in range(40):
        sched.expire(tick)
        sched.admit(tick)
        active = sched.decoding_slots()
        sched.ensure_capacity(k if active else 0, advance=False)
        for slot in list(active):
            if sched.slots[slot] is None:       # preempted this tick
                continue
            n = int(rng.integers(1, k + 1))
            sched.advance_written(slot, n)
            sched.commit(slot, np.full((n,), 7, np.int32), NO_EOS)
        assert sched.pool.free_pages + sched.pool.pages_in_use == usable
        live = []
        for slot in sched.active_slots():
            row = sched._rows[slot]
            npg = sched._npages[slot]
            assert (row[:npg] != TRASH_PAGE).all()
            assert (row[npg:] == TRASH_PAGE).all()
            live += [p for p in row.tolist() if p != TRASH_PAGE]
        assert len(live) == len(set(live))
        if not sched.has_work():
            break
    assert not sched.has_work()
    assert sched.pool.pages_in_use == 0
    assert set(sched.results) | set(sched.cancelled) == {0, 1, 2}
    assert set(sched.results) & set(sched.cancelled) == set()


def test_spec_abort_and_timeout_mid_run_no_leak():
    """Engine-level: an abort and a timeout landing while spec decoding
    is live leak nothing and never perturb the survivor's stream."""
    cfg, params = _tiny()
    scfg = _scfg("nvfp4", spec_k=3, draft_layers=1)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (20, 18, 24)]
    eng = ContinuousEngine(cfg, params, scfg)
    res = eng.run([Request(0, prompts[0], max_new=16, abort_at=3),
                   Request(1, prompts[1], max_new=10),
                   Request(2, prompts[2], max_new=8, timeout=4,
                           arrival=1)])
    sched = eng.scheduler
    assert 0 in sched.cancelled and sched.cancelled[0]["reason"] == "abort"
    assert sched.pool.pages_in_use == 0
    solo = ContinuousEngine(cfg, params, scfg).run(
        [Request(1, prompts[1], max_new=10)])
    np.testing.assert_array_equal(res[1], solo[1])
