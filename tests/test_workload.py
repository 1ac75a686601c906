"""Workload generator + simulated-clock metrics + bench-JSON merge (PR 8).

Host-side properties (no engine, no jax):
  * DETERMINISM: the same ``WorkloadConfig`` produces the same trace
    byte-for-byte (``trace_fingerprint``); a different seed does not;
  * TENANT ISOLATION: each tenant draws from its own child PRNG stream,
    so appending a tenant never perturbs another tenant's trace;
  * trace shape: sequential rids in (arrival, tenant, intra-tick) order,
    shared system prompts, prompt-length mixtures, burst overlays, and
    the deadline/abort_at/timeout arithmetic;
  * statistical sanity of the Poisson arrivals and the length mixture
    (seeded draws — the bounds are loose but the numbers never move);
  * nearest-rank percentile math + the MetricsRecorder lifecycle
    arithmetic (TTFT/TPOT/goodput, preemption-stable first-token);
  * benchmarks.run._merge_bench_json replaces groups at GROUP
    granularity and never clobbers the rest of BENCH_serve.json.
"""
import os
import sys

import numpy as np
import pytest

from repro.serve import (TenantSpec, WorkloadConfig, as_requests,
                         generate_workload, trace_fingerprint)
from repro.serve.metrics import (MetricsRecorder, percentile,
                                 percentile_summary)
from repro.serve.scheduler import Request

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
from benchmarks.run import _merge_bench_json  # noqa: E402


def _two_tenants(seed=0, ticks=16):
    return WorkloadConfig(tenants=(
        TenantSpec("chat", rate=0.6, prompt_lens=(4, 8),
                   system_prompt_len=8, max_new=6, deadline_slack=20),
        TenantSpec("batch", rate=0.3, prompt_lens=(16,), max_new=4,
                   abort_prob=0.5, abort_after=3, timeout=24),
    ), ticks=ticks, seed=seed, vocab=128)


# ---- determinism ---------------------------------------------------------------


def test_trace_is_deterministic_byte_for_byte():
    w = _two_tenants(seed=11)
    assert trace_fingerprint(generate_workload(w)) == \
        trace_fingerprint(generate_workload(w))


def test_seed_changes_the_trace():
    a = trace_fingerprint(generate_workload(_two_tenants(seed=1)))
    b = trace_fingerprint(generate_workload(_two_tenants(seed=2)))
    assert a != b


def test_tenant_streams_are_isolated():
    """Appending a tenant must not perturb the existing tenants' events
    (child streams are keyed by (seed, tenant index), not shared)."""
    base = generate_workload(_two_tenants(seed=5))
    extended = generate_workload(WorkloadConfig(
        tenants=_two_tenants(seed=5).tenants + (
            TenantSpec("extra", rate=1.5, prompt_lens=(2,)),),
        ticks=16, seed=5, vocab=128))

    def key(e):
        return (e.tenant, e.arrival, e.max_new, e.deadline, e.abort_at,
                e.timeout, e.prompt.tobytes())

    for name in ("chat", "batch"):
        assert [key(e) for e in base if e.tenant == name] == \
            [key(e) for e in extended if e.tenant == name]


# ---- trace shape ---------------------------------------------------------------


def test_rids_sequential_and_arrivals_sorted():
    evs = generate_workload(_two_tenants(seed=3))
    assert [e.rid for e in evs] == list(range(len(evs)))
    arr = [e.arrival for e in evs]
    assert arr == sorted(arr)


def test_system_prompt_shared_within_tenant():
    evs = [e for e in generate_workload(_two_tenants(seed=4))
           if e.tenant == "chat"]
    assert len(evs) >= 2          # seeded: the chat tenant does arrive
    sys_tok = evs[0].prompt[:8]
    for e in evs:
        np.testing.assert_array_equal(e.prompt[:8], sys_tok)
        assert len(e.prompt) - 8 in (4, 8)     # body from the mixture


def test_burst_overlay_fires_on_schedule():
    evs = generate_workload(WorkloadConfig(tenants=(
        TenantSpec("bursty", rate=0.0, prompt_lens=(4,),
                   burst_every=4, burst_size=2),), ticks=8, seed=0))
    assert len(evs) == 4                       # ticks 0 and 4, 2 each
    assert sorted(e.arrival for e in evs) == [0, 0, 4, 4]


def test_lifecycle_field_arithmetic():
    evs = generate_workload(WorkloadConfig(tenants=(
        TenantSpec("t", rate=1.0, prompt_lens=(4,), deadline_slack=10,
                   abort_prob=1.0, abort_after=3, timeout=7),),
        ticks=8, seed=2))
    assert evs
    for e in evs:
        assert e.deadline == e.arrival + 10
        assert e.abort_at == e.arrival + 3     # abort_prob == 1
        assert e.timeout == 7
    calm = generate_workload(WorkloadConfig(tenants=(
        TenantSpec("t", rate=1.0, prompt_lens=(4,)),), ticks=8, seed=2))
    assert all(e.deadline is None and e.abort_at is None
               and e.timeout is None for e in calm)


def test_as_requests_is_a_faithful_mapping():
    evs = generate_workload(_two_tenants(seed=6))
    reqs = as_requests(evs)
    assert all(isinstance(r, Request) for r in reqs)
    for e, r in zip(evs, reqs):
        assert (r.rid, r.max_new, r.arrival) == (e.rid, e.max_new, e.arrival)
        assert (r.deadline, r.abort_at, r.timeout) == \
            (e.deadline, e.abort_at, e.timeout)
        np.testing.assert_array_equal(r.prompt, e.prompt)


# ---- statistical sanity (seeded: loose bounds, frozen numbers) -----------------


def test_poisson_rate_sanity():
    evs = generate_workload(WorkloadConfig(tenants=(
        TenantSpec("t", rate=0.5, prompt_lens=(4,)),), ticks=400, seed=9))
    assert 120 <= len(evs) <= 280              # mean 200, sigma ~14


def test_prompt_mixture_respects_probs():
    evs = generate_workload(WorkloadConfig(tenants=(
        TenantSpec("t", rate=1.0, prompt_lens=(4, 32),
                   prompt_probs=(0.9, 0.1)),), ticks=200, seed=10))
    short = sum(1 for e in evs if len(e.prompt) == 4)
    assert len(evs) > 50
    assert short / len(evs) > 0.7              # 0.9 nominal, loose bound


# ---- validation ----------------------------------------------------------------


def test_spec_validation_errors():
    with pytest.raises(ValueError, match="rate"):
        TenantSpec("t", rate=-0.1)
    with pytest.raises(ValueError, match="prompt_lens"):
        TenantSpec("t", prompt_lens=())
    with pytest.raises(ValueError, match="prompt_probs"):
        TenantSpec("t", prompt_lens=(4, 8), prompt_probs=(1.0,))
    with pytest.raises(ValueError, match="abort_prob"):
        TenantSpec("t", abort_prob=1.5)
    with pytest.raises(ValueError, match="tenant"):
        WorkloadConfig(tenants=())
    with pytest.raises(ValueError, match="tick"):
        WorkloadConfig(tenants=(TenantSpec("t"),), ticks=0)


# ---- nearest-rank percentiles --------------------------------------------------


def test_percentile_nearest_rank():
    vals = [10, 20, 30, 40]
    assert percentile(vals, 50) == 20          # ceil(.5*4) = 2nd smallest
    assert percentile(vals, 75) == 30
    assert percentile(vals, 95) == 40
    assert percentile(vals, 100) == 40         # p100 is the max
    assert percentile([7], 99) == 7
    assert np.isnan(percentile([], 50))
    with pytest.raises(ValueError, match="percentile"):
        percentile(vals, 0)
    with pytest.raises(ValueError, match="percentile"):
        percentile(vals, 101)


def test_percentile_summary_shape():
    s = percentile_summary([1.0, 2.0, 3.0])
    assert set(s) == {"p50", "p95", "p99", "mean", "max", "n"}
    assert s["n"] == 3 and s["max"] == 3.0 and s["p50"] == 2.0
    empty = percentile_summary([])
    assert empty["n"] == 0 and np.isnan(empty["p50"])


# ---- MetricsRecorder lifecycle arithmetic --------------------------------------


def test_recorder_ttft_tpot_goodput():
    m = MetricsRecorder()
    # rid 0: arrival 0, first token tick 3, done tick 7 with 5 tokens,
    # deadline 10 (met).  rid 1: arrival 2, first tick 6, done tick 10
    # with 3 tokens, deadline 8 (missed).  rid 2: cancelled while queued.
    m.submitted(0, 0, deadline=10)
    m.submitted(1, 2, deadline=8)
    m.submitted(2, 4)
    m.admitted(0, 1)
    m.first_token(0, 3)
    m.finished(0, 7, 5)
    m.admitted(1, 4)
    m.first_token(1, 6)
    m.finished(1, 10, 3)
    m.cancelled(2, 5, "queued", "timeout")
    assert sorted(m.ttfts()) == [3, 4]
    assert sorted(m.tpots()) == [1.0, 2.0]     # (7-3)/4, (10-6)/2
    assert m.goodput() == pytest.approx(1 / 3)  # rid 0 only, of 3 submitted
    s = m.summary()
    assert s["submitted"] == 3 and s["completed"] == 2 \
        and s["cancelled"] == 1
    assert s["ttft_ticks"]["p50"] == 3 and s["ttft_ticks"]["max"] == 4
    assert s["tpot_ticks"]["p99"] == 2.0


def test_recorder_preemption_keeps_first_emission():
    """Preemption replays the identical stream, so the FIRST admission
    and first-token ticks stand — re-admission never moves them."""
    m = MetricsRecorder()
    m.submitted(0, 0)
    m.admitted(0, 1)
    m.first_token(0, 2)
    m.admitted(0, 5)                           # re-admission after preempt
    m.first_token(0, 6)                        # replayed first token
    m.finished(0, 8, 4)
    assert m.requests[0]["admitted"] == 1
    assert m.ttfts() == [2]


def test_recorder_no_deadline_counts_as_on_time():
    m = MetricsRecorder()
    m.submitted(0, 0)
    m.first_token(0, 1)
    m.finished(0, 3, 2)
    assert m.goodput() == 1.0
    assert MetricsRecorder().goodput() == 0.0  # empty trace


# ---- BENCH_serve.json group-level merge ----------------------------------------


def test_merge_bench_json_is_group_granular():
    existing = {"benches": {"kv_cache": {"a": 1.0}, "traffic": {"old": 2.0}},
                "generated_by": "benchmarks.run --json",
                "custom_note": "keep me"}
    out = _merge_bench_json(existing, {"traffic": {"ttft_ticks_p50": 3.0},
                                       "lint": {"rules": 9.0}})
    assert out["benches"]["kv_cache"] == {"a": 1.0}          # untouched
    assert out["benches"]["traffic"] == {"ttft_ticks_p50": 3.0}  # replaced
    assert out["benches"]["lint"] == {"rules": 9.0}          # added
    assert out["custom_note"] == "keep me"                   # kept verbatim
    assert out["generated_by"] == "benchmarks.run --json"
    # a fresh/unreadable artifact degenerates to just the new groups
    fresh = _merge_bench_json({}, {"traffic": {"x": 1.0}})
    assert fresh["benches"] == {"traffic": {"x": 1.0}}


def test_failing_bench_sets_exit_code(monkeypatch, capsys):
    """A bench that raises is reported as an ERROR row AND makes
    ``benchmarks.run`` exit non-zero; the other benches still run."""
    from benchmarks import run

    def boom():
        raise ValueError("boom")

    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setitem(run.BENCHES, "lint", boom)
    assert run.main(["--bench", "lint"]) == 1
    assert "lint,ERROR,ValueError: boom" in capsys.readouterr().out
    monkeypatch.setitem(run.BENCHES, "lint", lambda: [("g", "k", 1.0)])
    assert run.main(["--bench", "lint"]) == 0
