"""Observability (PR 10): the tracer changes NOTHING but what you can see.

Layers of evidence:
  * EXACTNESS: with a live tracer attached, the continuous engine's
    token streams are BIT-identical to an untraced run across every KV
    format (nvfp4/fp8/bf16), with speculative decoding + chunked
    prefill + the prefix cache all composed — and the five-program jit
    caches stay at one entry each (tracing is host-side only; fp4lint's
    obs-in-jit rule enforces that statically);
  * span balance: every request span opened at submit is closed by
    done/cancel — abort/timeout at EVERY lifecycle stage included —
    and preemption keeps the span open (the resumed request is the
    same request);
  * counter conservation: the tracer's page counters reconcile with
    the page pool at drain, and its sched_* counters agree with the
    scheduler's own stats dict;
  * the exporter: round-trips valid Chrome trace-event JSON (required
    keys, known phases, numeric timestamps, metadata-first ordering);
  * train telemetry: the trainer's √3-floor series lands exactly one
    entry per logged step, with per-layer ratio gauges for every
    parameter leaf and rounding/scale-health tallies alongside.
"""
import json

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.obs import (NULL_TRACER, Counters, Tracer, load_trace,
                       validate_events)
from repro.serve.metrics import MetricsRecorder
from repro.serve.scheduler import Request, Scheduler

FMTS = ("nvfp4", "fp8", "bf16")
NO_EOS = -1

_STATE = {}


def _tiny():
    if "cfg" not in _STATE:
        import jax
        from repro.configs import get_config
        from repro.models import registry
        _STATE["cfg"] = get_config("llama2-60m").smoke()
        _STATE["params"] = registry.init_params(_STATE["cfg"],
                                                jax.random.PRNGKey(0))
    return _STATE["cfg"], _STATE["params"]


# ---- tracer core (jax-free) ---------------------------------------------------


def test_tracer_simulated_clock_and_span_accounting():
    trc = Tracer(clock="tick", process="t")
    trc.set_time(5)
    trc.begin("req:0", "request", plen=7)
    trc.instant("req:0", "admit")
    trc.counter("pages", 3)
    trc.gauge("depth", 2)
    trc.set_time(9)
    trc.end("req:0", "request")
    evs = [e for e in trc.trace_events() if e["ph"] != "M"]
    assert [e["ts"] for e in evs] == [5, 5, 5, 5, 9]
    assert trc.spans_opened == 1 and trc.spans_closed == 1
    assert trc.open_spans() == {}
    assert trc.counters["pages"] == 3 and trc.gauges["depth"] == 2


def test_span_context_manager_balances_on_error():
    trc = Tracer()
    with pytest.raises(RuntimeError):
        with trc.span("t", "work"):
            raise RuntimeError("boom")
    assert trc.open_spans() == {}


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    NULL_TRACER.set_time(3)
    NULL_TRACER.begin("t", "x")
    NULL_TRACER.gauge("g", 1.0)
    with NULL_TRACER.span("t", "y"):
        pass
    assert NULL_TRACER.counter("n", 5) == 0
    assert NULL_TRACER.n_events == 0 and NULL_TRACER.trace_events() == []
    with pytest.raises(RuntimeError):
        NULL_TRACER.export("/dev/null")
    # untraced host objects hold the shared singleton, not None
    assert Scheduler(n_slots=1, max_len=16, page_size=4).tracer \
        is NULL_TRACER
    assert MetricsRecorder().tracer is NULL_TRACER


def test_counters_substrate_mapping_protocol():
    c = Counters({"a": 1})
    c.inc("a", 2)
    c.inc("b")
    c.set("a", 5)
    assert dict(c) == {"a": 5, "b": 1}
    assert c["a"] == 5 and c.get("zzz") == 0 and "b" in c and len(c) == 2
    assert sorted(c.keys()) == ["a", "b"]
    c.clear()
    assert dict(c) == {}


def test_metrics_recorder_on_counter_substrate():
    rec = MetricsRecorder(tracer=Tracer())
    rec.submitted(0, arrival=0, deadline=None)
    rec.admitted(0, 1)
    rec.first_token(0, 2)
    rec.finished(0, 4, ntokens=3)
    assert dict(rec.lifecycle) == {"submitted": 1, "admitted": 1,
                                   "first_tokens": 1, "finished": 1}
    rec.set_counters({"admitted": 1, "completed": 1})
    assert isinstance(rec.counters, Counters)
    assert dict(rec.counters) == {"admitted": 1, "completed": 1}
    # percentile semantics survive the rebase: summary shape unchanged
    s = rec.summary()
    assert s["ttft_ticks"]["p50"] == 2 and s["completed"] == 1
    assert s["counters"] == {"admitted": 1, "completed": 1}
    # and the tracer saw the lifecycle as events
    names = {e["name"] for e in rec.tracer.trace_events()}
    assert {"met_submitted", "met_finished", "first_token"} <= names


# ---- lifecycle sweep: span balance at every abort stage (jax-free) ------------


@settings(max_examples=8, deadline=None)
@given(abort_tick=st.integers(min_value=0, max_value=6))
def test_lifecycle_span_balance_at_any_stage(abort_tick):
    """A victim aborted at every possible tick of its life — queued,
    mid-chunked-prefill, decoding, or already finished: every request
    span still closes exactly once, the tracer's sched_* counters agree
    with the scheduler's stats, and the page counters conserve."""
    trc = Tracer(clock="tick")
    sched = Scheduler(n_slots=2, max_len=32, page_size=4, prefill_chunk=3,
                      tracer=trc)
    sched.submit(Request(0, np.arange(10, dtype=np.int32), max_new=4))
    sched.submit(Request(1, np.arange(9, dtype=np.int32), max_new=4,
                         abort_at=abort_tick))
    sched.submit(Request(2, np.arange(8, dtype=np.int32), max_new=3,
                         arrival=1))
    for tick in range(30):
        sched.expire(tick)
        sched.admit(tick)
        sched.prefill_work(tick)
        T = sched.tick_steps(2)
        sched.ensure_capacity(T)
        if T:
            for slot in sched.decoding_slots():
                sched.commit(slot, np.full((T,), 7, np.int32), NO_EOS)
        if not sched.has_work():
            break
    assert not sched.has_work()
    assert trc.spans_opened == 3            # one span per submitted request
    assert trc.spans_closed == 3
    assert trc.open_spans() == {}
    c = trc.counters
    assert c.get("sched_admitted") == sched.stats["admitted"]
    assert c.get("sched_completed") == sched.stats["completed"]
    assert c.get("sched_cancelled") == sched.stats["cancelled"]
    assert c.get("sched_completed") + c.get("sched_cancelled") == 3
    alloc = (c.get("pages_private") + c.get("pages_shared")
             + c.get("pages_demand"))
    assert alloc == c.get("pages_released")
    assert sched.pool.pages_in_use == 0
    # events are schema-valid without an export round-trip
    assert validate_events(trc.trace_events()) == []


def test_preemption_keeps_request_span_open():
    trc = Tracer(clock="tick")
    sched = Scheduler(n_slots=1, max_len=32, page_size=4,
                      prefix_cache=True, tracer=trc)
    sched.submit(Request(7, np.arange(8, dtype=np.int32), max_new=12))
    sched.admit(0)
    sched.commit(0, np.asarray([9], np.int32), NO_EOS)
    sched._preempt(0)
    assert trc.open_spans() == {("req:7", "request"): 1}
    assert trc.counters.get("sched_preempted") == 1
    # resume and finish: the SAME span closes (no second begin)
    sched.admit(1)
    while sched.has_work():
        T = sched.tick_steps(4)
        sched.ensure_capacity(T)
        for slot in list(sched.decoding_slots()):
            sched.commit(slot, np.full((max(T, 1),), 9, np.int32), NO_EOS)
    assert trc.spans_opened == 1 and trc.open_spans() == {}


# ---- exporter round-trip ------------------------------------------------------


def test_export_round_trip_chrome_schema(tmp_path):
    trc = Tracer(clock="tick", process="unit")
    trc.set_time(1)
    trc.begin("req:0", "request")
    trc.counter("pages", 2)
    trc.instant("req:0", "admit", slot=0)
    trc.gauge("depth", 3.5)
    trc.end("req:0", "request")
    path = str(tmp_path / "trace.json")
    assert trc.export(path) == path
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["otherData"] == {"clock": "tick", "process": "unit"}
    events = load_trace(path)
    assert validate_events(events) == []
    assert len(events) == len(trc.trace_events())
    phases = [e["ph"] for e in events]
    assert phases.count("B") == 1 and phases.count("E") == 1
    assert phases.count("C") == 2 and phases.count("i") == 1
    # metadata first: process_name, then thread_name per track
    assert events[0]["name"] == "process_name"
    inst = next(e for e in events if e["ph"] == "i")
    assert inst["s"] == "t" and inst["args"]["slot"] == 0
    # the bare-array form loads too
    bare = str(tmp_path / "bare.json")
    with open(bare, "w") as f:
        json.dump(trc.trace_events(), f)
    assert load_trace(bare) == events


def test_validate_events_flags_bad_events():
    assert validate_events([{"name": "x", "ph": "B", "ts": 0, "pid": 1,
                             "tid": 1}]) == []
    probs = validate_events([
        {"ph": "B", "ts": 0, "pid": 1, "tid": 1},          # missing name
        {"name": "x", "ph": "Z", "ts": 0, "pid": 1, "tid": 1},
        {"name": "x", "ph": "B", "ts": "later", "pid": 1, "tid": 1},
        "not an event"])
    assert len(probs) == 4


# ---- the engine: tracer on == tracer off, bit for bit -------------------------


_BASELINE = {}


def _requests(cfg, max_new=10):
    rng = np.random.default_rng(7)
    return [Request(i, rng.integers(0, cfg.vocab_size, n), max_new=max_new)
            for i, n in enumerate((33, 12, 37))]


@pytest.mark.parametrize("fmt", FMTS)
def test_tracer_on_off_bit_identical_full_compose(fmt):
    """Speculative decoding + chunked prefill + prefix cache, with and
    without a tracer: identical tokens, identical jit-cache guards."""
    from repro.serve import ContinuousEngine, ServeConfig
    cfg, params = _tiny()

    def scfg():
        return ServeConfig(batch_size=2, max_len=96, eos_id=NO_EOS,
                           kv_cache_format=fmt, page_size=16,
                           spec_k=3, draft_layers=1, prefill_chunk=5,
                           prefix_cache=True)

    if fmt not in _BASELINE:
        _BASELINE[fmt] = ContinuousEngine(cfg, params,
                                          scfg()).run(_requests(cfg))
    want = _BASELINE[fmt]
    trc = Tracer(clock="tick")
    eng = ContinuousEngine(cfg, params, scfg(), tracer=trc)
    res = eng.run(_requests(cfg))
    for rid in sorted(want):
        np.testing.assert_array_equal(res[rid], want[rid])
    # the five-program contract holds with the tracer attached
    assert eng.verify_compiles == 1
    assert eng.chunk_compiles == 1
    assert eng.prefill_suffix_compiles == 1
    assert eng.prefill_compiles == 0 and eng.decode_compiles == 0
    # and the trace itself is balanced and schema-valid
    assert trc.spans_opened == trc.spans_closed
    assert trc.open_spans() == {}
    assert trc.counters.get("sched_completed") == len(want)
    assert trc.counters.get("jit_compiles") == 3
    names = {e["name"] for e in trc.trace_events()}
    assert {"request", "tick", "jit_compile", "first_token"} <= names
    assert validate_events(trc.trace_events()) == []


# ---- train telemetry: one √3-series entry per logged step ---------------------


def test_trainer_sqrt3_series_one_entry_per_logged_step():
    import jax
    from repro.core import fqt
    from repro.train import TrainConfig, Trainer, TrainerConfig
    from repro.data.pipeline import DataConfig
    cfg, _ = _tiny()
    trc = Tracer(clock="step", process="train")
    trainer = Trainer(
        cfg, fqt.nvfp4_paper_config(), TrainConfig(remat=False),
        TrainerConfig(total_steps=6, log_every=2, ckpt_every=100),
        DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4),
        tracer=trc)
    assert trainer.tcfg.layer_stats        # auto-enabled by the live tracer
    state = trainer.run(jax.random.PRNGKey(0))
    logged = [0, 2, 4]                     # steps where step % log_every == 0
    evs = trc.trace_events()
    gnr = [e for e in evs if e["ph"] == "C" and e["name"] == "gnr"]
    assert [e["ts"] for e in gnr] == logged    # exactly one per logged step
    # per-layer ratio gauges: one per parameter leaf per logged step
    n_leaves = len(jax.tree.leaves(state.params))
    ratios = [e for e in evs
              if e["ph"] == "C" and e["name"].startswith("ratio")]
    assert len(ratios) == n_leaves * len(logged)
    # rounding tallies reflect the paper's mixed SR/RtN placement
    c = trc.counters
    assert c.get("rounding_sr_points") > 0
    assert c.get("rounding_rtn_points") > 0
    # scale health probed the forward weight spec each logged step
    assert c.get("scale_blocks") > 0
    assert c.get("scale_saturated") >= 0 and c.get("scale_underflow") >= 0
    assert validate_events(evs) == []


def test_trainer_without_tracer_keeps_layer_stats_off():
    from repro.core import fqt
    from repro.train import TrainConfig, Trainer, TrainerConfig
    from repro.data.pipeline import DataConfig
    cfg, _ = _tiny()
    trainer = Trainer(
        cfg, fqt.nvfp4_paper_config(), TrainConfig(remat=False),
        TrainerConfig(total_steps=1, ckpt_every=100),
        DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4))
    assert trainer.tracer is NULL_TRACER
    assert not trainer.tcfg.layer_stats
