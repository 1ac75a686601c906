"""The stdlib trace decoder: device time by scope path, idle time by host
span, and the readers built on them.

``data/small.xplane.pb`` is the trace described in test_bench_xplane.py:
five calls of ``jit(f)``, ``tanh(a @ b) * 2 + 1``, recorded on a TPU v5e,
with no scopes of the program's.
"""
import collections
import pathlib
import sys
import time

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import scopes  # noqa: E402
import xplane  # noqa: E402

TRACE = HERE / "data" / "small.xplane.pb"
PS = 10 ** 12          # picoseconds a second


@pytest.fixture(scope="module")
def red():
    return scopes.reduce(TRACE)


def test_fusion_carries_its_scope_path_and_copies_none(red):
    paths = {}
    for module, label, path, _ in red["ops"]:
        assert module == "jit_f"
        paths[label.split(" ")[0]] = path
    assert paths["fusion"] == "jit(f)/dot_general"
    assert paths["copy-start"] == "" and paths["copy-done"] == ""


def test_device_seconds_agree_with_reduce(red):
    old = xplane.reduce(TRACE)
    assert red["devices"] == old["devices"] == 1
    assert red["busy_s"] == pytest.approx(old["busy_s"], rel=1e-3)
    by_label = {}
    for _, label, _, s in red["ops"]:
        by_label[label] = by_label.get(label, 0.0) + s
    assert by_label.keys() == old["op_s"].keys()
    for k, v in old["op_s"].items():
        assert by_label[k] == pytest.approx(v, rel=1e-3, abs=1e-8)
    assert red["module_s"]["jit_f"] == pytest.approx(
        xplane.module_time(old, "jit_f")[0], rel=1e-3)


def test_small_trace_gaps_lie_outside_any_program_span(red):
    # the host slept 10 ms between calls and opened no engine span
    assert set(red["gap_s"]) == {""} and red["spans"] == 0
    assert 0.04 < red["gap_s"][""] < 0.06


def test_scope_path_drops_the_op_type():
    assert scopes.scope_path("jit(f)/dot_general:") == "jit(f)/dot_general"
    assert scopes.scope_path("jit(f)/x:Foo") == "jit(f)/x"
    assert scopes.scope_path(None) == ""


def test_innermost_scope_sees_through_transforms():
    path = "jit(step)/transpose(jvp(fqt.q.bwd_g))/abs"
    assert scopes.innermost_scope(path) == "fqt.q.bwd_g"
    assert scopes.innermost_scope(
        "jit(d)/fqt.dot.fwd/fqt.q.fwd_a/mul") == "fqt.q.fwd_a"
    assert scopes.innermost_scope("jit(f)/fqt.q.fwd_ab/x") == ""


def test_gaps_split_by_innermost_span():
    spans = [("engine.tick", 0, 100 * PS), ("engine.admit", 10 * PS, 30 * PS),
             ("engine.sync", 50 * PS, 90 * PS),
             ("engine.tick", 100 * PS, 200 * PS)]
    gaps = [(5 * PS, 15 * PS), (40 * PS, 60 * PS), (95 * PS, 105 * PS),
            (195 * PS, 220 * PS)]
    got = scopes.split_gaps(gaps, spans)
    assert got == pytest.approx({"engine.tick": 5 + 10 + 10 + 5,
                                 "engine.admit": 5, "engine.sync": 10,
                                 "": 20})
    assert scopes.split_gaps(gaps, []) == pytest.approx({"": 65})


def _synthetic():
    rows = [["jit__decode_impl", "fusion a", "jit(d)/packed.dequant/mul", 2.0],
            ["jit__decode_impl", "fusion b", "jit(d)/kv.read/gather", 1.0],
            ["jit__decode_impl", "scatter c", "jit(d)/while/kv.write/x", 0.5],
            ["jit__decode_impl", "copy d", "", 0.5],
            ["jit__prefill_impl", "fusion e", "jit(p)/packed.dequant/mul",
             7.0],
            ["jit_train_step", "fusion f",
             "jit(s)/transpose(jvp(fqt.q.upd_g))/abs", 3.0],
            ["jit_train_step", "fusion g", "jit(s)/fqt.dot.fwd/dot", 5.0]]
    return {"devices": 1, "busy_s": 20.0, "ops": rows, "gap_s": {},
            "module_s": {"jit__decode_impl": 5.0, "jit__prefill_impl": 7.0,
                         "jit_train_step": 8.0}}


def test_readers_on_a_scoped_reduction():
    ctx = {"scopes": _synthetic()}
    assert scopes.quant_share_train(ctx) == pytest.approx(15.0)
    assert scopes.dequant_share_decode(ctx) == pytest.approx(40.0)
    assert scopes.kv_share_decode(ctx) == pytest.approx(30.0)
    by = scopes.by_scope(ctx["scopes"], "decode")
    assert by == pytest.approx({"packed.dequant": 2.0, "kv.read": 1.0,
                                "kv.write": 0.5, "": 0.5})
    top = scopes.top_labels(ctx["scopes"], 2)
    assert top == [["fusion e", 7.0, 7.0], ["fusion g", 5.0, 5.0]]


@pytest.mark.parametrize("name", sorted(scopes.READERS))
def test_each_reader_is_silent_without_program_scopes(name, red):
    """A trace of a program without scopes (the small trace), or no trace
    at all, reads None: the metric is left out of the line."""
    read = scopes.READERS[name]
    assert read({"scopes": red, "tick_phases": {}}) is None
    assert read({}) is None


def _engine_events():
    from repro.obs.trace import Tracer
    trc = Tracer(wall=True)
    for tick in range(3):
        trc.set_time(tick)
        trc.begin("engine", "tick")
        trc.begin("req:0", "request")
        for phase in ("expire", "admit", "decode", "sync", "commit"):
            trc.begin("engine", phase)
            if phase == "sync":
                time.sleep(0.004)
            trc.end("engine", phase)
        trc.end("req:0", "request")
        trc.end("engine", "tick")
    return trc.trace_events()


def test_tick_phases_and_host_ms():
    phases = scopes.tick_phases(_engine_events())
    assert sorted(phases) == [0, 1, 2]
    for t in phases.values():
        assert set(t) == {"tick", "expire", "admit", "decode", "sync",
                          "commit"}
        assert t["tick"] >= t["sync"] >= 0.004
    host = scopes.host_ms_serve({"tick_phases": phases})
    want = 1e3 * sum(phases[k]["tick"] - phases[k]["sync"]
                     for k in (1, 2)) / 2
    assert host == pytest.approx(want)
    assert 0 <= host < 4


def test_profiler_mirror_reaches_the_trace(tmp_path):
    """A live tracer puts the engine's tick and phase spans on the
    profiler's host clock, each phase inside its tick; request spans stay
    off it."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import registry
    from repro.obs.trace import Tracer
    from repro.serve import ContinuousEngine, Request, ServeConfig
    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    trc = Tracer(wall=True)
    eng = ContinuousEngine(cfg, params, ServeConfig(
        batch_size=2, max_len=64, eos_id=-1, page_size=16), tracer=trc)
    reqs = [Request(i, np.arange(n, dtype=np.int32) + 1, max_new=3)
            for i, n in enumerate((9, 14))]
    jax.profiler.start_trace(str(tmp_path))
    eng.run(reqs)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(tmp_path)
    got = collections.defaultdict(list)
    for plane in scopes.planes(path, keep=lambda p, e: e.startswith(
            "engine.") or e == "request"):
        for _, events in plane.lines:
            for mid, s, d in events:
                got[plane.event_md[mid][0]].append((s, s + d))
    assert "request" not in got
    ticks = sorted(got.pop("engine.tick"))
    n_ticks = sum(1 for e in trc.trace_events()
                  if e["ph"] == "B" and e["name"] == "tick")
    assert len(ticks) == n_ticks > 0
    assert set(got) == {"engine." + p for p in (
        "expire", "admit", "prefill_chunk", "pages", "decode", "sync",
        "commit")}
    for spans in got.values():
        assert len(spans) == n_ticks
        for (s, e), (ts, te) in zip(sorted(spans), ticks):
            assert ts <= s <= e <= te


def test_gaps_by_tick():
    spans = [("engine.tick", 0, 100 * PS), ("engine.admit", 10 * PS, 30 * PS),
             ("engine.tick", 100 * PS, 200 * PS),
             ("engine.sync", 150 * PS, 190 * PS)]
    gaps = [(5 * PS, 15 * PS), (95 * PS, 160 * PS), (250 * PS, 260 * PS)]
    got = scopes.gaps_by_outer(gaps, spans, "engine.tick")
    assert len(got) == 2
    assert got[0] == pytest.approx({"engine.tick": 10, "engine.admit": 5})
    assert got[1] == pytest.approx({"engine.tick": 50, "engine.sync": 10})


WIRED = ("quant_share.train", "kv_share.decode", "host_ms.serve")


@pytest.mark.parametrize("name", WIRED)
def test_metric_files_read_what_the_readers_read(name):
    """Each metric file is its reader of ``READERS``, on the context a
    traced run builds: from the committed trace (no program scopes, None),
    with a scoped reduction and the engine's tick phases (a number), and
    with no scopes at all, as before the trace reached the readers."""
    import common
    import run
    read = common.load_reader(name)
    ctx = {}
    run.add_trace(ctx, TRACE)
    assert ctx["scopes"]["busy_s"] == pytest.approx(ctx["trace"]["busy_s"],
                                                    rel=1e-3)
    assert read(ctx) is None and scopes.READERS[name](ctx) is None
    full = dict(ctx, scopes=_synthetic(),
                tick_phases=scopes.tick_phases(_engine_events()))
    assert read(full) is not None
    assert read(full) == scopes.READERS[name](full)
    assert read({"trace": ctx["trace"]}) is None
