"""Device time by program scope, and idle time by host span, from a JAX
profiler trace (``.xplane.pb``), decoded with the standard library alone.

``xplane.reduce`` reads a trace through ``jax.profiler.ProfileData``, which
gives an event its own stats but not those of its metadata.  The metadata
is where the profiler keeps an op's scope path: the ``tf_op`` stat of each
``XLA Ops`` event metadata, e.g. ``jit(f)/fqt.q.fwd_a/abs``, the HLO
``op_name`` that ``jax.named_scope`` extends.  Ops that XLA inserts itself
(``copy-start``/``copy-done``, some layout copies) carry none.  ``reduce``
reads the XSpace protobuf directly and returns:

  * ``ops``: device self seconds per (program, op label, scope path), the
    label as ``xplane.label`` gives it and the path ``""`` where an op
    carries none;
  * ``module_s``: device seconds per program, from the ``XLA Modules`` line;
  * ``gap_s``: the seconds of every idle gap between device ops, split by
    the innermost host span whose name starts with ``prefix`` (the engine's
    tick phases, ``engine.<phase>``, which the program's continuous engine
    opens as profiler annotations whenever its tracer is live); ``""``
    holds what no such span covers;
  * ``gap_ticks``: the same split inside each ``engine.tick`` span, one
    dict a tick in the order the ticks ran.

The readers at the end match the program's scope names as literal strings
and return None where a trace holds none of them, as a program without
scopes gives.  ``tick_phases`` reads the per-tick phase spans from the
program's own tracer events (Chrome trace-event dicts with ``wall`` stamps).
"""
from __future__ import annotations

import bisect
import collections
import re
import struct

import xplane

QUANT = ("fqt.q.fwd_a", "fqt.q.fwd_w", "fqt.q.bwd_g", "fqt.q.bwd_w",
         "fqt.q.upd_a", "fqt.q.upd_g")
DOTS = ("fqt.dot.fwd", "fqt.dot.dgrad", "fqt.dot.wgrad")
DEQUANT = ("packed.dequant",)
KV = ("kv.read", "kv.write")
SCOPES = QUANT + DOTS + DEQUANT + KV + ("attn.core", "monitor.sigma_q",
                                       "optim.adamw", "layers.scan")
ENGINE = "engine."


# ---- protobuf wire format ----------------------------------------------------


def _fields(buf: memoryview):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited field, bytes for fixed."""
    i, n = 0, len(buf)
    while i < n:
        key, shift = 0, 0
        while True:
            c = buf[i]
            i += 1
            key |= (c & 0x7F) << shift
            shift += 7
            if c < 0x80:
                break
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, shift = 0, 0
            while True:
                c = buf[i]
                i += 1
                v |= (c & 0x7F) << shift
                shift += 7
                if c < 0x80:
                    break
        elif wire == 2:
            ln, shift = 0, 0
            while True:
                c = buf[i]
                i += 1
                ln |= (c & 0x7F) << shift
                shift += 7
                if c < 0x80:
                    break
            v = buf[i:i + ln]
            i += ln
        elif wire == 1:
            v = bytes(buf[i:i + 8])
            i += 8
        elif wire == 5:
            v = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"xplane: unsupported wire type {wire}")
        yield field, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entries(fields, number):
    """The values of a ``map<int64, Message>`` field, keyed."""
    out = {}
    for f, v in fields:
        if f == number:
            kv = dict(_fields(v))
            out[kv.get(1, 0)] = kv.get(2, memoryview(b""))
    return out


def _stat_value(v: memoryview, stat_names: dict):
    value = None
    for f, x in _fields(v):
        if f == 5:
            value = _str(x)
        elif f == 7:                        # a string kept in stat metadata
            value = stat_names.get(x, "")
        elif f in (3, 4):
            value = x
        elif f == 2:
            value = struct.unpack("<d", x)[0]
    return value


class Plane:
    """One XPlane: its name, its lines as (name, [(metadata id, start ps,
    duration ps)]) and its event metadata as {id: (name, stats)}, keeping
    only the events whose metadata ``keep(plane name, event name)`` takes
    (host planes hold most of a trace's events)."""

    def __init__(self, buf: memoryview, keep=None):
        fields = list(_fields(buf))
        self.name = next((_str(v) for f, v in fields if f == 2), "")
        self.lines, self.event_md = [], {}
        stat_names = {k: _str(dict(_fields(v)).get(2, b""))
                      for k, v in _map_entries(fields, 5).items()}
        for k, v in _map_entries(fields, 4).items():
            md = list(_fields(v))
            name = next((_str(x) for f, x in md if f == 2), "")
            if keep is not None and not keep(self.name, name):
                continue
            stats = {}
            for f, x in md:
                if f == 5:
                    sid = dict(_fields(x)).get(1)
                    stats[stat_names.get(sid, "")] = _stat_value(
                        x, stat_names)
            self.event_md[k] = (name, stats)
        if not self.event_md:
            return
        for f, v in fields:
            if f != 3:
                continue
            lf = list(_fields(v))
            lname = next((_str(x) for g, x in lf if g == 2), "")
            t0 = next((x for g, x in lf if g == 3), 0) * 1000
            events = []
            for g, x in lf:
                if g != 4:
                    continue
                mid = off = dur = 0
                for h, y in _fields(x):
                    if h == 1:
                        mid = y
                        if mid not in self.event_md:
                            break
                    elif h == 2:
                        off = y
                    elif h == 3:
                        dur = y
                if mid in self.event_md:
                    events.append((mid, t0 + off, dur))
            self.lines.append((lname, events))


def planes(path, keep=None):
    """The planes of a trace file (see ``Plane`` for ``keep``)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [Plane(v, keep) for f, v in _fields(buf) if f == 1]


def scope_path(tf_op) -> str:
    """The scope path of a ``tf_op`` stat: ``jit(f)/dot_general:`` (the
    op's name, a colon and its type, empty for JAX) -> ``jit(f)/dot_general``."""
    if not tf_op:
        return ""
    head, sep, _ = str(tf_op).rpartition(":")
    return head if sep else str(tf_op)


# ---- the reduction -----------------------------------------------------------


def _innermost(spans):
    """Cut the time that ``spans`` [(name, start, end)] cover into pieces
    [(start, end, name)], each named by the innermost span over it (the
    latest begun of those open; spans of one thread nest)."""
    points = sorted({t for _, s, e in spans for t in (s, e)})
    begins = collections.defaultdict(list)
    ends = collections.defaultdict(list)
    for i, (_, s, e) in enumerate(spans):
        begins[s].append(i)
        ends[e].append(i)
    open_, out = [], []
    for a, b in zip(points, points[1:] + [None]):
        for i in sorted(begins.get(a, ()), key=lambda i: -spans[i][2]):
            open_.append(i)
        for i in ends.get(a, ()):
            open_.remove(i)
        if b is not None and open_:
            out.append((a, b, spans[open_[-1]][0]))
    return out


def split_gaps(gaps, spans) -> dict:
    """Seconds of idle ``gaps`` [(start, end)] under the innermost of
    ``spans`` [(name, start, end)] (times in ps); ``""`` what none covers."""
    pieces = _innermost(spans)
    starts = [p[0] for p in pieces]
    out = collections.Counter()
    for a, b in gaps:
        covered = 0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(pieces) and pieces[i][0] < b:
            s, e, name = pieces[i]
            lo, hi = max(a, s), min(b, e)
            if hi > lo:
                out[name] += (hi - lo) / 1e12
                covered += hi - lo
            i += 1
        if b - a > covered:
            out[""] += (b - a - covered) / 1e12
    return dict(out)


def gaps_by_outer(gaps, spans, outer: str) -> list:
    """``split_gaps`` once for each span named ``outer``, in the order they
    begin, over the part of the gaps inside it (e.g. idle seconds by phase,
    tick by tick)."""
    out = []
    for _, s, e in sorted((sp for sp in spans if sp[0] == outer),
                          key=lambda sp: sp[1]):
        inside = [(max(a, s), min(b, e)) for a, b in gaps
                  if min(b, e) > max(a, s)]
        out.append(split_gaps(inside, [sp for sp in spans
                                       if sp[1] < e and sp[2] > s]))
    return out


def reduce(path, prefix: str = ENGINE) -> dict:
    """Device self seconds by program and scope path, and idle seconds by
    host span (module docstring)."""
    def keep(plane, event):
        return bool(xplane.DEVICE_PLANE.match(plane)) or (
            plane.startswith("/host") and event.startswith(prefix))

    ps = planes(path, keep)
    ops = collections.Counter()
    module_s = collections.Counter()
    busy, gaps, spans, n = [], [], [], 0
    for p in ps:
        if xplane.DEVICE_PLANE.match(p.name):
            dev_ops, mods = [], []
            for lname, events in p.lines:
                if lname == "XLA Ops":
                    dev_ops += events
                elif lname == "XLA Modules":
                    mods += events
            if not dev_ops and not mods:
                continue
            n += 1
            mods.sort(key=lambda m: m[1])
            mstart = [m[1] for m in mods]
            for m, _, d in mods:
                module_s[xplane._module(p.event_md[m][0])] += d / 1e12

            def module_of(t):
                i = bisect.bisect_right(mstart, t) - 1
                if i >= 0 and t <= mods[i][1] + mods[i][2]:
                    return xplane._module(p.event_md[mods[i][0]][0])
                return ""

            info = {mid: (xplane.label(name), scope_path(stats.get("tf_op")))
                    for mid, (name, stats) in p.event_md.items()}
            for (mid, s), own in xplane._self_times(
                    [((mid, s), s, d) for mid, s, d in dev_ops]):
                ops[(module_of(s),) + info[mid]] += own / 1e12
            iv = sorted((s, s + d) for _, s, d in dev_ops)
            busy.append(xplane._union(iv) / 1e12)
            end = None
            for s, e in iv:
                if end is not None and s > end:
                    gaps.append((end, s))
                end = e if end is None else max(end, e)
        elif p.name.startswith("/host"):
            for _, events in p.lines:
                for mid, s, d in events:
                    name = p.event_md[mid][0]
                    if name.startswith(prefix):
                        spans.append((name, s, s + d))
    if not n:
        return {"devices": 0}
    rows = sorted(([m, lab, pth, v / n] for (m, lab, pth), v in ops.items()),
                  key=lambda r: -r[3])
    return {"devices": n, "busy_s": sum(busy) / n, "ops": rows,
            "module_s": {k: v / n for k, v in module_s.items()},
            "gap_s": {k: v / n for k, v in split_gaps(gaps, spans).items()},
            "gap_ticks": [{k: v / n for k, v in t.items()} for t in
                          gaps_by_outer(gaps, spans, prefix + "tick")],
            "spans": len(spans)}


# ---- what the readers share --------------------------------------------------


def _tokens(path: str):
    return set(re.split(r"[/()]", path))


def innermost_scope(path: str, names=SCOPES) -> str:
    """The innermost of ``names`` on a scope path, or ``""``."""
    best = ""
    for tok in re.split(r"[/()]", path):
        if tok in names:
            best = tok
    return best


def seconds_under(red: dict, names, module_word: str = "") -> float:
    """Self seconds of the ops under any of ``names`` in the programs whose
    name holds ``module_word``."""
    names = set(names)
    return sum(v for m, _, pth, v in red.get("ops", ())
               if module_word in m and names & _tokens(pth))


def by_scope(red: dict, module_word: str = "") -> dict:
    """Self seconds by innermost scope of ``SCOPES`` (``""``: none)."""
    out = collections.Counter()
    for m, _, pth, v in red.get("ops", ()):
        if module_word in m:
            out[innermost_scope(pth)] += v
    return dict(out)


def top_labels(red: dict, k: int = 10) -> list:
    """The ``k`` op labels with the most self seconds, each as [label,
    seconds, seconds under any of ``SCOPES``]."""
    tot, scoped = collections.Counter(), collections.Counter()
    for _, lab, pth, v in red.get("ops", ()):
        tot[lab] += v
        if innermost_scope(pth):
            scoped[lab] += v
    return [[lab, v, scoped[lab]] for lab, v in tot.most_common(k)]


def tick_phases(events) -> dict:
    """{tick: {span: wall seconds}} of the engine's ``tick`` spans and the
    phase spans inside them, from the program's tracer events."""
    engine_tid = next((e["tid"] for e in events if e.get("ph") == "M"
                       and e.get("name") == "thread_name"
                       and e.get("args", {}).get("name") == "engine"), None)
    open_, out = {}, collections.defaultdict(dict)
    for e in events:
        if e.get("tid") != engine_tid or e.get("ph") not in ("B", "E") \
                or "wall" not in e.get("args", {}):
            continue
        key = (e["ts"], e["name"])
        if e["ph"] == "B":
            open_[key] = e["args"]["wall"]
        elif key in open_:
            t = out[e["ts"]]
            t[e["name"]] = t.get(e["name"], 0.0) + \
                e["args"]["wall"] - open_.pop(key)
    return dict(out)


# ---- readers: read(ctx) -> number or None -----------------------------------
# ctx["scopes"] is reduce()'s result for the traced window, ctx["tick_phases"]
# tick_phases() of the window's tracer events.


def quant_share_train(ctx):
    """Self seconds of ops under any ``fqt.q.*`` scope over busy seconds, in
    %.  Moves train_tokens_per_s."""
    red = ctx.get("scopes") or {}
    s = seconds_under(red, QUANT)
    if not s or not red.get("busy_s"):
        return None
    return 100.0 * s / red["busy_s"]


def _decode_share(ctx, names):
    red = ctx.get("scopes") or {}
    s = seconds_under(red, names, "decode")
    total = sum(v for k, v in red.get("module_s", {}).items()
                if "decode" in k)
    if not s or not total:
        return None
    return 100.0 * s / total


def dequant_share_decode(ctx):
    """Self seconds of the decode program's ops under ``packed.dequant`` over
    that program's device seconds, in %.  Moves tpot_p95_s."""
    return _decode_share(ctx, DEQUANT)


def kv_share_decode(ctx):
    """The same under ``kv.read`` or ``kv.write``.  Moves tpot_p95_s."""
    return _decode_share(ctx, KV)


def host_ms_serve(ctx):
    """Mean wall milliseconds per window tick (tick 1 on) outside the
    tick's ``sync`` span.  Moves serve_tokens_per_s."""
    ticks = [t for k, t in (ctx.get("tick_phases") or {}).items()
             if k >= 1 and "tick" in t and "sync" in t]
    if not ticks:
        return None
    return 1e3 * sum(t["tick"] - t["sync"] for t in ticks) / len(ticks)


READERS = {"quant_share.train": quant_share_train,
           "dequant_share.decode": dequant_share_decode,
           "kv_share.decode": kv_share_decode,
           "host_ms.serve": host_ms_serve}
