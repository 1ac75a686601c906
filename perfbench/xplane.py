"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are those named ``/device:TPU:<n>`` (or GPU).  On each, the
``XLA Ops`` line holds one event per executed HLO operation and the
``XLA Modules`` line one event per executed program; the host plane's
threads hold the host's own events (dispatch, the benchmark's
annotations).  From these:

  * busy seconds: the union of the op intervals, per device, averaged;
  * op self seconds (an op's time less the ops nested in it, as a while
    loop holds its body's ops) by label (op kind and result shape) and by
    category: GEMM work (convolutions, dots, and XLA's output fusions,
    which on the TPU are the fusions rooted at a dot) against the rest;
  * module seconds and call counts by program name;
  * the longest idle gaps between ops, each named by the innermost host
    event that covers its middle.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def find_xplane(trace_dir) -> str:
    hits = sorted(glob.glob(os.path.join(str(trace_dir), "plugins",
                                         "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HEAD = re.compile(r"= (\([^()]*\)|\S+) ([\w-]+)\(")


def label(name: str) -> str:
    """``kind type`` of an op from its HLO text, e.g. ``fusion
    bf16[1024,1024] kOutput``; tuple types shorten to ``(...)``."""
    text = _LAYOUT.sub("", name)
    m = _HEAD.search(text)
    if not m:
        return name.split(" ")[0]
    typ = "(...)" if m.group(1).startswith("(") else m.group(1)
    kind = re.search(r"kind=(k\w+)", text)
    return f"{m.group(2)} {typ}" + (f" {kind.group(1)}" if kind else "")


def is_gemm(name: str) -> bool:
    """An op whose work is a matrix product: a convolution or dot, or an
    output fusion (``kind=kOutput``), XLA's fusion rooted at one."""
    text = _LAYOUT.sub("", name)
    m = _HEAD.search(text)
    kind = m.group(2) if m else ""
    return kind in ("convolution", "dot") or "kind=kOutput" in text


def _self_times(ops):
    """(name, self duration) of each op: its duration less that of the
    ops that start and end inside it."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    own = [d for _, _, d in ops]
    stack = []
    for i, (_, s, d) in enumerate(ops):
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return [(ops[i][0], max(own[i], 0)) for i in range(len(ops))]


def _union(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def reduce(path) -> dict:
    """The reduction of one trace file (times in seconds)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    devices, host_events = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops.append((ev.name, ev.start_ns, ev.duration_ns))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        mods.append((ev.name, ev.start_ns, ev.duration_ns))
            if ops or mods:
                devices.append((plane.name, ops, mods))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    host_events.append((ev.name, ev.start_ns,
                                        ev.start_ns + ev.duration_ns))
    if not devices:
        return {"devices": 0}
    busy, op_s, gemm_s, other_s = [], collections.Counter(), 0.0, 0.0
    mod_s, mod_n = collections.Counter(), collections.Counter()
    gaps = []
    for _, ops, mods in devices:
        iv = [(s, s + d) for _, s, d in ops] or \
            [(s, s + d) for _, s, d in mods]
        busy.append(_union(iv) / 1e9)
        for name, d in _self_times(ops):
            op_s[label(name)] += d / 1e9
            if is_gemm(name):
                gemm_s += d / 1e9
            else:
                other_s += d / 1e9
        for name, s, d in mods:
            mod_s[_module(name)] += d / 1e9
            mod_n[_module(name)] += 1
        iv.sort()
        end = None
        for s, e in iv:
            if end is not None and s > end:
                gaps.append((end, s))
            end = e if end is None else max(end, e)
    n = len(devices)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    named_gaps = [(_host_label(host_events, (a + b) / 2), (b - a) / 1e9)
                  for a, b in gaps[:10]]
    return {"devices": n, "busy_s": sum(busy) / n,
            "op_s": {k: v / n for k, v in op_s.items()},
            "gemm_s": gemm_s / n, "other_s": other_s / n,
            "module_s": {k: v / n for k, v in mod_s.items()},
            "module_n": {k: v // n for k, v in mod_n.items()},
            "idle_gaps": named_gaps}


def _module(name: str) -> str:
    """A program's name without its run id: ``jit_f(123)`` -> ``jit_f``."""
    return re.sub(r"\(\d+\)$", "", name)


def _host_label(events, t) -> str:
    best = None
    for name, s, e in events:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no host event"


def breakdown(red: dict) -> dict:
    ops = sorted(red.get("op_s", {}).items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.get("idle_gaps", [])]}


def module_time(red: dict, word: str):
    """(seconds, calls) of the programs whose name contains ``word``."""
    s = sum(v for k, v in red.get("module_s", {}).items() if word in k)
    n = sum(v for k, v in red.get("module_n", {}).items() if word in k)
    return s, n
