"""Environment preflight + docs-drift guard + serving self-check.

  PYTHONPATH=src python tools/check_env.py          # dependency report
  PYTHONPATH=src python tools/check_env.py --docs   # docs snippet check
  PYTHONPATH=src python tools/check_env.py --serve  # scheduler invariants
  PYTHONPATH=src python tools/check_env.py --traffic # workload/lifecycle
  PYTHONPATH=src python tools/check_env.py --spec   # speculative decoding
  PYTHONPATH=src python tools/check_env.py --mesh   # partition-spec check
  PYTHONPATH=src python tools/check_env.py --lint   # fp4lint AST invariants
  PYTHONPATH=src python tools/check_env.py --obs    # tracing/telemetry
  PYTHONPATH=src python tools/check_env.py --all    # every self-check

Default mode prints one line per dependency so a red test run can be
triaged at a glance instead of letting pytest collection explode on an
ImportError; missing REQUIRED deps exit non-zero.

``--docs`` scans README.md and docs/*.md fenced code blocks and verifies
they have not drifted from the code: every ``import``/``from repro...``
line must import (and every imported name must exist), every file path
mentioned in a command must exist, every ``--flag`` of a quoted command
must appear in the invoked module's source, every ``--bench NAME`` must
be a registered benchmark, and constructors named in ``KWARG_GUARDS``
(ServeConfig/Request/PrefixCache) must only be quoted with real
fields/parameters.  Wired into tier-1 as a fast test (tests/test_docs.py).

``--serve`` is a jax-free self-check of the serving scheduler's host
machinery: it builds a tiny refcounted page pool + prefix-cache radix
tree and drives a full submit/admit/grow/decode/free cycle, asserting
refcount conservation and that no page leaks.  Also tier-1
(tests/test_docs.py).

``--traffic`` is a host-side self-check of the traffic harness
(serve/workload.py + serve/metrics.py + the scheduler's chunked-prefill
and abort/timeout lifecycle): byte-for-byte workload determinism,
nearest-rank percentile math, page-pool conservation under cancellation
at every stage, and the per-tick-per-slot prefill chunk budget.  Also
tier-1 (tests/test_docs.py).

``--spec`` is a jax-free self-check of the speculative-decoding host
machinery (serve/metrics.py spec trajectory + the scheduler's spec
protocol): the greedy acceptance rule and rollback arithmetic (numpy
mirrors of the verify program), accepted-tokens/tick/slot percentiles,
ensure_capacity/advance_written bookkeeping, and partial-suffix
preemption's written/prompt invariant.  Also tier-1
(tests/test_docs.py).

``--mesh`` is a jax-free self-check of the sharded-serving partition-spec
layer (repro.distributed.specs): ``--mesh tp=N`` CLI grammar, the
code/scale congruence invariant of packed leaves, drop diagnostics for
odd dims, and the 4.5 bits/param packed wire accounting.  Also tier-1
(tests/test_docs.py).

``--lint`` runs fp4lint (repro.analysis, stdlib-ast, jax-free) over the
whole repo and fails on any finding outside tools/lint_baseline.txt or
any stale baseline entry — the static invariants (rounding policy, PRNG
stream discipline, PartitionSpec canonical form, trace hazards, packed
dtypes; see docs/lint.md).  Also tier-1 (tests/test_docs.py).

``--obs`` is a jax-free self-check of the observability layer
(repro.obs.trace + the scheduler's instrumentation): span balance
across the full request lifecycle (done / abort / timeout close the
request span; preemption keeps it open), counter conservation against
the scheduler's own stats and the page pool, the disabled tracer's
no-op contract, and the Chrome-trace-event exporter schema.  Also
tier-1 (tests/test_docs.py).

``--all`` runs every self-check above (docs, serve, traffic, spec, mesh,
lint, obs) plus the dependency report, and fails if any of them does.
"""
from __future__ import annotations

import importlib
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REQUIRED = ("jax", "jaxlib", "ml_dtypes", "numpy", "pytest", "hypothesis")

DOC_FILES = ("README.md", "docs/formats.md", "docs/serving.md",
             "docs/lint.md", "docs/observability.md")


def _probe(name: str):
    try:
        mod = importlib.import_module(name)
        return getattr(mod, "__version__", "?")
    except ImportError:
        return None


# ---- docs-drift check ---------------------------------------------------------


def _fenced_blocks(text: str):
    """Yield (lang, body) for every ``` fenced block."""
    for m in re.finditer(r"```(\w*)\n(.*?)```", text, re.DOTALL):
        yield m.group(1) or "", m.group(2)


def _check_import_line(line: str, errors: list, where: str):
    line = line.strip()
    m = re.match(r"from\s+([\w.]+)\s+import\s+(.+)", line)
    if m:
        mod_name, names = m.group(1), m.group(2)
        try:
            mod = importlib.import_module(mod_name)
        except Exception as e:                              # noqa: BLE001
            errors.append(f"{where}: cannot import {mod_name}: {e}")
            return
        # tolerate parenthesized import lists (possibly split across lines)
        names = names.split("#")[0].strip().strip("()\\,")
        for name in re.split(r"\s*,\s*", names):
            name = name.split(" as ")[0].strip().strip("()")
            if name and not hasattr(mod, name):
                errors.append(f"{where}: {mod_name} has no {name!r}")
        return
    m = re.match(r"import\s+([\w.]+)", line)
    if m:
        try:
            importlib.import_module(m.group(1))
        except Exception as e:                              # noqa: BLE001
            errors.append(f"{where}: cannot import {m.group(1)}: {e}")


# Serving-knob drift guard: docs quoting these constructors must only use
# real dataclass fields / signature parameters (catches knob renames —
# e.g. ServeConfig.page_size or PrefixCache.max_pages going away while
# docs still advertise them).
KWARG_GUARDS = {
    "ServeConfig": ("repro.serve", "ServeConfig"),
    "Request": ("repro.serve", "Request"),
    "PrefixCache": ("repro.serve", "PrefixCache"),
    "WorkloadConfig": ("repro.serve", "WorkloadConfig"),
    "TenantSpec": ("repro.serve", "TenantSpec"),
}


def _guarded_fields(cls) -> set:
    """Accepted keyword names of a guarded constructor: dataclass fields,
    or (plain classes like PrefixCache) the __init__ signature."""
    import dataclasses
    import inspect
    if dataclasses.is_dataclass(cls):
        return {f.name for f in dataclasses.fields(cls)}
    return {p for p in inspect.signature(cls).parameters if p != "self"}


def _check_guarded_kwargs(body: str, errors: list, where: str):
    for name, (mod_name, attr) in KWARG_GUARDS.items():
        hits = re.finditer(
            name + r"\(([^()]*(?:\([^()]*\)[^()]*)*)\)", body)
        # strip string literals (a mesh="tp=2" value must not read as a
        # tp= kwarg) and nested call arguments (np.array(x, dtype=...))
        # so neither contributes phantom kwargs
        args = [re.sub(r"\([^()]*\)", "",
                       re.sub(r"'[^']*'|\"[^\"]*\"", "''", m.group(1)))
                for m in hits]
        kwargs = {kw for a in args
                  for kw in re.findall(r"(?<![\w.])(\w+)\s*=", a)}
        if not kwargs:
            continue
        try:
            cls = getattr(importlib.import_module(mod_name), attr)
            fields = _guarded_fields(cls)
        except Exception as e:                              # noqa: BLE001
            errors.append(f"{where}: cannot resolve {mod_name}.{attr}: {e}")
            continue
        for kw in sorted(kwargs - fields):
            errors.append(f"{where}: {name} has no field {kw!r} "
                          f"(have {sorted(fields)})")


def _module_source(modpath: str):
    """Best-effort source file of ``python -m modpath`` within the repo."""
    for base in ("src", "."):
        cand = os.path.join(REPO_ROOT, base, *modpath.split(".")) + ".py"
        if os.path.exists(cand):
            return cand
        pkg = os.path.join(REPO_ROOT, base, *modpath.split("."),
                           "__main__.py")
        if os.path.exists(pkg):
            return pkg
    return None


def _check_command(cmd: str, errors: list, where: str):
    """One shell command quoting this repo: paths, flags, bench names."""
    toks = cmd.split()
    src_file = None
    if "-m" in toks and toks.index("-m") + 1 < len(toks):
        modpath = toks[toks.index("-m") + 1]
        if modpath != "pytest":
            src_file = _module_source(modpath)
            if src_file is None:
                errors.append(f"{where}: module {modpath} not found")
    for t in toks:
        if re.fullmatch(r"[\w./-]+\.(py|md)", t):
            if not os.path.exists(os.path.join(REPO_ROOT, t)):
                errors.append(f"{where}: referenced file {t} missing")
            elif t.endswith(".py") and src_file is None:
                src_file = os.path.join(REPO_ROOT, t)
    if src_file:
        src = open(src_file).read()
        for t in toks:
            if t.startswith("--") and re.fullmatch(r"--[\w-]+", t):
                if t not in src:
                    errors.append(f"{where}: {os.path.relpath(src_file, REPO_ROOT)} "
                                  f"does not define flag {t}")
    if "--bench" in toks and toks.index("--bench") + 1 < len(toks):
        bench = toks[toks.index("--bench") + 1]
        sys.path.insert(0, REPO_ROOT)
        try:
            from benchmarks.run import BENCHES
            if bench not in BENCHES:
                errors.append(f"{where}: unknown bench {bench!r} "
                              f"(have {sorted(BENCHES)})")
        finally:
            sys.path.pop(0)
    if "--mesh" in toks and toks.index("--mesh") + 1 < len(toks):
        # quoted mesh specs must parse (jax-free: repro.distributed.specs)
        spec = toks[toks.index("--mesh") + 1].strip("'\"")
        sys.path.insert(0, os.path.join(REPO_ROOT, "src"))
        try:
            from repro.distributed.specs import parse_mesh_spec
            try:
                parse_mesh_spec(spec)
            except ValueError as e:
                errors.append(f"{where}: bad --mesh spec {spec!r}: {e}")
        finally:
            sys.path.pop(0)


def check_docs() -> int:
    """Verify README/docs code snippets against the code.  0 = no drift."""
    errors = []
    for rel in DOC_FILES:
        path = os.path.join(REPO_ROOT, rel)
        if not os.path.exists(path):
            errors.append(f"{rel}: missing")
            continue
        text = open(path).read()
        for lang, body in _fenced_blocks(text):
            # join backslash-continued command lines
            body = re.sub(r"\\\n\s*", " ", body)
            for ln, line in enumerate(body.splitlines(), 1):
                where = f"{rel} (block line {ln})"
                if lang in ("python", "py", ""):
                    if re.match(r"\s*(from|import)\s", line):
                        _check_import_line(line, errors, where)
                if lang in ("bash", "sh", "shell", ""):
                    if re.search(r"\bpython3?\b", line):
                        _check_command(line.strip(), errors, where)
            if lang in ("python", "py"):
                _check_guarded_kwargs(body, errors, f"{rel} (block)")
        # markdown links to local files must resolve
        for m in re.finditer(r"\]\(([\w./-]+\.md)\)", text):
            tgt = os.path.normpath(os.path.join(os.path.dirname(path),
                                                m.group(1)))
            if not os.path.exists(tgt):
                errors.append(f"{rel}: broken link {m.group(1)}")
    if errors:
        for e in errors:
            print(f"DRIFT    {e}")
        print(f"FATAL: {len(errors)} docs drift error(s)")
        return 1
    print(f"ok       docs snippets in sync ({', '.join(DOC_FILES)})")
    return 0


# ---- serving scheduler self-check ---------------------------------------------


def check_serve() -> int:
    """Host-side (jax-free) invariants of the serving scheduler stack:
    refcount conservation in the page pool, radix-tree bookkeeping, and
    no page leaked after a full submit/admit/grow/decode/free cycle."""
    for base in ("src",):
        p = os.path.join(REPO_ROOT, base)
        if p not in sys.path:
            sys.path.insert(0, p)
    import numpy as np
    from repro.serve.prefix_cache import PrefixCache
    from repro.serve.scheduler import PagePool, Request, Scheduler

    errors = []

    def conserved(pool, what):
        if pool.free_pages + pool.pages_in_use != pool.total_pages - 1:
            errors.append(
                f"{what}: refcount conservation broken "
                f"(free {pool.free_pages} + in-use {pool.pages_in_use} "
                f"!= {pool.total_pages - 1})")

    # pool: alloc/ref/free conservation + hardening
    pool = PagePool(9)
    pages = pool.alloc(4)
    pool.ref(pages[0])
    pool.free([pages[0]])
    conserved(pool, "pool after shared free")
    pool.free(pages)
    conserved(pool, "pool after full free")
    for bad, tag in (([pages[0]], "double free"), ([0], "trash"),
                     ([42], "out of range")):
        try:
            pool.free(bad)
            errors.append(f"pool accepted {tag}")
        except ValueError:
            pass

    # radix tree over a fresh pool: insert/match/evict
    pool = PagePool(9)
    pc = PrefixCache(pool, page_size=4)
    toks = np.arange(12)
    row = pool.alloc(3)
    pc.insert(toks, row)
    pool.free(row)                       # cache's refs keep pages alive
    conserved(pool, "tree after slot free")
    if pc.match(toks) != row:
        errors.append("radix tree did not match its own insert")
    if pc.match(np.arange(1, 13)) != []:
        errors.append("radix tree matched a different prefix")
    if pc.evict(3) != 3 or pool.free_pages != pool.total_pages - 1:
        errors.append("eviction leaked pages")

    # full scheduler cycle: submit/admit/grow/decode/free, warm reuse
    sched = Scheduler(n_slots=2, max_len=32, page_size=4,
                      prefix_cache=True)
    prompt = np.arange(10)
    for rid in range(3):
        sched.submit(Request(rid, prompt, max_new=6, arrival=0))
    placed = sched.admit(0)
    if [p[3] for p in placed] != [0, 8]:
        errors.append(f"expected cold then 8-token warm admission, got "
                      f"{[p[3] for p in placed]}")
    tick = 0
    while sched.has_work() and tick < 50:
        sched.admit(tick)
        T = sched.tick_steps(4, {s: 1 for s in sched.active_slots()})
        sched.ensure_capacity(T)
        for s in list(sched.active_slots()):
            sched.commit(s, np.full((max(T, 1),), 7), eos_id=-1)
        sched.count_tick(T)
        tick += 1
    if sched.stats["completed"] != 3:
        errors.append(f"cycle did not complete: {sched.stats}")
    conserved(sched.pool, "scheduler after cycle")
    live = sched.pool.pages_in_use - sched.prefix_cache.cached_pages
    if live != 0:
        errors.append(f"{live} pages leaked past the prefix cache after "
                      f"all slots freed")
    if sched.prefix_cache.evict(sched.prefix_cache.cached_pages) < 1 or \
            sched.pool.pages_in_use != 0:
        errors.append("draining the prefix cache left pages in use")

    if errors:
        for e in errors:
            print(f"SERVE    {e}")
        print(f"FATAL: {len(errors)} serving invariant error(s)")
        return 1
    print("ok       serving scheduler invariants (pool refcounts, radix "
          "tree, admit/grow/free cycle)")
    return 0


# ---- traffic harness self-check ----------------------------------------------


def check_traffic() -> int:
    """Host-side invariants of the traffic harness (serve/workload.py,
    serve/metrics.py, and the scheduler's chunked-prefill/lifecycle
    machinery — no engine, no device): workload determinism byte-for-
    byte, nearest-rank percentile math, and the request-lifecycle state
    machine (abort/timeout at every stage conserves the page pool; at
    most prefill_chunk prompt tokens per slot per tick)."""
    for base in ("src",):
        p = os.path.join(REPO_ROOT, base)
        if p not in sys.path:
            sys.path.insert(0, p)
    import numpy as np
    from repro.serve.metrics import MetricsRecorder, percentile
    from repro.serve.scheduler import Request, Scheduler
    from repro.serve.workload import (TenantSpec, WorkloadConfig,
                                      generate_workload, trace_fingerprint)

    errors = []

    # workload generator: deterministic byte-for-byte, seed-sensitive
    wcfg = WorkloadConfig(tenants=(
        TenantSpec("chat", rate=0.6, prompt_lens=(4, 8),
                   system_prompt_len=4, deadline_slack=16),
        TenantSpec("batch", rate=0.3, prompt_lens=(12,), abort_prob=0.3,
                   timeout=20, burst_every=6, burst_size=1),
    ), ticks=20, seed=3)
    a, b = generate_workload(wcfg), generate_workload(wcfg)
    if trace_fingerprint(a) != trace_fingerprint(b):
        errors.append("workload trace not deterministic for a fixed seed")
    import dataclasses
    c = generate_workload(dataclasses.replace(wcfg, seed=4))
    if trace_fingerprint(a) == trace_fingerprint(c):
        errors.append("workload trace identical across different seeds")
    if [e.rid for e in a] != list(range(len(a))):
        errors.append("workload rids not sequential in arrival order")
    if any(a[i].arrival > a[i + 1].arrival for i in range(len(a) - 1)):
        errors.append("workload events not sorted by arrival")

    # nearest-rank percentile math (no interpolation, ever)
    vals = [10, 20, 30, 40]
    for p, want in ((50, 20), (75, 30), (95, 40), (99, 40), (100, 40)):
        got = percentile(vals, p)
        if got != want:
            errors.append(f"percentile({p}) = {got}, want {want}")
    if percentile([7], 50) != 7:
        errors.append("percentile of a singleton is not the singleton")
    rec = MetricsRecorder()
    rec.submitted(0, arrival=2, deadline=10)
    rec.admitted(0, 3)
    rec.first_token(0, 5)
    rec.finished(0, 9, ntokens=5)
    rec.submitted(1, arrival=2, deadline=4)
    rec.first_token(1, 6)
    rec.finished(1, 8, ntokens=3)
    s = rec.summary()
    if s["ttft_ticks"]["p50"] != 3 or s["ttft_ticks"]["max"] != 4:
        errors.append(f"TTFT summary wrong: {s['ttft_ticks']}")
    if s["tpot_ticks"]["p50"] != 1.0:
        errors.append(f"TPOT summary wrong: {s['tpot_ticks']}")
    if s["goodput"] != 0.5:           # rid 1 finished past its deadline
        errors.append(f"goodput {s['goodput']} != 0.5")

    # lifecycle state machine: abort/timeout at every stage conserves the
    # pool; chunked prefill never exceeds its per-tick-per-slot budget
    def conserved(sched, what):
        pool = sched.pool
        if pool.free_pages + pool.pages_in_use != pool.total_pages - 1:
            errors.append(f"{what}: pool conservation broken")

    C = 3
    sched = Scheduler(n_slots=2, max_len=32, page_size=4,
                      prefill_chunk=C)
    rng = np.random.default_rng(0)
    sched.submit(Request(0, rng.integers(0, 99, 10), max_new=4))
    sched.submit(Request(1, rng.integers(0, 99, 9), max_new=4,
                         abort_at=1))                   # dies mid-prefill
    sched.submit(Request(2, rng.integers(0, 99, 6), max_new=4,
                         arrival=0, timeout=1))         # dies queued
    tick = 0
    while sched.has_work() and tick < 30:
        sched.expire(tick)
        sched.admit(tick)
        sched.prefill_work(tick)
        T = sched.tick_steps(4, {})
        sched.ensure_capacity(T)
        for s_ in list(sched.decoding_slots()):
            if T:
                sched.commit(s_, np.full((T,), 7), eos_id=-1)
        conserved(sched, f"tick {tick}")
        tick += 1
    if sorted(sched.cancelled) != [1, 2]:
        errors.append(f"expected rids 1,2 cancelled, got "
                      f"{sorted(sched.cancelled)}")
    stages = {r: v["stage"] for r, v in sched.cancelled.items()}
    if stages.get(1) != "prefill" or stages.get(2) != "queued":
        errors.append(f"wrong cancel stages: {stages}")
    if 0 not in sched.results:
        errors.append("surviving request did not complete")
    if sched.pool.pages_in_use != 0:
        errors.append(f"{sched.pool.pages_in_use} pages leaked after the "
                      f"lifecycle cycle")
    per_tick = {}
    for t, s_, _, clen in sched.prefill_log:
        per_tick[(t, s_)] = per_tick.get((t, s_), 0) + clen
        if clen > C:
            errors.append(f"chunk of {clen} tokens exceeds prefill_chunk "
                          f"{C} at tick {t}")
    if per_tick and max(per_tick.values()) > C:
        errors.append("a slot prefilled more than one chunk in a tick")
    # cancel() mid-decode on a fresh scheduler
    sched = Scheduler(n_slots=1, max_len=32, page_size=4)
    sched.submit(Request(5, np.arange(6), max_new=8))
    sched.admit(0)
    sched.ensure_capacity(2)
    sched.commit(0, np.full((2,), 9), eos_id=-1)
    if not sched.cancel(5, reason="abort"):
        errors.append("cancel() did not find a decoding request")
    if sched.cancelled[5]["stage"] != "decode" or \
            len(sched.cancelled[5]["tokens"]) != 2:
        errors.append(f"decode-stage cancel wrong: {sched.cancelled[5]}")
    if sched.pool.pages_in_use != 0:
        errors.append("cancel() leaked pages")
    if sched.cancel(99):
        errors.append("cancel() accepted an unknown rid")

    if errors:
        for e in errors:
            print(f"TRAFFIC  {e}")
        print(f"FATAL: {len(errors)} traffic harness error(s)")
        return 1
    print("ok       traffic harness (workload determinism, nearest-rank "
          "percentiles, lifecycle conservation, chunk budget)")
    return 0


# ---- speculative decoding self-check ------------------------------------------


def check_spec() -> int:
    """Host-side (jax-free) invariants of the speculative-decoding
    machinery: the greedy acceptance rule (longest matching prefix via
    a cumulative product of per-position agreement, plus one corrected
    token — 1..k emitted, always), the rollback arithmetic the verify
    program applies to cache lengths, the accepted-tokens metrics
    trajectory, and the scheduler's spec protocol (ensure_capacity
    without the written advance, then advance_written by the ACCEPTED
    length) including partial-suffix preemption's written/prompt
    bookkeeping."""
    for base in ("src",):
        p = os.path.join(REPO_ROOT, base)
        if p not in sys.path:
            sys.path.insert(0, p)
    import numpy as np
    from repro.serve.metrics import MetricsRecorder
    from repro.serve.scheduler import Request, Scheduler

    errors = []

    # acceptance rule: numpy mirror of the verify program's
    # acc = sum(cumprod(match)) — longest agreeing prefix, then +1
    def n_emit(drafts, greedy):
        match = (np.asarray(greedy[:-1]) == np.asarray(drafts)).astype(int)
        return int(np.cumprod(match).sum()) + 1

    for drafts, greedy, want in (
            ([5, 6, 7], [5, 6, 7, 8], 4),      # all accepted: k tokens
            ([5, 6, 7], [5, 6, 9, 8], 3),      # 2 drafts + correction
            ([5, 6, 7], [9, 6, 7, 8], 1),      # first draft wrong
            ([5, 6, 7], [5, 9, 7, 8], 2),      # later agreement ignored
            ([], [4], 1)):                     # k=1 degenerate: decode
        got = n_emit(drafts, greedy)
        if got != want:
            errors.append(f"acceptance({drafts}, {greedy}) = {got}, "
                          f"want {want}")
    # rollback arithmetic: lengths advance by k at write, shrink to
    # base + n_emit — equivalently += n_emit - k, and 1 <= n_emit <= k
    for k in (2, 3, 4):
        for acc in range(k):
            ne = acc + 1
            base, after = 37, 37 + k
            rolled = after + (ne - k)
            if not base + 1 <= rolled <= base + k:
                errors.append(f"rollback k={k} acc={acc}: length {rolled} "
                              f"outside (base, base+k]")

    # metrics: the accepted-tokens/tick/slot trajectory and rate
    rec = MetricsRecorder()
    rec.spec_tick([3, 1], k=3)
    rec.spec_tick([2], k=3)
    s = rec.summary()
    acc = s.get("spec_accepted_per_tick_slot", {})
    if acc.get("n") != 3 or acc.get("max") != 3 or acc.get("p50") != 2:
        errors.append(f"spec accepted summary wrong: {acc}")
    rate = s.get("spec_acceptance_rate", {})
    if rate.get("max") != 1.0 or rate.get("p50") != 0.5:
        errors.append(f"spec acceptance-rate summary wrong: {rate}")
    if "spec_accepted_per_tick_slot" in MetricsRecorder().summary():
        errors.append("spec metrics reported for a non-spec trace")

    # scheduler spec protocol: grow for k candidate rows WITHOUT the
    # written advance, then advance by the accepted length only
    k = 3
    sched = Scheduler(n_slots=1, max_len=32, page_size=4)
    sched.submit(Request(0, np.arange(10), max_new=9))
    sched.admit(0)
    st = sched.slots[0]
    if st.written != 10:
        errors.append(f"admission written {st.written} != plen")
    sched.ensure_capacity(k, advance=False)
    if st.written != 10:
        errors.append("ensure_capacity(advance=False) advanced written")
    for ne, want in ((2, 12), (3, 15)):
        sched.ensure_capacity(k, advance=False)
        sched.advance_written(0, ne)
        if st.written != want:
            errors.append(f"advance_written: written {st.written} != {want}")
        sched.commit(0, np.full((ne,), 7), eos_id=-1)
    if sched.pool.free_pages + sched.pool.pages_in_use \
            != sched.total_pages - 1:
        errors.append("spec protocol broke pool conservation")

    # partial-suffix preemption bookkeeping: the requeued effective
    # prompt carries written + 1 tokens (the last committed token's row
    # is not in the pages yet); the adopted pages cover exactly written
    sched = Scheduler(n_slots=1, max_len=32, page_size=4,
                      prefix_cache=True)
    sched.submit(Request(1, np.arange(8), max_new=12))
    sched.admit(0)
    st = sched.slots[0]
    sched.commit(0, np.asarray([7]), eos_id=-1)   # prefill-sampled token:
    # committed WITHOUT a written advance (its row lands next tick)
    sched.ensure_capacity(k, advance=False)
    sched.advance_written(0, 3)
    sched.commit(0, np.asarray([7, 7, 7]), eos_id=-1)
    written = st.written
    sched._preempt(0)
    req = sched.queue[0]
    if len(req.prompt) != written + 1:
        errors.append(f"preempted effective prompt {len(req.prompt)} "
                      f"!= written + 1 ({written + 1})")
    if sched.prefix_cache.cached_pages != written // 4:
        errors.append(f"adopted pages {sched.prefix_cache.cached_pages} "
                      f"!= written // page_size ({written // 4})")
    placed = sched.admit(1)
    if not placed or placed[0][3] != written // 4 * 4:
        errors.append(f"resume did not share the adopted full pages: "
                      f"{placed}")
    if list(sched.slots[0].tokens) != [7, 7, 7, 7]:
        errors.append(f"resume lost committed tokens: "
                      f"{sched.slots[0].tokens}")

    if errors:
        for e in errors:
            print(f"SPEC     {e}")
        print(f"FATAL: {len(errors)} speculative-decoding error(s)")
        return 1
    print("ok       speculative decoding (greedy acceptance rule, rollback "
          "arithmetic, accepted-tokens metrics, scheduler spec protocol, "
          "partial-suffix resume)")
    return 0


# ---- mesh spec self-check -----------------------------------------------------


def check_mesh() -> int:
    """Jax-free self-check of the packed-serving partition-spec layer
    (repro.distributed.specs): the mesh-spec CLI grammar, and the
    code/scale CONGRUENCE invariant — a mesh axis shards logical dim d of
    the block scales iff it shards dim d of the nibble codes, for every
    weight kind x shape x TP size, with odd dims diagnosed (never silently
    replicated) and the wire-format accounting at its closed form."""
    for base in ("src",):
        p = os.path.join(REPO_ROOT, base)
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.distributed import specs

    errors = []

    # CLI grammar
    for spec, want in (
            (None, {"model": 1}), ("", {"model": 1}),
            ("tp=2", {"model": 2}), ("tp=4", {"model": 4}),
            ("dp=2,tp=4", {"data": 2, "model": 4}),
            ("fsdp=2", {"data": 2, "model": 1})):
        got = specs.parse_mesh_spec(spec)
        if got != want:
            errors.append(f"parse_mesh_spec({spec!r}) = {got}, want {want}")
    for bad in ("tp=0", "tp=-1", "ep=2", "tp", "tp=2;dp=2"):
        try:
            specs.parse_mesh_spec(bad)
            errors.append(f"parse_mesh_spec accepted {bad!r}")
        except ValueError:
            pass

    # congruence sweep: every kind x shape x tp size keeps scale specs
    # derived from (== congruent with) code specs
    kinds = {                       # logical base specs, Megatron rules
        "io": (None, "model"), "oi": ("model", None),
        "d_vocab": (None, "model"), "stacked_io": (None, None, "model"),
    }
    shapes = ((64, 32), (64, 48), (48, 64), (2, 64, 32), (17, 30))
    for tp in (1, 2, 4):
        sizes = {"model": tp}
        for kname, base in kinds.items():
            for shape in shapes:
                if len(base) != len(shape):
                    continue
                drops = []
                out = specs.packed_leaf_specs(
                    base, shape, axis=-2, block=16, axis_sizes=sizes,
                    path=f"{kname}{shape}", drops=drops)
                if not specs.congruent(out["packed"], out["scales"]):
                    errors.append(
                        f"{kname}{shape} tp={tp}: scales "
                        f"{out['scales']} not congruent with codes "
                        f"{out['packed']}")
                sharded = any(a is not None for a in out["packed"])
                if tp > 1 and not sharded and not drops:
                    errors.append(
                        f"{kname}{shape} tp={tp}: fully replicated "
                        f"without a drop diagnostic")

    # odd dims must be DIAGNOSED, not silently replicated
    drops = []
    specs.packed_leaf_specs((None, "model"), (64, 30), axis=-2, block=16,
                            axis_sizes={"model": 4}, path="w_odd",
                            drops=drops)
    if not drops or "w_odd" not in drops[0]:
        errors.append(f"odd-dim drop not diagnosed by path: {drops}")
    drops = []
    specs.divisible_axes(("model",), (30,), {"model": 4}, path="leaf_odd",
                         drops=drops)
    if not drops or "leaf_odd" not in drops[0]:
        errors.append(f"divisible_axes drop not diagnosed: {drops}")

    # wire-format accounting: NVFP4 block 16 == 4.5 bits/param exactly
    bits = specs.packed_wire_bits_per_param()
    if bits != 4.5:
        errors.append(f"packed wire bits/param {bits} != 4.5")
    ratio = specs.packed_gather_ratio()
    if abs(ratio - 16 / 4.5) > 1e-12:
        errors.append(f"packed gather ratio {ratio} != {16 / 4.5}")

    if errors:
        for e in errors:
            print(f"MESH     {e}")
        print(f"FATAL: {len(errors)} mesh spec error(s)")
        return 1
    print("ok       mesh partition specs (CLI grammar, code/scale "
          "congruence, drop diagnostics, 4.5 bits/param wire accounting)")
    return 0


# ---- fp4lint self-check -------------------------------------------------------


def check_lint() -> int:
    """Run fp4lint over the repo scan set and diff the baseline exactly.
    Jax-free (repro.analysis is pure stdlib), so this runs even when the
    accelerator stack is broken."""
    for base in ("src",):
        p = os.path.join(REPO_ROOT, base)
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.analysis import baseline_diff, lint_paths, load_baseline

    findings, stats = lint_paths(root=REPO_ROOT)
    baseline = load_baseline(
        os.path.join(REPO_ROOT, "tools", "lint_baseline.txt"))
    new, stale = baseline_diff(findings, baseline)
    for f in new:
        print(f"LINT     {f.render()}")
    for key in stale:
        print(f"LINT     stale baseline entry: {key}")
    if new or stale:
        print(f"FATAL: {len(new)} new finding(s), {len(stale)} stale "
              f"baseline entr{'y' if len(stale) == 1 else 'ies'} "
              f"(python tools/lint.py for details)")
        return 1
    per_rule = ", ".join(f"{k}={v}" for k, v in
                         sorted(stats.per_rule.items())) or "0 findings"
    print(f"ok       fp4lint ({stats.files_scanned} files, {per_rule}, "
          f"{stats.suppressed} pragma-suppressed, baseline exact, "
          f"{stats.runtime_s * 1e3:.0f} ms)")
    return 0


# ---- observability self-check -------------------------------------------------


def check_obs() -> int:
    """Jax-free self-check of the observability layer (repro.obs.trace +
    the serving scheduler's instrumentation): the tracer's span-balance
    accounting, the disabled tracer's no-op contract, a full request
    lifecycle (completion, mid-prefill abort, queued timeout) with span
    balance and counter conservation against the scheduler's own stats
    and page pool, preemption keeping the request span open, and the
    Chrome-trace-event exporter schema."""
    for base in ("src",):
        p = os.path.join(REPO_ROOT, base)
        if p not in sys.path:
            sys.path.insert(0, p)
    import json
    import tempfile

    import numpy as np
    from repro.obs.trace import (NULL_TRACER, Counters, Tracer, load_trace,
                                 validate_events)
    from repro.serve.scheduler import Request, Scheduler

    errors = []

    # tracer unit: simulated clock, span balance, counter accumulation
    trc = Tracer(clock="tick", process="check")
    trc.set_time(3)
    trc.begin("t", "work")
    trc.counter("n", 2)
    trc.counter("n")
    trc.gauge("depth", 7)
    trc.instant("t", "mark")
    trc.end("t", "work")
    if trc.counters["n"] != 3:
        errors.append(f"counter accumulation: n = {trc.counters['n']} != 3")
    if trc.spans_opened != 1 or trc.spans_closed != 1 or trc.open_spans():
        errors.append(f"span accounting broken: {trc.spans_opened} opened, "
                      f"{trc.spans_closed} closed, {trc.open_spans()} open")
    if any(e["ts"] != 3 for e in trc.trace_events() if e["ph"] != "M"):
        errors.append("set_time(3) did not stamp every event at ts=3")

    # disabled tracer: inert, records nothing, refuses to export
    if NULL_TRACER.enabled:
        errors.append("NULL_TRACER claims to be enabled")
    NULL_TRACER.begin("t", "x")
    if NULL_TRACER.counter("n", 5) != 0 or NULL_TRACER.n_events != 0:
        errors.append("NULL_TRACER recorded something")
    try:
        NULL_TRACER.export("/dev/null")
        errors.append("NULL_TRACER.export did not refuse")
    except RuntimeError:
        pass

    # counter substrate keeps the mapping protocol MetricsRecorder uses
    c = Counters({"a": 1})
    c.inc("a", 2)
    if dict(c) != {"a": 3} or "a" not in c or len(c) != 1:
        errors.append(f"Counters mapping protocol broken: {c!r}")

    # full lifecycle with a tracer attached: one completion, one abort
    # mid-prefill, one timeout while queued — every request span closes,
    # and the tracer's counters agree with the scheduler's stats
    trc = Tracer(clock="tick")
    sched = Scheduler(n_slots=2, max_len=32, page_size=4, prefill_chunk=3,
                      tracer=trc)
    rng = np.random.default_rng(0)
    sched.submit(Request(0, rng.integers(0, 99, 10), max_new=4))
    sched.submit(Request(1, rng.integers(0, 99, 9), max_new=4, abort_at=1))
    sched.submit(Request(2, rng.integers(0, 99, 6), max_new=4, arrival=0,
                         timeout=1))
    tick = 0
    while sched.has_work() and tick < 30:
        sched.expire(tick)
        sched.admit(tick)
        sched.prefill_work(tick)
        T = sched.tick_steps(4, {})
        sched.ensure_capacity(T)
        for s_ in list(sched.decoding_slots()):
            if T:
                sched.commit(s_, np.full((T,), 7), eos_id=-1)
        tick += 1
    cnt = trc.counters
    if trc.spans_opened != 3 or trc.open_spans():
        errors.append(f"lifecycle spans unbalanced: {trc.spans_opened} "
                      f"opened, {trc.open_spans()} still open at drain")
    for cname, sname in (("sched_admitted", "admitted"),
                         ("sched_completed", "completed"),
                         ("sched_cancelled", "cancelled")):
        if cnt.get(cname) != sched.stats[sname]:
            errors.append(f"{cname} = {cnt.get(cname)} disagrees with "
                          f"scheduler stats {sname} = {sched.stats[sname]}")
    alloc = (cnt.get("pages_private") + cnt.get("pages_shared")
             + cnt.get("pages_demand"))
    if alloc != cnt.get("pages_released") or sched.pool.pages_in_use != 0:
        errors.append(f"page counters not conserved at drain: "
                      f"{alloc} allocated != {cnt.get('pages_released')} "
                      f"released ({sched.pool.pages_in_use} still in use)")

    # preemption keeps the request span OPEN (resume is the same request)
    ptrc = Tracer(clock="tick")
    psched = Scheduler(n_slots=1, max_len=32, page_size=4,
                       prefix_cache=True, tracer=ptrc)
    psched.submit(Request(7, np.arange(8), max_new=12))
    psched.admit(0)
    psched.commit(0, np.asarray([9]), eos_id=-1)
    psched._preempt(0)
    if ptrc.open_spans() != {("req:7", "request"): 1}:
        errors.append(f"preemption closed the request span: "
                      f"{ptrc.open_spans()}")
    if ptrc.counters.get("sched_preempted") != 1:
        errors.append("preemption did not bump sched_preempted")

    # exporter round-trip: valid Chrome trace-event JSON, object form
    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        trc.export(path)
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict) or "traceEvents" not in doc:
            errors.append("export is not the traceEvents object form")
        events = load_trace(path)
        problems = validate_events(events)
        for pr in problems[:5]:
            errors.append(f"exported event invalid: {pr}")
        if len(events) != len(trc.trace_events()):
            errors.append("export dropped events")
        if doc.get("otherData", {}).get("clock") != "tick":
            errors.append("export lost the clock annotation")
    finally:
        os.unlink(path)

    if errors:
        for e in errors:
            print(f"OBS      {e}")
        print(f"FATAL: {len(errors)} observability error(s)")
        return 1
    print("ok       observability (span balance, counter conservation vs "
          "scheduler stats, no-op tracer contract, Chrome trace schema)")
    return 0


# ---- dependency report --------------------------------------------------------


def check_deps() -> int:
    print(f"python {sys.version.split()[0]}")
    missing_required = []
    for name in REQUIRED:
        ver = _probe(name)
        if ver is None:
            missing_required.append(name)
            print(f"MISSING  {name}  (required)")
        else:
            print(f"ok       {name} {ver}")
    try:
        import jax
        print(f"backend  {jax.default_backend()} "
              f"({len(jax.devices())} device(s))")
    except Exception as e:                                  # noqa: BLE001
        print(f"backend  probe failed: {e}")
    if missing_required:
        print(f"FATAL: missing required deps: {missing_required}")
        return 1
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--all" in argv:
        rc = 0
        for check in (check_docs, check_serve, check_traffic, check_spec,
                      check_mesh, check_lint, check_obs, check_deps):
            rc |= check()
        return rc
    if "--docs" in argv:
        return check_docs()
    if "--serve" in argv:
        return check_serve()
    if "--traffic" in argv:
        return check_traffic()
    if "--spec" in argv:
        return check_spec()
    if "--mesh" in argv:
        return check_mesh()
    if "--lint" in argv:
        return check_lint()
    if "--obs" in argv:
        return check_obs()
    return check_deps()


if __name__ == "__main__":
    sys.exit(main())
