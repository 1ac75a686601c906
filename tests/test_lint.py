"""fp4lint self-tests: every rule fires on its positive fixture, stays
silent on the clean twin, and is silenced by the pragma; the whole-repo
run is exactly at its checked-in baseline; a deliberately seeded
violation of each rule in a scratch file is caught by the whole-repo
run; and the rounding-policy rule proves no SR spec is constructible
from serve/ or models/ module scope.

Everything here is jax-free (repro.analysis is pure stdlib), so this
file runs even when the accelerator stack is broken.
"""
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import (DEFAULT_SCAN_DIRS, RULES, all_rule_names,
                            baseline_diff, lint_paths, lint_source,
                            load_baseline, render_baseline)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO_ROOT, "tools", "lint_baseline.txt")


def rules_of(findings):
    return {f.rule for f in findings}


def run(src, path):
    return lint_source(textwrap.dedent(src), path)


# ---- fixtures: (rule, firing source, firing path, clean source, clean path)


FIXTURES = {
    "rounding-policy": dict(
        firing="spec = BlockQuantSpec(stochastic=True)\n",
        firing_path="src/repro/serve/x.py",
        clean="spec = BlockQuantSpec(stochastic=True)\n",
        clean_path="src/repro/train/x.py",       # backward path: allowed
    ),
    "prng-reuse": dict(
        firing="""
        def f(seed, shape):
            key = jax.random.PRNGKey(seed)
            a = jax.random.normal(key, shape)
            b = jax.random.uniform(key, shape)
            return a, b
        """,
        firing_path="src/repro/x.py",
        clean="""
        def f(seed, shape):
            key = jax.random.PRNGKey(seed)
            ka, kb = jax.random.split(key)
            a = jax.random.normal(ka, shape)
            b = jax.random.uniform(kb, shape)
            return a, b
        """,
        clean_path="src/repro/x.py",
    ),
    "spec-canonical": dict(
        firing='spec = P("model", None)\n',
        firing_path="src/repro/x.py",
        clean='spec = P("model")\n',
        clean_path="src/repro/x.py",
    ),
    "trace-hazard": dict(
        firing="""
        @jax.jit
        def f(x):
            return x * float(x.mean())
        """,
        firing_path="src/repro/x.py",
        clean="""
        def f(x):
            return x * float(x.mean())    # not traced: host code
        """,
        clean_path="src/repro/x.py",
    ),
    "packed-dtype": dict(
        firing="w = qt.packed.astype(jnp.float32)\n",
        firing_path="src/repro/serve/x.py",
        clean="w = qt.packed.astype(jnp.float32)\n",
        clean_path="src/repro/core/quantize.py",  # sanctioned dequant site
    ),
    "obs-in-jit": dict(
        firing="""
        @jax.jit
        def decode_step(x, tracer):
            tracer.counter("decode_steps")
            return x
        """,
        firing_path="src/repro/serve/x.py",
        clean="""
        def host_tick(x, tracer):
            tracer.counter("decode_steps")   # host loop: emit freely
            return decode_step(x)
        """,
        clean_path="src/repro/serve/x.py",
    ),
}


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_fires_on_positive(rule):
    fx = FIXTURES[rule]
    found = run(fx["firing"], fx["firing_path"])
    assert rule in rules_of(found), (rule, found)


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_silent_on_clean_twin(rule):
    fx = FIXTURES[rule]
    found = run(fx["clean"], fx["clean_path"])
    assert rule not in rules_of(found), (rule, found)


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_silenced_by_pragma(rule):
    fx = FIXTURES[rule]
    src = textwrap.dedent(fx["firing"])
    # annotate every line: same-line pragmas silence wherever it fired
    src = "".join(f"{ln}  # fp4lint: disable={rule}\n" if ln.strip() else "\n"
                  for ln in src.splitlines())
    assert rule not in rules_of(lint_source(src, fx["firing_path"]))


def test_every_shipped_rule_has_a_fixture_and_a_docstring_example():
    assert sorted(FIXTURES) == all_rule_names()
    for name, rule in RULES.items():
        doc = rule.check.__self__.__doc__ or rule.__doc__
        assert doc and "FIRES" in doc and "CLEAN" in doc, name


# ---- pragma mechanics ---------------------------------------------------------


def test_standalone_pragma_covers_next_line():
    src = ('# fp4lint: disable=spec-canonical\n'
           'spec = P("model", None)\n')
    assert lint_source(src, "src/repro/x.py") == []


def test_trailing_pragma_covers_only_its_own_line():
    src = ('a = P("model", None)  # fp4lint: disable=spec-canonical\n'
           'b = P("model", None)\n')
    found = lint_source(src, "src/repro/x.py")
    assert [f.line for f in found] == [2]


def test_bare_disable_silences_all_rules():
    src = 'w = qt.packed.astype(jnp.float32)  # fp4lint: disable\n'
    assert lint_source(src, "src/repro/serve/x.py") == []


def test_pragma_for_other_rule_does_not_silence():
    src = 'spec = P("model", None)  # fp4lint: disable=packed-dtype\n'
    assert rules_of(lint_source(src, "src/repro/x.py")) == {"spec-canonical"}


# ---- rule-specific behavior ---------------------------------------------------


def test_rounding_policy_with_rounding_in_models():
    found = run("sr = NVFP4.with_rounding(True)\n", "src/repro/models/m.py")
    assert rules_of(found) == {"rounding-policy"}


def test_rounding_policy_kernel_decode_scopes():
    # decode, draft and verify functions are all forward serving paths:
    # an SR draft desyncs from the RtN verify, an SR verify breaks
    # bit-exactness vs sequential decode
    for fn in ("decode_read", "draft_propose", "verify_k_read",
               "spec_verify"):
        fire = f"""
        def {fn}(pool):
            return dequant(pool, NVFP4.with_rounding(True))
        """
        assert rules_of(run(fire, "src/repro/kernels/k.py")) \
            == {"rounding-policy"}, fn
    ok = """
    def backward_quant(g):
        return quant(g, NVFP4.with_rounding(True))
    """
    assert rules_of(run(ok, "src/repro/kernels/k.py")) == set()


def test_rounding_policy_pack_quantize_anywhere():
    src = "qt = pack_quantize(w, BlockQuantSpec(stochastic=True))\n"
    found = run(src, "src/repro/train/x.py")     # even on the train side
    assert rules_of(found) == {"rounding-policy"}


def test_rounding_policy_not_constructible_from_serve_or_models():
    """The static proof the issue asks for: (a) today neither serve/ nor
    models/ constructs an SR spec anywhere (module or function scope);
    (b) for EVERY file there, introducing one would fire the rule."""
    serve_models = [p for p in _scan_files()
                    if "/serve/" in p or "/models/" in p]
    assert serve_models, "scan set lost serve//models/"
    for path in serve_models:
        rel = os.path.relpath(path, REPO_ROOT)
        with open(path, encoding="utf-8") as f:
            src = f.read()
        assert "rounding-policy" not in rules_of(lint_source(src, rel)), rel
        seeded = src + "\n_viol = BlockQuantSpec(stochastic=True)\n"
        assert "rounding-policy" in rules_of(lint_source(seeded, rel)), rel


def test_prng_literal_scoping():
    src = "key = jax.random.PRNGKey(0)\n"
    assert rules_of(run(src, "src/repro/x.py")) == {"prng-reuse"}
    for exempt in ("tests/test_x.py", "src/repro/configs/x.py",
                   "benchmarks/x.py", "tools/x.py"):
        assert rules_of(run(src, exempt)) == set(), exempt


def test_prng_reuse_branches_do_not_cross_flag():
    src = """
    def f(key, c, shape):
        if c:
            a = jax.random.normal(key, shape)
        else:
            a = jax.random.uniform(key, shape)   # exclusive: not reuse
        return a
    """
    assert rules_of(run(src, "src/repro/x.py")) == set()


def test_prng_reuse_single_statement_double_sample():
    src = """
    def f(key, shape):
        return {"a": jax.random.normal(key, shape),
                "b": jax.random.normal(key, shape)}
    """
    found = run(src, "src/repro/x.py")
    assert [f.rule for f in found] == ["prng-reuse"]   # exactly once


def test_spec_canonical_all_replicated_and_interior_none():
    assert rules_of(run("s = P(None, None)\n", "src/repro/x.py")) \
        == {"spec-canonical"}
    # interior None is fine — only TRAILING Nones are non-canonical
    assert rules_of(run('s = P(None, "model")\n', "src/repro/x.py")) == set()
    assert rules_of(run("s = PartitionSpec()\n", "src/repro/x.py")) == set()


def test_trace_hazard_call_site_and_raise_exemption():
    src = """
    def _impl(self, x):
        return x * float(x.mean())
    step = jax.jit(_impl)
    """
    assert rules_of(run(src, "src/repro/x.py")) == {"trace-hazard"}
    ok = """
    @jax.jit
    def f(x):
        if x.shape[0] != 4:
            raise ValueError(f"bad leading dim {x.shape[0]} for {x}")
        n = float(x.shape[0])            # static metadata: exempt
        return x * n
    """
    assert rules_of(run(ok, "src/repro/x.py")) == set()


def test_trace_hazard_item_and_asarray_in_pallas_body():
    src = """
    def kernel(x_ref, o_ref):
        o_ref[...] = np.asarray(x_ref[...]).sum() + x_ref[0].item()
    out = pl.pallas_call(kernel, out_shape=shape)(x)
    """
    found = run(src, "src/repro/x.py")
    assert [f.rule for f in found] == ["trace-hazard", "trace-hazard"]


def test_packed_dtype_scales_and_storage_cast():
    assert rules_of(run("s = scales.astype(jnp.bfloat16)\n",
                        "src/repro/distributed/x.py")) == {"packed-dtype"}
    # storage-width cast stays clean; kernels/ is a sanctioned site
    assert rules_of(run("n = qt.packed.astype(jnp.uint8)\n",
                        "src/repro/serve/x.py")) == set()
    assert rules_of(run("w = codes.astype(jnp.float32)\n",
                        "src/repro/kernels/k.py")) == set()


# ---- whole-repo run + baseline ------------------------------------------------


def _scan_files():
    from repro.analysis.engine import iter_py_files
    return iter_py_files(DEFAULT_SCAN_DIRS, REPO_ROOT)


def test_whole_repo_exactly_at_baseline():
    findings, stats = lint_paths(root=REPO_ROOT)
    new, stale = baseline_diff(findings, load_baseline(BASELINE))
    assert new == [], "non-baselined findings:\n" + "\n".join(
        f.render() for f in new)
    assert stale == [], f"stale baseline entries: {stale}"
    assert stats.files_scanned > 80      # the scan set is the real repo
    assert stats.parse_errors == 0


def test_empty_baseline_for_prng_and_spec_rules():
    """Issue acceptance: prng-reuse and spec-canonical true positives were
    FIXED, not grandfathered (and so was everything else, in fact)."""
    entries = load_baseline(BASELINE)
    for rule in ("prng-reuse", "spec-canonical"):
        assert not any(f":{rule}:" in e for e in entries), entries


def test_seeded_violations_caught_by_whole_repo_run(tmp_path):
    """One scratch file violating all six rules, dropped into the scan
    tree: the whole-repo run must catch every one of them.  The tree is a
    copy, so a lint of the live repo running meanwhile (another test
    worker) never sees the scratch file."""
    for d in DEFAULT_SCAN_DIRS:
        shutil.copytree(os.path.join(REPO_ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    scratch = tmp_path / "src" / "repro" / "serve" / "_lint_seed_scratch.py"
    src = textwrap.dedent("""
        spec = BlockQuantSpec(stochastic=True)
        key = jax.random.PRNGKey(0)
        pspec = P("model", None)
        w = qt.packed.astype(jnp.float32)

        @jax.jit
        def f(x):
            return x * float(x.mean())

        @jax.jit
        def g(x, tracer):
            tracer.counter("oops")
            return x
        """)
    scratch.write_text(src, encoding="utf-8")
    findings, _ = lint_paths(root=str(tmp_path))
    hit = {f.rule for f in findings
           if f.path == "src/repro/serve/_lint_seed_scratch.py"}
    assert hit == set(all_rule_names()), hit
    new, _ = baseline_diff(findings, load_baseline(BASELINE))
    assert len(new) >= 6             # none of them baselined away


# ---- baseline machinery -------------------------------------------------------


def test_baseline_keys_are_line_number_independent():
    src_a = 'spec = P("model", None)\n'
    src_b = "\n\n# moved down by unrelated edits\n" + src_a
    fa = lint_source(src_a, "src/repro/x.py")
    fb = lint_source(src_b, "src/repro/x.py")
    assert fa[0].key() == fb[0].key()
    assert fa[0].line != fb[0].line


def test_baseline_diff_both_directions():
    found = lint_source('s = P("a", None)\n', "src/repro/x.py")
    new, stale = baseline_diff(found, [])
    assert new == found and stale == []
    new, stale = baseline_diff(found, [found[0].key(), "ghost:rule:line"])
    assert new == [] and stale == ["ghost:rule:line"]
    # duplicates are a multiset: one baseline entry covers one finding
    new, stale = baseline_diff(found + found, [found[0].key()])
    assert len(new) == 1 and stale == []


def test_render_baseline_deterministic():
    findings, _ = lint_paths(["src"], root=REPO_ROOT)
    assert render_baseline(findings) == render_baseline(list(findings))
    assert render_baseline(reversed(findings)) == render_baseline(findings)


# ---- the CLI ------------------------------------------------------------------


def _cli(*args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "lint.py"),
         *args], capture_output=True, text=True, cwd=cwd)


def test_cli_green_on_current_repo():
    r = _cli("--stats")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "fp4lint:" in r.stdout


def test_cli_fails_on_non_baselined_finding_and_stale_entry(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text('spec = P("model", None)\n')
    r = _cli(str(bad))
    assert r.returncode == 1
    assert "spec-canonical" in r.stdout and 'P("model", None)' in r.stdout
    stale = tmp_path / "stale_baseline.txt"
    # under the scanned prefix, so the partial scan judges it; entries for
    # unscanned trees are exempt from staleness (the scan can't see them)
    stale.write_text("src/repro/ghost.py:spec-canonical:x = P(None, None)\n"
                     "elsewhere/ghost.py:spec-canonical:x = P(None, None)\n")
    r = _cli("src", "--baseline", str(stale))
    assert r.returncode == 1
    assert r.stdout.count("stale baseline entry") == 1   # src/ one only


def test_cli_update_baseline_deterministic(tmp_path):
    bl = tmp_path / "bl.txt"
    r1 = _cli("--update-baseline", "--baseline", str(bl))
    first = bl.read_text()
    r2 = _cli("--update-baseline", "--baseline", str(bl))
    assert r1.returncode == r2.returncode == 0
    assert bl.read_text() == first
    # and the current repo state writes an EMPTY baseline (header only)
    assert all(ln.startswith("#") or not ln.strip()
               for ln in first.splitlines())
