"""Architecture modules: the interface each configuration's ``arch`` file
provides, the dense module's counts and weight layout (by hand at small
shapes, and as the harness counted them before the split into modules),
and a new architecture that enters the harness as one file."""
import json
import pathlib
import sys
import time

import jax
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tests"))

import common  # noqa: E402
import flops  # noqa: E402
import tiny  # noqa: E402
from test_bench_control import SEED, TRAIN_LIMITS  # noqa: E402

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
dense = common.arch({"arch": "dense"})
Model = dense.Model

# d 8, 2 heads of 4, 1 KV head, d_ff 16, vocab 10, 3 layers
M = Model(d=8, heads=2, kv_heads=1, d_ff=16, vocab=10, layers=3,
          theta=1e4, eps=1e-6)


def _config(name):
    c = {x["name"]: x for x in MAN["configs"]}[name]
    return json.loads((ROOT / c["file"]).read_text())


# ---- the interface -------------------------------------------------------------


@pytest.mark.parametrize("name", [c["name"] for c in MAN["configs"]])
def test_every_config_names_an_arch_with_its_interface(name):
    conf = _config(name)
    kinds = {json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                        .read_text())["kind"]
             for w in MAN["workloads"] if w["config"] == name}
    assert (HERE / "arch" / f"{conf['arch']}.py").exists()
    for kind in sorted(kinds):
        arch = common.arch(conf, kind)
        for fn in common.ARCH_API["any"] + common.ARCH_API[kind]:
            assert callable(getattr(arch, fn)), fn
    m = arch.model_of(conf)
    hash(m)                       # a static argument of jitted functions
    assert m.vocab == conf["vocab_size"]
    assert m.layers == conf["num_hidden_layers"]
    arch.program_config(conf)     # raises where a width disagrees


def test_a_missing_function_is_named(tmp_path, monkeypatch):
    (tmp_path / "half.py").write_text("def model_of(config):\n    pass\n")
    monkeypatch.setattr(common, "ARCH", tmp_path)
    with pytest.raises(SystemExit, match="program_config"):
        common.arch({"arch": "half"}, "train")


# ---- the dense module against the harness before the split ---------------------

# Counted by the harness's flops.py and weights.py before the architecture
# modules existed, at the configurations' sizes.
PARENT = {
    "codeqwen1.5-7b-pp16": {
        "gemm_params": 453115904,
        "train_flops": 23921223008256,
        "train_gemm_flops_executed": 31119722414080,
        "prefill_flops": 104249688064,
        "served_flops": 812197609472,
        "decode_step_bytes": 301617152.0,
        "tree": {"embed": (11776, 4096), "lm_head": (4096, 11776),
                 "ln_f": (4096,), "ln1": (2, 4096), "ln2": (2, 4096),
                 "wq": (2, 4096, 4096), "wk": (2, 4096, 512),
                 "wv": (2, 4096, 512), "wo": (2, 4096, 4096),
                 "bq": (2, 4096), "bk": (2, 512), "bv": (2, 512),
                 "w_gate": (2, 4096, 13440), "w_up": (2, 4096, 13440),
                 "w_down": (2, 13440, 4096)}},
    "qwen2.5-32b-pp4-v4": {
        "gemm_params": 7996047360,
        "train_flops": 409518420787200,
        "train_gemm_flops_executed": 542835547832320,
        "prefill_flops": 2000254402560,
        "served_flops": 14394872299520,
        "decode_step_bytes": 5237458944.0,
        "tree": {"embed": (38144, 5120), "lm_head": (5120, 38144),
                 "ln_f": (5120,), "ln1": (16, 5120), "ln2": (16, 5120),
                 "wq": (16, 5120, 5120), "wk": (16, 5120, 1024),
                 "wv": (16, 5120, 1024), "wo": (16, 5120, 5120),
                 "bq": (16, 5120), "bk": (16, 1024), "bv": (16, 1024),
                 "w_gate": (16, 5120, 27648), "w_up": (16, 5120, 27648),
                 "w_down": (16, 27648, 5120)}},
}
NESTED = {"wq": "attn", "wk": "attn", "wv": "attn", "wo": "attn",
          "bq": "attn", "bk": "attn", "bv": "attn", "w_gate": "mlp",
          "w_up": "mlp", "w_down": "mlp", "ln1": "", "ln2": ""}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_dense_counts_and_tree_match_the_parent(name):
    want = PARENT[name]
    m = dense.model_of(_config(name))
    assert dense.gemm_params(m) == want["gemm_params"]
    assert dense.train_flops(m, 2, 4096) == want["train_flops"]
    assert dense.train_gemm_flops_executed(m, 2, 4096, True) \
        == want["train_gemm_flops_executed"]
    assert dense.prefill_flops(m, 128) == want["prefill_flops"]
    assert dense.served_flops(m, 128, 768) == want["served_flops"]
    assert dense.decode_step_bytes(m, 64, 40000) == want["decode_step_bytes"]
    tree = jax.eval_shape(lambda: dense.make_params(
        m, jax.random.PRNGKey(0)))
    got = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [p.key for p in path]
        assert leaf.dtype == jax.numpy.bfloat16
        got[keys[-1]] = tuple(leaf.shape)
        if keys[0] == "layers":
            assert keys[1:-1] == ([NESTED[keys[-1]]]
                                  if NESTED[keys[-1]] else [])
    assert got == want["tree"]


# ---- counts by hand at small shapes --------------------------------------------


def test_gemm_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, mlp 3 x 8x16
    assert dense.layer_gemm_params(M) == 64 + 32 + 32 + 64 + 384
    assert dense.head_params(M) == 80
    assert dense.gemm_params(M) == 3 * 576 + 80


def test_embedding_not_counted():
    bigger_vocab = Model(**{**M.__dict__, "vocab": 1000})
    # only the head grows with the vocabulary: d per extra token
    assert dense.gemm_params(bigger_vocab) - dense.gemm_params(M) \
        == 8 * (1000 - 10)


def test_train_flops_by_hand():
    b, s = 2, 4
    pairs = 4 * 5 // 2                              # causal query-key pairs
    attn_fwd = 4 * pairs * 2 * 4 * 3               # 2 heads x 4 dims x 3
    want = 6 * (3 * 576 + 80) * b * s + 3 * attn_fwd * b
    assert flops.attn_pairs_causal(s) == 10
    assert dense.train_flops(M, b, s) == want


def test_remat_adds_one_forward_of_the_layers_only():
    b, s = 2, 4
    base = dense.train_flops(M, b, s)
    extra = dense.train_gemm_flops_executed(M, b, s, remat=True) - base
    assert dense.train_gemm_flops_executed(M, b, s, remat=False) == base
    assert extra == 2 * 3 * 576 * b * s + 4 * 10 * 2 * 4 * 3 * b


def test_serving_counts():
    p, n = 5, 3
    pre = 2 * 3 * 576 * p + 2 * 80 + 4 * 15 * 2 * 4 * 3
    assert dense.prefill_flops(M, p) == pre
    dec = sum(2 * (3 * 576 + 80) + 4 * (p + j) * 2 * 4 * 3
              for j in (1, 2))
    assert dense.served_flops(M, p, n) == pre + dec
    assert flops.served_rows(p, n) == 6 + 7


def test_decode_bytes_by_hand():
    small = 3 * (2 * 8 + (2 + 2) * 4) + 8          # norms + biases, bf16
    weights = (3 * 576 + 80) * 0.5625 + 2 * small
    row = 2 * 3 * 1 * 4 * 0.5625                    # K and V, all layers
    assert dense.packed_weight_bytes(M) == weights
    assert dense.kv_row_bytes(M) == row
    assert dense.decode_step_bytes(M, slots=2, rows=9) == \
        weights + 2 * 2 * 8 + row * 11


# ---- a new architecture as one file ----------------------------------------------

# The dense module with its QKV biases taken out, as text edits; each must
# find its target, or the throwaway module would silently be the dense one.
NO_BIAS = [
    ('"wo", "bq", "bk", "bv", "w_gate"', '"wo", "w_gate"', 1),
    ('"bq": (qd,), "bk": (kvd,), "bv": (kvd,),\n', "", 1),
    ('("wq", "wk", "wv", "wo", "bq",\n                                           '
     '"bk", "bv")', '("wq", "wk", "wv", "wo")', 1),
    (' + lp["bq"]', "", 2), (' + lp["bk"]', "", 2), (' + lp["bv"]', "", 2),
    ('n_kv_heads=config["num_key_value_heads"])',
     'n_kv_heads=config["num_key_value_heads"],\n'
     '                              qkv_bias=False)', 1),
]


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    d = tmp_path_factory.mktemp("jax_cache")
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(d))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_new_arch_needs_only_its_file(tmp_path, monkeypatch, compile_cache):
    """A bias-free decoder written as ``<arch dir>/dense_nobias.py`` and
    named by the configuration runs the training cell's whole check on the
    CPU: a sound run is correct, a step that hands back its state
    unchanged is not."""
    import train_cell
    src = (HERE / "arch" / "dense.py").read_text()
    for old, new, n in NO_BIAS:
        assert src.count(old) == n, old
        src = src.replace(old, new)
    (tmp_path / "dense_nobias.py").write_text(src)
    monkeypatch.setattr(common, "ARCH", tmp_path)
    spec = tiny.train_spec(TRAIN_LIMITS)
    spec["config"] = dict(spec["config"], arch="dense_nobias",
                          attention_bias=False)
    arch = common.arch(spec["config"], "train")
    tree = jax.eval_shape(lambda: arch.make_params(
        arch.model_of(spec["config"]), jax.random.PRNGKey(0)))
    assert set(tree["layers"]["attn"]) == {"wq", "wk", "wv", "wo"}

    def run(fault):
        out, _, ctx, checks, _, _ = train_cell.run(
            spec, SEED, 0.5, False, time.perf_counter(), fault=fault,
            check_device=False)
        assert ctx["arch"] is arch
        return out["correct"], {k: c["value"] for k, c in checks.items()}

    ok, readings = run("none")
    assert ok, readings
    ok, readings = run("frozen")
    assert not ok, readings
    assert not (HERE / "arch" / "dense_nobias.py").exists()
