"""Launch-layer unit tests: cell specs, skip rules, model-FLOPs accounting,
roofline math (no 512-device mesh needed — that's the dry-run's job)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch import roofline as rl
from repro.launch import specs
from repro.models.config import SHAPES_BY_NAME


def test_skip_rules():
    long = SHAPES_BY_NAME["long_500k"]
    assert specs.skip_reason(get_config("llama3-405b"), long)
    assert specs.skip_reason(get_config("qwen2.5-32b"), long)
    # sub-quadratic archs run 500k decode
    assert specs.skip_reason(get_config("zamba2-1.2b"), long) is None
    assert specs.skip_reason(get_config("xlstm-125m"), long) is None
    assert specs.skip_reason(get_config("mixtral-8x7b"), long) is None
    # everything runs train
    for a in ARCH_IDS:
        assert specs.skip_reason(get_config(a),
                                 SHAPES_BY_NAME["train_4k"]) is None


def test_params_struct_no_allocation():
    """eval_shape only — must hold even for llama3-405b on this laptop."""
    cfg = get_config("llama3-405b")
    ps = specs.params_struct(cfg)
    n = sum(x.size for x in jax.tree.leaves(ps))
    assert 390e9 < n < 430e9, n / 1e9
    assert all(isinstance(x, jax.ShapeDtypeStruct)
               for x in jax.tree.leaves(ps))


def test_batch_struct_shapes():
    cfg = get_config("whisper-base")
    b = specs.batch_struct(cfg, SHAPES_BY_NAME["train_4k"])
    assert b["tokens"].shape == (256, 4097)
    assert b["frames"].shape == (256, cfg.enc_seq, cfg.d_model)


def test_model_flops_moe_active_params():
    """MoE model-FLOPs must use ACTIVE params (top_k/E of expert weight)."""
    cfg = get_config("mixtral-8x7b")
    ps = specs.params_struct(cfg)
    shape = SHAPES_BY_NAME["train_4k"]
    mf = rl.model_flops(cfg, ps, shape)
    total = sum(x.size for x in jax.tree.leaves(ps))
    # mixtral: ~47B total, ~13B active -> model flops well below 6*N_total*D
    assert mf < 6.0 * total * shape.tokens * 0.45
    assert mf > 6.0 * total * shape.tokens * 0.15


def test_roofline_terms_and_bottleneck():
    r = rl.Roofline(flops=197e12, hbm_bytes=819e9 * 2, coll_bytes=50e9 / 2,
                    chips=256, model_flops=197e12 * 256 * 0.5)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(0.5)
    assert r.bottleneck == "memory"
    assert r.t_bound == pytest.approx(2.0)
    # model_flops / (flops_per_device × chips) = 0.5 by construction
    assert r.useful_fraction == pytest.approx(0.5)
    assert r.mfu_bound == pytest.approx(
        r.model_flops / (256 * rl.PEAK_FLOPS * 2.0))


def test_decode_carry_structs_all_archs():
    """make_decode_state eval_shapes for every arch x decode shape."""
    shape = SHAPES_BY_NAME["decode_32k"]
    for a in ("mixtral-8x7b", "zamba2-1.2b", "xlstm-125m", "whisper-base",
              "tinyllama-1.1b"):
        cfg = get_config(a)
        c = specs.decode_carry_struct(cfg, shape)
        leaves = jax.tree.leaves(c)
        assert leaves, a
        # SWA rolling buffer stays window-sized
        if cfg.sliding_window:
            kv = [x for x in leaves if x.ndim == 4 and x.shape[1] > 1]
            assert all(x.shape[2] <= cfg.sliding_window for x in kv)


def test_compile_cache_placement(monkeypatch):
    """Entry points keep JAX's persistent cache where
    JAX_COMPILATION_CACHE_DIR says, else at one fixed in-repo path."""
    import os

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before  # untouched

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path      # fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
