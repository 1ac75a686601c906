"""The reference's number formats, checked against ml_dtypes' casts and
against the program's quantizer (the reference itself imports neither)."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import reference as ref  # noqa: E402


def _values(n=20000, seed=0):
    r = np.random.default_rng(seed)
    x = r.standard_normal(n) * np.exp(r.uniform(-6, 4, n))
    return np.concatenate([x, [0.0, 0.25, 0.75, 1.75, 2.5, 5.0, 6.0, 7.0,
                               -2.5, -5.0, 100.0]]).astype(np.float32)


def test_e2m1_matches_float4_cast():
    x = _values()
    want = np.clip(x, -6, 6).astype(ml_dtypes.float4_e2m1fn).astype(
        np.float32)
    np.testing.assert_array_equal(np.asarray(ref.e2m1_rtn(jnp.asarray(x))),
                                  want)


def test_e4m3_matches_float8_cast():
    x = np.abs(_values())
    want = np.minimum(x, 448).astype(ml_dtypes.float8_e4m3fn).astype(
        np.float32)
    np.testing.assert_array_equal(np.asarray(ref.e4m3_rtn(jnp.asarray(x))),
                                  want)


def test_sr_is_unbiased_and_on_grid():
    x = jnp.full((200000,), 2.3, jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(0), x.shape)
    q = np.asarray(ref.e2m1_sr(x, u))
    assert set(np.unique(q)) == {2.0, 3.0}
    assert abs(q.mean() - 2.3) < 0.01


@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("fmt", ["nvfp4", "mxfp4"])
def test_block_quantize_matches_program(fmt, axis):
    from repro.core.quantize import MXFP4, NVFP4, fake_quant
    spec = {"nvfp4": NVFP4, "mxfp4": MXFP4}[fmt]
    x = (jax.random.normal(jax.random.PRNGKey(1), (64, 96)) * 3).astype(
        jnp.bfloat16)
    got = ref.quantize(x, ref.FORMATS[fmt], axis)
    want = fake_quant(x, spec, axis=axis).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kv_rows_match_program_cache_rows():
    from repro.core.quantize import kv_dequant, kv_quant_rows
    x = (jax.random.normal(jax.random.PRNGKey(2), (8, 4, 128)) * 2).astype(
        jnp.bfloat16)
    codes, scales = kv_quant_rows(x, "nvfp4", 16)
    want = kv_dequant(codes, scales, "nvfp4", 16, jnp.float32)
    np.testing.assert_array_equal(np.asarray(ref.kv_rows(x, ref.NVFP4)),
                                  np.asarray(want))
