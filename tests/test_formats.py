"""Unit + property tests for the generic minifloat quantizers."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
import ml_dtypes
from hypothesis import given, settings, strategies as st

from repro.core import formats
from repro.core.formats import (E2M1, E4M3, E5M2, E8M0, E3M4, get_format,
                                quantize_rtn, quantize_sr)

jax.config.update("jax_enable_x64", False)


# ---- grids -------------------------------------------------------------------

def test_e2m1_grid():
    np.testing.assert_allclose(E2M1.grid(), [0, .5, 1, 1.5, 2, 3, 4, 6])


def test_e4m3_props():
    assert E4M3.max == 448.0
    assert E4M3.smallest_subnormal == pytest.approx(2.0 ** -9)


def test_e8m0_props():
    assert E8M0.max == 2.0 ** 127
    assert not E8M0.signed


@pytest.mark.parametrize("name,mldt", [
    ("e2m1", ml_dtypes.float4_e2m1fn),
    ("e4m3", ml_dtypes.float8_e4m3fn),
    ("e5m2", ml_dtypes.float8_e5m2),
    ("e3m4", ml_dtypes.float8_e3m4),
])
def test_rtn_matches_ml_dtypes(name, mldt):
    """Our generic RtN must agree bit-exactly with ml_dtypes saturating casts
    on finite, in-range inputs."""
    fmt = get_format(name)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(4096).astype(np.float32) * fmt.max * 0.5
    ours = np.asarray(quantize_rtn(jnp.asarray(x), fmt))
    # ml_dtypes astype is RtN-even but non-saturating at the very top;
    # restrict to clearly in-range values for the comparison.
    mask = np.abs(x) <= fmt.max * 0.99
    theirs = x.astype(mldt).astype(np.float32)
    np.testing.assert_array_equal(ours[mask], theirs[mask])


def test_rtn_saturates():
    out = quantize_rtn(jnp.asarray([1e9, -1e9, 7.0, -6.5]), E2M1)
    np.testing.assert_allclose(out, [6, -6, 6, -6])


def test_rtn_on_grid():
    """Every RtN output is a grid point; error <= half ulp."""
    rng = np.random.default_rng(1)
    for fmt in [E2M1, E4M3, E3M4, get_format("e1m6"), get_format("e6m1")]:
        x = rng.uniform(-fmt.max, fmt.max, 8192).astype(np.float32)
        q = np.asarray(quantize_rtn(jnp.asarray(x), fmt))
        assert formats.snap_distance(q, fmt).max() == 0.0, fmt.name
        # nearest-ness: |x - q| must be <= distance to any other grid point
        d = formats.snap_distance(x.astype(np.float64), fmt)
        np.testing.assert_allclose(np.abs(x - q), d, rtol=1e-5, atol=1e-7)


def test_rtn_ties_to_even():
    # E2M1: 2.5 ties between 2 (even mantissa) and 3 (odd) -> 2
    out = quantize_rtn(jnp.asarray([2.5, 3.5, 1.25, 1.75, 0.25]), E2M1)
    np.testing.assert_allclose(out, [2.0, 4.0, 1.0, 2.0, 0.0])


def test_sr_on_grid():
    rng = np.random.default_rng(2)
    x = rng.uniform(-6, 6, 8192).astype(np.float32)
    q = np.asarray(quantize_sr(jnp.asarray(x), E2M1, jax.random.PRNGKey(0)))
    assert formats.snap_distance(q, E2M1).max() == 0.0
    # SR never moves by more than one grid gap
    lo_hi_gap = 2.0  # largest E2M1 gap (4 -> 6)
    assert np.abs(q - x).max() <= lo_hi_gap


@settings(max_examples=30, deadline=None)
@given(st.floats(min_value=-5.875, max_value=5.875, allow_nan=False,
                 allow_infinity=False, width=32))
def test_sr_unbiased(val):
    """E[Q_SR(x)] == x for in-range x (the core property behind the paper's
    split-rounding scheme and the §4 analysis)."""
    n = 4096
    x = jnp.full((n,), val, dtype=jnp.float32)
    q = quantize_sr(x, E2M1, jax.random.PRNGKey(42))
    mean = float(jnp.mean(q))
    # standard error of the mean of a Bernoulli mixture with gap <= 2
    se = 2.0 / np.sqrt(n)
    assert abs(mean - val) < 5 * se + 1e-6


def test_sr_probabilities():
    """P(round up) == fractional position between neighbours."""
    # 2.75 lies between 2 and 3: p(3) = 0.75
    x = jnp.full((20000,), 2.75, dtype=jnp.float32)
    q = quantize_sr(x, E2M1, jax.random.PRNGKey(7))
    frac_up = float(jnp.mean(q == 3.0))
    assert abs(frac_up - 0.75) < 0.02
    assert set(np.unique(np.asarray(q))) <= {2.0, 3.0}


def test_e8m0_floor():
    x = jnp.asarray([1.0, 1.5, 2.0, 3.9, 0.3])
    np.testing.assert_allclose(formats.e8m0_floor(x), [1, 1, 2, 2, 0.25])
