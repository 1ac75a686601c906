"""Property tests for the trip-count-aware HLO cost pass (launch/hlo_cost)
— the §Roofline numbers are only as good as this parser."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.launch import hlo_cost


def _cost(fn, *args):
    return hlo_cost.analyze(jax.jit(fn).lower(*args).compile().as_text())


@settings(max_examples=8, deadline=None)
@given(m=st.sampled_from([32, 64, 128]), k=st.sampled_from([32, 128]),
       n=st.sampled_from([32, 64]))
def test_dot_flops_exact(m, k, n):
    a = jax.ShapeDtypeStruct((m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((k, n), jnp.float32)
    c = _cost(lambda a, b: a @ b, a, b)
    assert c.flops == pytest.approx(2 * m * k * n, rel=0.01)


@settings(max_examples=6, deadline=None)
@given(trips=st.sampled_from([2, 5, 16, 40]))
def test_while_trip_multiplication(trips):
    M = 64
    w = jax.ShapeDtypeStruct((trips, M, M), jnp.float32)
    x = jax.ShapeDtypeStruct((M, M), jnp.float32)

    def f(x, w):
        return jax.lax.scan(lambda h, wi: (jnp.dot(h, wi), None), x, w)[0]

    c = _cost(f, x, w)
    assert c.flops == pytest.approx(trips * 2 * M ** 3, rel=0.01)


def test_nested_scan_multiplies():
    """scan-of-scan: flops must scale by BOTH trip counts."""
    M, outer, inner = 32, 3, 4
    w = jax.ShapeDtypeStruct((outer, inner, M, M), jnp.float32)
    x = jax.ShapeDtypeStruct((M, M), jnp.float32)

    def f(x, w):
        def outer_body(h, wo):
            h, _ = jax.lax.scan(
                lambda hh, wi: (jnp.dot(hh, wi), None), h, wo)
            return h, None
        return jax.lax.scan(outer_body, x, w)[0]

    c = _cost(f, x, w)
    assert c.flops == pytest.approx(outer * inner * 2 * M ** 3, rel=0.01)


def test_bytes_min_le_bytes_and_monotone():
    M = 64
    x = jax.ShapeDtypeStruct((M, M), jnp.float32)
    small = _cost(lambda a: jnp.tanh(a @ a), x)
    big = _cost(lambda a: jnp.tanh(a @ a) @ a + a, x)
    assert 0 <= small.bytes_min <= small.bytes
    assert big.flops > small.flops
    assert big.bytes >= small.bytes


def test_shape_bytes_dtypes():
    assert hlo_cost._shape_bytes("bf16[4,8]") == 64
    assert hlo_cost._shape_bytes("f32[10]{0}") == 40
    assert hlo_cost._shape_bytes("u4[16]") == 8
    assert hlo_cost._shape_bytes("(f32[2,2], bf16[4])") == 24
    assert hlo_cost._shape_bytes("pred[]") == 1    # scalar


def test_collectives_counted_by_kind():
    """A psum under jit with sharding produces an all-reduce whose bytes
    land in the right bucket (uses a tiny 1-device mesh: the collective
    may be optimized away — so parse a synthetic module instead)."""
    hlo = """
HloModule m, entry_computation_layout={()->f32[8]}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %ar = f32[8]{0} all-reduce(%p), to_apply=%add
  ROOT %ag = f32[16]{0} all-gather(%ar), dimensions={0}
}
"""
    c = hlo_cost.analyze(hlo)
    assert c.coll["all-reduce"] == 32
    assert c.coll["all-gather"] == 64
