"""The trace reduction on a small trace recorded on a TPU v5e.

``data/small.xplane.pb`` is the profiler trace of five calls of one
jitted program, ``f(a, b) = tanh(a @ b) * 2 + 1`` on bf16 (1024, 2048) x
(2048, 1024), 10 ms apart, recorded on a TPU v5e; the host's source
paths in it are overwritten with a placeholder of the same length.
"""
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import xplane  # noqa: E402

TRACE = HERE / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(TRACE)


def test_one_device_and_five_calls(red):
    assert red["devices"] == 1
    assert xplane.module_time(red, "jit_f")[1] == 5


def test_busy_is_the_union_of_ops(red):
    ops = sum(red["op_s"].values())
    assert 0 < red["busy_s"] <= ops + 1e-9
    mod, _ = xplane.module_time(red, "jit_f")
    assert red["busy_s"] <= mod * 1.05


def test_gemm_and_the_rest(red):
    assert red["gemm_s"] > 0 and red["other_s"] >= 0
    assert abs(red["gemm_s"] + red["other_s"]
               - sum(red["op_s"].values())) < 1e-9
    # 2 x 1024 x 2048 x 1024 operations a call cannot beat 197 TFLOP/s
    assert 5 * 2 * 1024 * 2048 * 1024 / red["gemm_s"] < 197e12


def test_gaps_are_named_and_ordered(red):
    gaps = red["idle_gaps"]
    assert 0 < len(gaps) <= 10
    secs = [g for _, g in gaps]
    assert secs == sorted(secs, reverse=True)
    # the host slept 10 ms between calls: the longest gaps are that long
    assert secs[0] > 0.005
    assert all(isinstance(n, str) and n for n, _ in gaps)


def test_breakdown_shape(red):
    b = xplane.breakdown(red)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10
    assert all(isinstance(k, str) and v > 0 for k, v in b["device_ops"])
