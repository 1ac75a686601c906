"""A run of each cell kind at a size the CPU holds, with the chip check
skipped: a sound run is correct, the control and every planted fault of
the timed path are not.  The limits here are this small size's own, set
from its readings like the cells' limits from theirs (PERF.md)."""
import pathlib
import sys
import time

import jax
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tests"))

import tiny  # noqa: E402

SEED = 2 ** 31 + 77
TRAIN_LIMITS = {"loss_gap": 0.03, "grad_norm_gap": 0.04,
                "change_norm_gap": 0.03}
SERVE_LIMITS = {"token_far_share": 0.15, "request_far_share_max": 0.15}


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    """Each run builds its programs afresh; a persistent cache lets the
    later runs of this module load what the first compiled."""
    d = tmp_path_factory.mktemp("jax_cache")
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(d))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def train(fault="none", quant=None):
    import train_cell
    out, _, _, checks, _, _ = train_cell.run(
        tiny.train_spec(TRAIN_LIMITS), SEED, 0.5, False, time.perf_counter(),
        fault=fault, quant=quant, check_device=False)
    return out["correct"], {k: c["value"] for k, c in checks.items()}


@pytest.mark.parametrize("fault,quant,want", [
    ("none", None, True),
    ("none", "mxfp4", False),        # the control: next precision below
    ("half_batch", None, False),
    ("frozen", None, False),
])
def test_train_cell_check(fault, quant, want):
    ok, readings = train(fault, quant)
    assert ok is want, readings


def test_serve_cell_check_and_control():
    import serve_cell
    spec = tiny.serve_spec(SERVE_LIMITS)
    out, _, ctx, checks, _, _, (key, seqs, logits) = serve_cell.run(
        spec, SEED, 1.0, False, time.perf_counter(), check_device=False)
    assert out["correct"], checks
    # the control's tokens in the served tokens' place, through the same
    # reading and verdict
    ctl = serve_cell.control_stats(ctx["arch"], ctx["model"], key, seqs,
                                   logits)
    _, ok = serve_cell.judge(ctl, SERVE_LIMITS)
    assert not ok, ctl
    # one slot's tokens altered: the worst request shows it
    bad, _, _, bad_checks, *_ = serve_cell.run(
        spec, SEED, 1.0, False, time.perf_counter(), fault="token",
        check_device=False)
    assert not bad["correct"]
    c = bad_checks["request_far_share_max"]
    assert c["value"] > c["limit"], bad_checks
