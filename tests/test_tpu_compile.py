"""The main-path Pallas kernels compile for a TPU v5e, at real widths.

Nothing runs: each kernel is lowered for a described (not attached) v5e
chip and compiled by the TPU compiler that ships with libtpu, so what
Mosaic refuses (a reshape that splits the lane tiling, an unaligned block,
too much fast memory) fails here instead of on the chip.  Shapes are those
of ``chip_smoke.py``: llama2-350m's FQT GEMMs at 8 x 1024 tokens and
tinyllama-1.1b's decode GEMMs over packed NVFP4 weights (8 slots).

The topology is described only inside the module fixture (libtpu may be
loaded by one process at a time), and ``interpret=False`` is passed
explicitly: the kernels' own default picks interpret mode on a CPU host.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quantize import NVFP4
from repro.kernels import fp4_matmul, nvfp4_quant

SR = NVFP4.with_rounding(stochastic=True)
TOKENS = 8 * 1024                                  # llama2-350m train batch
TRAIN_WEIGHTS = {"w_down": (2816, 1024), "lm_head": (1024, 32000)}
DECODE_WEIGHTS = [(2048, 5632), (5632, 2048), (2048, 32000)]   # tinyllama


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("weight", sorted(TRAIN_WEIGHTS))
@pytest.mark.parametrize("gemm", ["forward", "backward", "update"])
def test_fused_quant_matmul_compiles(one_chip, gemm, weight):
    """The three FQT GEMMs of one weight (K, N), blocks along the
    contraction: x @ W (RtN), g @ W^T (SR on g), x^T @ g (SR on both)."""
    K, N = TRAIN_WEIGHTS[weight]
    M, bf, u32 = TOKENS, jnp.bfloat16, jnp.uint32
    if gemm == "forward":
        text = _compiled_text(
            lambda a, b: fp4_matmul.fused_quant_matmul(
                a, b, NVFP4, NVFP4, out_dtype=bf, interpret=False),
            one_chip, ((M, K), bf), ((K, N), bf))
    elif gemm == "backward":
        text = _compiled_text(
            lambda g, w, rg: fp4_matmul.fused_quant_matmul(
                g, w, SR, NVFP4, a_rbits=rg, out_dtype=bf, interpret=False),
            one_chip, ((M, N), bf), ((N, K), bf), ((M, N), u32))
    else:
        text = _compiled_text(
            lambda x, g, rx, rg: fp4_matmul.fused_quant_matmul(
                x, g, SR, SR, a_rbits=rx, b_rbits=rg, out_dtype=bf,
                interpret=False),
            one_chip, ((K, M), bf), ((M, N), bf), ((K, M), u32),
            ((M, N), u32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kn", DECODE_WEIGHTS,
                         ids=lambda kn: f"{kn[0]}x{kn[1]}")
def test_packed_block_matmul_compiles(one_chip, kn):
    """One decode step's GEMM: 8 slots x packed (K, N) NVFP4 weight, uint8
    nibble codes and float8 block scales, as the serving store holds it."""
    K, N = kn
    text = _compiled_text(
        lambda a, codes, scales, ts: fp4_matmul.packed_block_matmul(
            a, codes, scales, ts, NVFP4, out_dtype=jnp.bfloat16,
            interpret=False),
        one_chip, ((8, K), jnp.bfloat16), ((K, N // 2), jnp.uint8),
        ((K // 16, N), jnp.float8_e4m3fn), ((), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("spec", [NVFP4, SR], ids=["rtn", "sr"])
def test_block_quantize_pallas_compiles(one_chip, spec):
    """Standalone quantization of a (tokens, d_ff) activation."""
    shape = (TOKENS, 2816)
    if spec.stochastic:
        text = _compiled_text(
            lambda x, r: nvfp4_quant.block_quantize_pallas(
                x, spec, rbits=r, interpret=False),
            one_chip, (shape, jnp.bfloat16), (shape, jnp.uint32))
    else:
        text = _compiled_text(
            lambda x: nvfp4_quant.block_quantize_pallas(
                x, spec, interpret=False),
            one_chip, (shape, jnp.bfloat16))
    assert "tpu_custom_call" in text
