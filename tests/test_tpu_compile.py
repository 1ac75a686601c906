"""The main-path Pallas kernels compile for a TPU v5e, at real widths.

Nothing runs: each kernel is lowered for a described (not attached) v5e
chip and compiled by the TPU compiler that ships with libtpu, so what
Mosaic refuses (a reshape that splits the lane tiling, an unaligned block,
too much fast memory) fails here instead of on the chip.  Shapes are those
of ``chip_smoke.py`` (llama2-350m's FQT GEMMs at 8 x 1024 tokens) and of
the serve benchmark's qwen2.5-32b stage: its packed NVFP4 GEMMs at the
64-slot decode and the 256-token prefill.

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
# qwen2.5-32b: wq/wo, wk/wv, w_gate/w_up, w_down, the vocab-parallel head
SERVE_WEIGHTS = [(5120, 5120), (5120, 1024), (5120, 27648), (27648, 5120),
                 (5120, 38016)]


@pytest.fixture(scope="module")
def v5e_2x2():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


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


@pytest.mark.parametrize("m", [64, 256])
@pytest.mark.parametrize("kn", SERVE_WEIGHTS,
                         ids=lambda kn: f"{kn[0]}x{kn[1]}")
def test_packed_block_matmul_compiles(one_chip, kn, m):
    """One served GEMM: m rows of bf16 activation x a packed (K, N) NVFP4
    weight, (K/2, N) uint8 codes and (K/16, N) float8 scales.  (The row
    tiles of an m above 256, such as a 300-token suffix prefill, are
    compiled by ``test_packed_block_matmul_compiles_row_tiles``.)"""
    K, N = kn
    text = _compiled_text(
        lambda a, codes, scales, ts: fp4_matmul.packed_block_matmul(
            a, codes, scales, ts, out_dtype=jnp.bfloat16, interpret=False),
        one_chip, ((m, K), jnp.bfloat16), ((K // 2, N), jnp.uint8),
        ((K // 16, N), jnp.float8_e4m3fn), ((), jnp.float32))
    assert "tpu_custom_call" in text


def test_packed_block_matmul_compiles_row_tiles(one_chip):
    """300 rows run as two row tiles of 160 (padded to 320, not 512)."""
    K, N = 5120, 5120
    assert fp4_matmul.packed_tiles(300, K, N, 16)[0] == 160
    text = _compiled_text(
        lambda a, codes, scales, ts: fp4_matmul.packed_block_matmul(
            a, codes, scales, ts, out_dtype=jnp.bfloat16, interpret=False),
        one_chip, ((300, K), jnp.bfloat16), ((K // 2, N), jnp.uint8),
        ((K // 16, N), jnp.float8_e4m3fn), ((), jnp.float32))
    assert "tpu_custom_call" in text


def test_packed_fp4_matmul_writes_no_bf16_weight(one_chip, monkeypatch):
    """A decode-shaped ``fp4_matmul`` over a packed (5120, 27648) weight
    takes the kernel, and its temporaries stay far below one bf16 copy of
    the weight (the XLA dequant route needed 0.85 GB for 0.08 GB packed)."""
    from repro.core import fqt
    from repro.core.quantize import PackedQuantizedTensor
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    K, N = 5120, 27648
    sd = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa
    w = PackedQuantizedTensor(packed=sd((K // 2, N), jnp.uint8),
                              scales=sd((K // 16, N), jnp.float8_e4m3fn),
                              tscale=sd((), jnp.float32), axis=-2, block=16)
    c = jax.jit(lambda x, w: fqt.fp4_matmul(x, w, cfg=fqt.qaf_config())
                ).lower(sd((64, K), jnp.bfloat16), w).compile()
    assert "tpu_custom_call" in c.as_text()
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < K * N * 2 // 20, temp


@pytest.mark.parametrize("tp", [1, 4])
def test_engine_decode_compiles_on_a_mesh(v5e_2x2, monkeypatch, tp):
    """A ``ContinuousEngine`` decode program compiles for one chip and for
    tp=4 over the four chips of a v5e:2x2.  GSPMD cannot partition a
    Mosaic call, so in the four-chip program every packed GEMM takes the
    dequant route, and in the one-chip program every one takes the
    kernel (seven a layer and the head)."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core import fqt
    from repro.distributed import sharding as shd
    from repro.kernels import ops
    from repro.models import registry
    from repro.serve import ContinuousEngine, ServeConfig
    cfg = dataclasses.replace(get_config("llama2-60m").smoke(), d_model=256,
                              d_ff=512, n_heads=8, n_kv_heads=8)
    scfg = ServeConfig(batch_size=2, max_len=64, eos_id=-1,
                       kv_cache_format="nvfp4", page_size=16)
    eng = ContinuousEngine(cfg, registry.init_params(
        cfg, jax.random.PRNGKey(0)), scfg)                 # on the host
    carry = registry.make_decode_state(cfg, eng.n_slots, scfg.max_len,
                                       kv_cache_format="nvfp4",
                                       page_size=16)
    mesh = Mesh(np.array(v5e_2x2.devices[:tp]), ("model",))
    eng.mesh, eng._rep = mesh, NamedSharding(mesh, P())

    def described(tree, shardings):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return jax.tree_util.tree_unflatten(treedef, [
            jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
            for x, s in zip(leaves, jax.tree_util.tree_leaves(shardings))])

    vec = jax.ShapeDtypeStruct((eng.n_slots,), jnp.int32, sharding=eng._rep)
    args = (described(eng.params,
                      shd.serve_params_shardings(eng.params, mesh)),
            vec, described(carry, shd.serve_cache_shardings(carry, mesh)),
            vec, vec,
            jax.ShapeDtypeStruct((eng.n_slots,), bool, sharding=eng._rep))
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    text = eng._decode.lower(*args).compile().as_text()
    routes = eng.packed_gemms
    took, other = ("kernel", "dequant") if tp == 1 else ("dequant", "kernel")
    assert routes[fqt.PACKED_GEMM + took] == 8, routes
    assert fqt.PACKED_GEMM + other not in routes, routes
    assert ("tpu_custom_call" in text) == (tp == 1)


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
