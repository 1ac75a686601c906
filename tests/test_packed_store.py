"""Quantize-once packed NVFP4 weight store: equivalence + regressions.

Covers the PR's acceptance criteria:
  * pack_e2m1/unpack_e2m1 round-trip (arithmetic codec, no float4 dtype);
  * PackedQuantizedTensor.dequant == QuantizedTensor.dequant BIT-exact;
  * batched pack_quantize slices == per-matrix fake-quant (the lax.scan
    invariant behind stacked layer weights);
  * fqt.fp4_matmul with a packed weight == fake-quant forward bit-exact
    (jnp impl) and == Pallas packed_block_matmul (interpret);
  * Engine.generate tokens identical packed vs fake-quant;
  * packed params tree save/restores through checkpoint/ckpt.py and is
    <= 0.6 bytes/param on disk for the GEMM weights;
  * regression: fused_quant_matmul honors spec_b's formats (it used to
    silently quantize B with spec_a's data/scale formats);
  * regression: jax.shard_map callable with the distributed layer's
    keyword surface on one device.
"""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs import get_config
from repro.core import fqt
from repro.core.quantize import (NVFP4, BlockQuantSpec, PackedQuantizedTensor,
                                 block_quantize, fake_quant, pack_e2m1,
                                 pack_quantize, pack_quantized, unpack_e2m1)
from repro.models import registry
from repro.serve import ContinuousEngine, Engine, ServeConfig
from repro.serve.packing import (pack_model_params, param_count,
                                 weight_store_bytes)


def _rand(shape, seed=0, dtype=jnp.bfloat16):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape)
                       .astype(np.float32)).astype(dtype)


# ---- codec ------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pack_unpack_roundtrip_all_codes(dtype):
    """Every signed E2M1 grid value survives the nibble round-trip."""
    grid = np.array([0, .5, 1, 1.5, 2, 3, 4, 6], np.float32)
    vals = np.concatenate([grid, -grid]).astype(np.float32)
    x = jnp.asarray(vals, dtype)
    un = unpack_e2m1(pack_e2m1(x), dtype=dtype)
    np.testing.assert_array_equal(np.asarray(un, np.float32),
                                  np.asarray(x, np.float32))


def test_pack_requires_even_last_axis():
    with pytest.raises(ValueError, match="even"):
        pack_e2m1(jnp.zeros((4, 3)))


# ---- packed tensor equivalence ----------------------------------------------


@pytest.mark.parametrize("axis", [0, 1, -2, -1])
def test_packed_dequant_bit_exact(axis):
    x = _rand((64, 64), seed=1)
    qt = block_quantize(x, NVFP4, axis=axis)
    pq = pack_quantized(qt)
    assert pq.scales.dtype == jnp.float8_e4m3fn
    np.testing.assert_array_equal(
        np.asarray(pq.dequant(), np.float32),
        np.asarray(qt.dequant(), np.float32))


def test_pack_quantize_batched_matches_per_slice():
    """Stacked (L, K, N) packing must equal per-layer fake-quant: per-slice
    tensor scales, sliceable as a pytree (what lax.scan does)."""
    W = _rand((3, 32, 48), seed=2)
    pk = pack_quantize(W, NVFP4, axis=-2, batch_dims=1)
    for i in range(3):
        ref = fake_quant(W[i], NVFP4, axis=0)
        sl = jax.tree_util.tree_map(lambda a: a[i], pk)
        np.testing.assert_array_equal(np.asarray(sl.dequant(), np.float32),
                                      np.asarray(ref, np.float32))


def test_pack_quantize_batched_two_level_false():
    """two_level=False (MXFP4) must still give a batch-shaped tscale so
    stacked weights slice under lax.scan (regression: scalar tscale made
    MXFP4-packed serving crash at trace time)."""
    from repro.core.quantize import MXFP4
    W = _rand((3, 32, 48), seed=2)
    pk = pack_quantize(W, MXFP4, axis=-2, batch_dims=1)
    assert pk.tscale.shape == (3,)
    for i in range(3):
        ref = fake_quant(W[i], MXFP4, axis=0)
        sl = jax.tree_util.tree_map(lambda a: a[i], pk)
        np.testing.assert_array_equal(np.asarray(sl.dequant(), np.float32),
                                      np.asarray(ref, np.float32))


def test_engine_tokens_identical_mxfp4(tiny):
    params = registry.init_params(tiny, jax.random.PRNGKey(0))
    scfg = ServeConfig(batch_size=1, max_len=48)
    prompts = [np.random.default_rng(0).integers(0, tiny.vocab_size, 6)]
    qcfg = fqt.mxfp4_config()
    out_p = Engine(tiny, params, scfg, qcfg=qcfg).generate(prompts, max_new=4)
    out_f = Engine(tiny, params, scfg, qcfg=qcfg,
                   pack_weights=False).generate(prompts, max_new=4)
    np.testing.assert_array_equal(out_p[0], out_f[0])


def test_packed_bytes_per_param():
    W = _rand((256, 256), seed=3)
    pk = pack_quantize(W, NVFP4, axis=-2)
    bpp = pk.nbytes() / W.size
    assert bpp <= 0.6, bpp          # 4-bit codes + f8 scale per 16 = 0.5625


def test_packed_forward_bit_exact_jnp():
    x = _rand((16, 128), seed=4)
    w = _rand((128, 96), seed=5)
    cfg = fqt.qaf_config()
    y_fake = fqt.fp4_matmul(x, w, cfg=cfg)
    y_packed = fqt.fp4_matmul(x, pack_quantize(w, NVFP4, axis=-2), cfg=cfg)
    np.testing.assert_array_equal(np.asarray(y_fake, np.float32),
                                  np.asarray(y_packed, np.float32))


def test_packed_kernel_matches_jnp_path():
    x = _rand((64, 128), seed=6)
    w = _rand((128, 64), seed=7)
    pw = pack_quantize(w, NVFP4, axis=-2)
    y_jnp = fqt.fp4_matmul(x, pw, cfg=fqt.qaf_config())
    y_pal = fqt.fp4_matmul(x, pw, cfg=fqt.qaf_config(impl="pallas"))
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_jnp, np.float32),
                               rtol=1e-5, atol=1e-5)


# ---- serving engine ----------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    return get_config("llama2-60m").smoke()


def test_engine_tokens_identical_packed_vs_fake(tiny):
    params = registry.init_params(tiny, jax.random.PRNGKey(0))
    scfg = ServeConfig(batch_size=2, max_len=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, tiny.vocab_size, 8),
               rng.integers(0, tiny.vocab_size, 5)]
    packed = Engine(tiny, params, scfg)                     # default: packed
    fake = Engine(tiny, params, scfg, pack_weights=False)
    out_p = packed.generate(prompts, max_new=8)
    out_f = fake.generate(prompts, max_new=8)
    assert any(isinstance(l, PackedQuantizedTensor)
               for l in jax.tree_util.tree_leaves(
                   packed.params,
                   is_leaf=lambda x: isinstance(x, PackedQuantizedTensor)))
    for a, b in zip(out_p, out_f):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("pack", [True, False])
def test_engine_weights_are_program_arguments(tiny, pack):
    """Both engines hand their weights to every compiled program as an
    argument.  Closed over, they were captured as constants and copied
    into each executable: at tinyllama-1.1b widths that is 2.2 GB per
    program, enough to exhaust host memory while compiling."""
    params = registry.init_params(tiny, jax.random.PRNGKey(0))
    prompts = [np.random.default_rng(0).integers(0, tiny.vocab_size, 6)]
    before = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 64 * 1024)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*constants were "
                                    "captured.*")
            for cls in (Engine, ContinuousEngine):
                cls(tiny, params, ServeConfig(batch_size=1, max_len=32),
                    pack_weights=pack).generate(prompts, max_new=2)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", before)


def test_engine_bf16_config_stays_unpacked(tiny):
    params = registry.init_params(tiny, jax.random.PRNGKey(0))
    eng = Engine(tiny, params, ServeConfig(batch_size=2, max_len=64),
                 qcfg=fqt.bf16_config())
    assert not any(isinstance(l, PackedQuantizedTensor)
                   for l in jax.tree_util.tree_leaves(
                       eng.params,
                       is_leaf=lambda x: isinstance(x, PackedQuantizedTensor)))


# ---- checkpoint export --------------------------------------------------------


def test_packed_checkpoint_roundtrip_and_size(tiny, tmp_path):
    params = registry.init_params(tiny, jax.random.PRNGKey(0))
    packed = pack_model_params(tiny, params, fqt.qaf_config().fwd_w)
    ckpt.save(str(tmp_path), 1, packed)
    restored = ckpt.restore(str(tmp_path), 1, packed)
    for a, b in zip(jax.tree_util.tree_leaves(packed),
                    jax.tree_util.tree_leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the packed GEMM weights are <= 0.6 bytes/param in the store
    packed_leaves = [l for l in jax.tree_util.tree_leaves(
        packed, is_leaf=lambda x: isinstance(x, PackedQuantizedTensor))
        if isinstance(l, PackedQuantizedTensor)]
    stored = sum(l.nbytes() for l in packed_leaves)
    n = sum(int(np.prod(l.shape)) for l in packed_leaves)
    assert stored / n <= 0.6
    # and the whole artifact shrank vs the bf16 tree
    disk = sum(os.path.getsize(os.path.join(r, f))
               for r, _, fs in os.walk(tmp_path) for f in fs)
    assert disk < weight_store_bytes(params)
    assert param_count(packed) == param_count(params)


# ---- regressions --------------------------------------------------------------


def test_fused_quant_matmul_honors_spec_b():
    """fused_quant_matmul used to build the kernel from spec_a's formats
    only, silently misquantizing B when spec_b differed."""
    from repro.kernels import ops, ref
    e8 = BlockQuantSpec(data_fmt="e2m1", scale_fmt="e8m0", block=16,
                        two_level=False)
    a = _rand((32, 64), seed=8, dtype=jnp.float32)
    b = _rand((64, 32), seed=9, dtype=jnp.float32)
    out_k = ops.fused_quant_matmul(a, b, NVFP4, e8)
    out_r = ref.fused_quant_matmul_ref(a, b, NVFP4, e8)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)
    # and the mirrored case (spec_a exotic, spec_b NVFP4)
    out_k2 = ops.fused_quant_matmul(a, b, e8, NVFP4)
    out_r2 = ref.fused_quant_matmul_ref(a, b, e8, NVFP4)
    np.testing.assert_allclose(np.asarray(out_k2), np.asarray(out_r2),
                               rtol=1e-5, atol=1e-5)


def test_fused_quant_matmul_block_mismatch_raises():
    from repro.kernels import ops
    from repro.core.quantize import MXFP4
    a = _rand((32, 64), seed=8, dtype=jnp.float32)
    b = _rand((64, 32), seed=9, dtype=jnp.float32)
    with pytest.raises(ValueError, match="block"):
        ops.fused_quant_matmul(a, b, NVFP4, MXFP4)   # block 16 vs 32


def test_shard_map_compat_single_device():
    """jax.shard_map with the keyword surface the distributed layer calls
    it with (manual ``axis_names``, ``check_vma=False``) on one device."""
    mesh = jax.make_mesh((1,), ("pipe",))
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.psum(x, "pipe")

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(),),
                                out_specs=P(),
                                axis_names=frozenset({"pipe"}),
                                check_vma=False))(jnp.ones((4, 4)))
    np.testing.assert_array_equal(np.asarray(out), np.ones((4, 4)))
