"""Mesh-native serving: ONE code path from 1 to N devices.

The contract under test:
  * the 1-device mesh is the DEGENERATE CASE of the same code path — an
    engine built with an explicit ``--mesh`` spec ("tp=1") produces
    bit-identical tokens to the default engine (no ``if sharded:`` fork
    exists to diverge), across nvfp4/fp8/bf16 KV-cache formats;
  * the no-recompile guarantees survive the mesh: three compiled programs
    (prefill / warm-prefix prefill / decode), jit cache sizes == 1 across
    admissions, slot reuse and repeated runs;
  * the spec-derivation layer (distributed/specs.py) keeps block-scale
    axes CONGRUENT with nibble-code axes, normalizes size-1 mesh axes and
    trailing Nones (GSPMD's canonical form — spec equality keys the jit
    compile cache), and DIAGNOSES dropped axes instead of silently
    replicating;
  * real TP=2/4 semantics (subprocess, forced host devices — see
    conftest.run_multidev): sharded engines emit exactly the 1-device
    token streams, column-parallel ``tp_fp4_matmul`` is bitwise equal to
    the 1-device packed forward, row-parallel matches to psum reordering,
    and the packed all-gather round-trips the ~4.5 bits/param wire format.
"""
import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as shd, specs as pspecs
from repro.models import registry
from repro.serve import ContinuousEngine, Engine, Request, ServeConfig

FMTS = ("nvfp4", "fp8", "bf16")
NO_EOS = -1


# ---- spec derivation (pure logic, no devices) ---------------------------------


def test_parse_mesh_spec():
    assert pspecs.parse_mesh_spec(None) == {"model": 1}
    assert pspecs.parse_mesh_spec("") == {"model": 1}
    assert pspecs.parse_mesh_spec("tp=2") == {"model": 2}
    assert pspecs.parse_mesh_spec("dp=2,tp=4") == {"data": 2, "model": 4}
    assert pspecs.parse_mesh_spec("fsdp=2") == {"data": 2, "model": 1}
    for bad in ("tp=0", "tp=-1", "ep=2", "tp", "tp=2;dp=2"):
        with pytest.raises(ValueError):
            pspecs.parse_mesh_spec(bad)


def test_spec_canonical_form():
    """Size-1 axes and trailing Nones must normalize away: GSPMD reports
    jit OUTPUT shardings in canonical form, and PartitionSpec equality
    keys the compile cache — a non-canonical input spec means a spurious
    recompile on call 2."""
    assert pspecs.strip_trailing_none((None, None)) == ()
    assert pspecs.strip_trailing_none(("model", None)) == ("model",)
    # size-1 mesh axis == replication
    assert pspecs.divisible_axes(("model", None), (8, 8),
                                 {"model": 1}) == ()
    out = pspecs.packed_leaf_specs((None, "model"), (64, 32), axis=-2,
                                   block=16, axis_sizes={"model": 1})
    assert out == {"packed": (), "scales": (), "tscale": ()}


def test_packed_leaf_specs_congruent():
    """Scale specs are DERIVED from code specs — congruent by construction
    across kinds/shapes/tp sizes; a dim that cannot shard on every leaf
    is replicated on all of them WITH a diagnostic naming the leaf."""
    for tp in (2, 4):
        out = pspecs.packed_leaf_specs((None, "model"), (64, 32), axis=-2,
                                       block=16, axis_sizes={"model": tp})
        assert out["packed"] == (None, "model")
        assert pspecs.congruent(out["packed"], out["scales"])
    # odd output dim: 30 not divisible by 4 -> dropped, named
    drops = []
    out = pspecs.packed_leaf_specs((None, "model"), (64, 30), axis=-2,
                                   block=16, axis_sizes={"model": 4},
                                   path="layers/attn/wq", drops=drops)
    assert out["packed"] == () and out["scales"] == ()
    assert drops and "layers/attn/wq" in drops[0]


@pytest.mark.parametrize("tp,shards", [(2, True), (4, True), (8, False)])
def test_packed_leaf_specs_halved_blocking_axis(tp, shards):
    """The blocking axis is the halved one: a row-parallel (K, N) weight
    packs to (K/2, N) codes and (K/16, N) scales, and shards on K only
    where every shard holds whole blocks (K/16 divisible); N is not
    halved, so an output dim shards whenever it divides."""
    K, N = 64, 30
    drops = []
    out = pspecs.packed_leaf_specs(("model", None), (K, N), axis=-2,
                                   block=16, axis_sizes={"model": tp},
                                   path="layers/attn/wo", drops=drops)
    assert out["packed"] == (("model",) if shards else ())
    assert pspecs.congruent(out["packed"], out["scales"])
    assert bool(drops) != shards
    col = pspecs.packed_leaf_specs((None, "model"), (K, N), axis=-2,
                                   block=16, axis_sizes={"model": 2})
    assert col["packed"] == (None, "model")


def test_wire_format_accounting():
    """NVFP4 wire format: 4-bit codes + one f8 scale per 16 = 4.5 bits."""
    assert pspecs.packed_wire_bits_per_param() == 4.5
    assert pspecs.packed_gather_ratio() == pytest.approx(16 / 4.5)


def test_divisible_diagnoses_dropped_axes(caplog):
    """Satellite: no silent replication fallback — named leaves log (or
    raise, strict=True) a diagnostic identifying the leaf path."""
    mesh = shd.make_serve_mesh(None)

    class _M:                                   # 2-device stand-in mesh
        axis_names = ("model",)

        class devices:
            shape = (2,)

    with caplog.at_level(logging.WARNING, "repro.distributed.sharding"):
        spec = shd._divisible(P("model"), (15,), _M(), path="mlp/w_up")
    assert spec == P()
    assert any("mlp/w_up" in r.message for r in caplog.records)
    with pytest.raises(ValueError, match="mlp/w_up"):
        shd._divisible(P("model"), (15,), _M(), path="mlp/w_up",
                       strict=True)
    # anonymous (activation-constraint) calls stay silent
    with caplog.at_level(logging.WARNING, "repro.distributed.sharding"):
        n0 = len(caplog.records)
        assert shd._divisible(P("model"), (15,), _M()) == P()
    assert len(caplog.records) == n0
    del mesh


# ---- 1-device mesh (fast, in-process) -----------------------------------------


@pytest.fixture(scope="module")
def tiny():
    return get_config("llama2-60m").smoke()


@pytest.fixture(scope="module")
def tiny_params(tiny):
    return registry.init_params(tiny, jax.random.PRNGKey(0))


def _scfg(fmt="nvfp4", **kw):
    kw.setdefault("eos_id", NO_EOS)
    return ServeConfig(batch_size=2, max_len=64, kv_cache_format=fmt,
                       page_size=16, **kw)


def test_make_serve_mesh_default_and_errors():
    mesh = shd.make_serve_mesh(None)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {"model": 1}
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        shd.make_serve_mesh("tp=8")


def test_spec_for_packed_replicated_on_one_device(tiny, tiny_params):
    """On the 1-device mesh every packed leaf canonicalizes to P() —
    placement is the identity, the degenerate case of the same rules."""
    from repro.core.quantize import pack_quantize
    mesh = shd.make_serve_mesh(None)
    pw = pack_quantize(jnp.ones((64, 32), jnp.float32), axis=-2)
    sh = shd.spec_for_packed("layers/attn/wq", pw, mesh)
    assert sh == {"packed": P(), "scales": P(), "tscale": P()}


@pytest.mark.parametrize("fmt", FMTS)
def test_explicit_mesh_engine_token_identical(tiny, tiny_params, fmt):
    """ContinuousEngine under an explicit 1-device mesh spec is BIT-
    identical (no margin gate) to the default engine: same code path."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, tiny.vocab_size, 8) for _ in range(2)]
    base = ContinuousEngine(tiny, tiny_params, _scfg(fmt))
    out_b = base.generate(prompts, max_new=8)
    meshed = ContinuousEngine(tiny, tiny_params,
                              dataclasses.replace(_scfg(fmt), mesh="tp=1"))
    out_m = meshed.generate(prompts, max_new=8)
    for a, b in zip(out_m, out_b):
        np.testing.assert_array_equal(a, b)
    assert meshed.prefill_compiles == 1 and meshed.decode_compiles == 1


def test_lockstep_mesh_engine_token_identical(tiny, tiny_params):
    """Same for the lockstep Engine, with the mesh passed explicitly."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, tiny.vocab_size, 8) for _ in range(2)]
    out_b = Engine(tiny, tiny_params, _scfg()).generate(prompts, max_new=8)
    eng = Engine(tiny, tiny_params, _scfg(),
                 mesh=shd.make_serve_mesh("tp=1"))
    out_m = eng.generate(prompts, max_new=8)
    for a, b in zip(out_m, out_b):
        np.testing.assert_array_equal(a, b)


def test_mesh_engine_no_recompile_across_runs(tiny, tiny_params):
    """Jit-cache guards under the mesh: slot reuse, a second run, and the
    stable-sharding carry/token annotations keep all three programs at
    ONE compilation each."""
    scfg = dataclasses.replace(_scfg("nvfp4"), mesh="tp=1")
    rng = np.random.default_rng(2)
    eng = ContinuousEngine(tiny, tiny_params, scfg)
    mk = lambda rid, n, arr=0: Request(
        rid, rng.integers(0, tiny.vocab_size, n), max_new=6, arrival=arr)
    eng.run([mk(0, 8), mk(1, 6), mk(2, 5, arr=1)])   # queued -> freed slot
    eng.run([mk(3, 7), mk(4, 4)])                    # second trace
    assert eng.prefill_compiles == 1
    assert eng.decode_compiles == 1
    assert eng.prefill_suffix_compiles <= 1


# ---- real TP (subprocess, forced host devices) --------------------------------


_TP_ENGINE = """
    import dataclasses
    import jax, numpy as np
    from repro.configs import get_config
    from repro.models import registry
    from repro.serve import ContinuousEngine, ServeConfig

    cfg = get_config("llama2-60m").smoke()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    scfg = ServeConfig(batch_size=2, max_len=64, kv_cache_format="nvfp4",
                       page_size=16, eos_id=-1)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 8) for _ in range(2)]

    ref = ContinuousEngine(cfg, params, scfg).generate(prompts, max_new=8)
    for tp in (2, 4):
        eng = ContinuousEngine(cfg, params,
                               dataclasses.replace(scfg, mesh=f"tp={tp}"))
        out = eng.generate(prompts, max_new=8)
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert eng.prefill_compiles == 1 and eng.decode_compiles == 1, tp
        print(f"tp{tp} engine OK")
"""


@pytest.mark.slow
def test_tp_engine_token_identical_multidevice(run_multidev):
    """TP=2 and TP=4 ContinuousEngine: EXACTLY the 1-device token streams
    (TP reduction orders are fixed per device count by the psum tree; the
    quantize-once packed weights make the local GEMMs bit-stable), with
    the one-compile-per-program guarantee intact."""
    r = run_multidev(_TP_ENGINE)
    assert "tp2 engine OK" in r.stdout
    assert "tp4 engine OK" in r.stdout


_TP_MATMUL = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import fqt
    from repro.core.quantize import pack_quantize
    from repro.distributed.compression import allgather_packed
    from repro.distributed.sharding import make_serve_mesh
    from repro.kernels.fp4_matmul import tp_fp4_matmul

    cfg = fqt.qaf_config()
    K, N = 64, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (4, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32) * 0.2
    pw = pack_quantize(w, axis=-2)

    # 1-device reference: quantize-a once, dequantized packed GEMM
    qx = fqt._maybe_q(x, fqt._if_divisible(cfg.fwd_a, K), axis=-1,
                      seed=jnp.zeros((), jnp.uint32), site=0)
    ref = jnp.matmul(qx, pw.dequant(),
                     preferred_element_type=jnp.float32).astype(x.dtype)

    mesh = make_serve_mesh("tp=2")
    col = tp_fp4_matmul(x, pw, cfg=cfg, mesh=mesh, parallel="column")
    np.testing.assert_array_equal(np.asarray(col), np.asarray(ref))
    row = tp_fp4_matmul(x, pw, cfg=cfg, mesh=mesh, parallel="row")
    np.testing.assert_allclose(np.asarray(row), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    print("tp matmul OK")

    # FSDP-style gather of the PACKED wire format: bitwise column result
    mesh2 = make_serve_mesh("dp=2,tp=2")
    both = tp_fp4_matmul(x, pw, cfg=cfg, mesh=mesh2, parallel="column",
                         gather_axis="data")
    np.testing.assert_array_equal(np.asarray(both), np.asarray(ref))
    assert pw.wire_nbytes() == K * (N // 2) + (K // 16) * N
    print("packed gather OK")
"""


@pytest.mark.slow
def test_tp_matmul_collectives_multidevice(run_multidev):
    """The explicit Megatron decomposition of the packed GEMM: column-
    parallel bitwise == 1-device (activation quantized once, globally),
    row-parallel allclose (psum reorder only), and the ~4.5 bits/param
    packed all-gather reconstructs the exact weight shards."""
    r = run_multidev(_TP_MATMUL)
    assert "tp matmul OK" in r.stdout
    assert "packed gather OK" in r.stdout
