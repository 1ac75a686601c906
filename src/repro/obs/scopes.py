"""Names that reach the profiler: device scopes and host phase spans.

Device scopes wrap parts of the jitted programs in ``jax.named_scope``.
A scope changes only HLO metadata (each op's ``op_name``), never the
computation, so tokens, losses, fusion and the jit caches stay as they
are.  A profiler trace carries the path in the ``tf_op`` stat of each
device op, which is how a trace's device time is put down to a site:

  * ``fqt.q.<point>`` — one of the six FQT quantize points (the fake
    quantization and, for SR points, its random bits); the point is one
    of ``core/fqt.POINTS``;
  * ``fqt.dot.fwd`` / ``fqt.dot.dgrad`` / ``fqt.dot.wgrad`` — the three
    GEMMs of an FQT matmul (with the Pallas path, the fused
    quantize-and-multiply call); on TPU a packed weight's GEMM is one
    Pallas call here that dequantizes its tiles in VMEM;
  * ``packed.dequant`` — the fallback route of a packed NVFP4 weight (CPU,
    shapes the kernel does not take): unpack, scale and multiply to a
    bf16 weight (``PackedQuantizedTensor.dequant``);
  * ``kv.read`` / ``kv.write`` — the paged KV cache's page gather and row
    dequant, and its row quantize and scatter;
  * ``attn.core`` — attention itself (scores, softmax, the value product),
    in training, prefill and the decode scan;
  * ``monitor.sigma_q`` / ``optim.adamw`` — the train step's σ_q probe of
    the §4 monitor and its optimizer update;
  * ``layers.scan`` — the scan over the stacked layers: its per-layer
    slices of the stacked weights and KV pools and their write-back, and
    the layer's ops that no finer scope names (norms, residual adds).

Host phases are the spans the continuous engine opens inside each
``tick`` span on its ``engine`` track.  The engine opens a
``jax.profiler.TraceAnnotation`` named ``engine.<name>`` beside each of
them whenever its tracer is live, so a profiler trace shows what the host
was doing while the device sat idle.

Stdlib only, like the rest of ``repro.obs``.
"""
from __future__ import annotations

FQT_Q = "fqt.q."           # + the quantize point's name
DOT_FWD = "fqt.dot.fwd"
DOT_DGRAD = "fqt.dot.dgrad"
DOT_WGRAD = "fqt.dot.wgrad"
PACKED_DEQUANT = "packed.dequant"
KV_READ = "kv.read"
KV_WRITE = "kv.write"
ATTN = "attn.core"
SIGMA_PROBE = "monitor.sigma_q"
OPTIM = "optim.adamw"
LAYER_SCAN = "layers.scan"

# the engine track, its tick span, and the phases of a tick in the order
# they run
ENGINE_TRACK = "engine"
TICK = "tick"
PHASES = ("expire", "admit", "prefill_chunk", "pages", "decode", "sync",
          "commit")
