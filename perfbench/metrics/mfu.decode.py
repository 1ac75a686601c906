"""The decode program's share of the chip's bf16 peak: the operations of
the window's decode steps (the architecture's ``served_flops`` less its
``prefill_flops``: 2 per GEMM weight per served token after the first,
plus attention over its live rows), over the device seconds of the
decode program in the trace.  Moves tpot_p95_s."""
import xplane


def read(ctx):
    sec, calls = xplane.module_time(ctx.get("trace", {}), "decode")
    if not calls:
        return None
    a, m = ctx["arch"], ctx["model"]
    need = sum(a.served_flops(m, ctx["prompts"][r], n)
               - a.prefill_flops(m, ctx["prompts"][r])
               for r, n in ctx["served"].items())
    return 100.0 * need / sec / ctx["peaks"]["bf16_flops_per_s"]
