"""Share of the GEMM roofline: the GEMM operations the window's steps
execute (recompute of rematerialized layers included) at the bf16 peak,
over the device seconds of the trace's dot and convolution ops.  Moves
train_tokens_per_s."""
import flops


def read(ctx):
    tr = ctx.get("trace", {})
    if not tr.get("gemm_s"):
        return None
    m = ctx["model"]
    need = flops.train_gemm_flops_executed(
        m, ctx["batch"], ctx["seq"], ctx["remat"]) * ctx["steps"]
    return 100.0 * need / ctx["peaks"]["bf16_flops_per_s"] / tr["gemm_s"]
