"""Share of the GEMM roofline: the GEMM operations the window's steps
execute (the architecture's ``train_gemm_flops_executed``: recompute of
rematerialized layers included) at the bf16 peak, over the device seconds
of the trace's dot and convolution ops.  Moves train_tokens_per_s."""


def read(ctx):
    tr = ctx.get("trace", {})
    if not tr.get("gemm_s"):
        return None
    need = ctx["arch"].train_gemm_flops_executed(
        ctx["model"], ctx["batch"], ctx["seq"], ctx["remat"]) * ctx["steps"]
    return 100.0 * need / ctx["peaks"]["bf16_flops_per_s"] / tr["gemm_s"]
