"""Model FLOP utilization of the traced window's training steps: the
operations a step needs (the architecture's ``train_flops``: 6 per GEMM
weight per token plus causal attention, no recompute, no embedding) at
the window's tokens per second, over the chip's bf16 peak.  Moves
train_tokens_per_s."""


def read(ctx):
    m, b, s = ctx["model"], ctx["batch"], ctx["seq"]
    per_s = ctx["arch"].train_flops(m, b, s) * ctx["steps"] / ctx["window_s"]
    return 100.0 * per_s / ctx["peaks"]["bf16_flops_per_s"]
