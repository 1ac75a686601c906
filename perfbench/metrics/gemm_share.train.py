"""Share of the device's busy time spent in dot and convolution ops; the
rest is fake quantization, SR bits and other elementwise work.  Moves
train_tokens_per_s."""


def read(ctx):
    tr = ctx.get("trace", {})
    if not tr.get("busy_s"):
        return None
    return 100.0 * tr["gemm_s"] / tr["busy_s"]
