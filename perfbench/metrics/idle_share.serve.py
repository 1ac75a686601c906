"""Share of the serving window in which no op ran on the device (trace).
Moves serve_tokens_per_s."""


def read(ctx):
    tr = ctx.get("trace", {})
    if not tr.get("busy_s"):
        return None
    return 100.0 * max(0.0, 1.0 - tr["busy_s"] / ctx["window_s"])
