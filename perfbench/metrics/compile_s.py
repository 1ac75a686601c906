"""Seconds spent in set-up compiling programs or reading them from the
persistent compile cache (JAX's own events, as chip_smoke.Clock counts
them).  Moves setup_s."""


def read(ctx):
    compile_s, _, cache_read_s = ctx["compile"]
    return compile_s + cache_read_s
