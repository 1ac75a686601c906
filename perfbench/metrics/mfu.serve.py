"""Model FLOP utilization of serving: the operations of every prompt and
served token processed in the window (the architecture's
``served_flops``), over the window times the bf16 peak.  Independent of
which programs the engine runs.  Moves serve_tokens_per_s."""


def read(ctx):
    a, m = ctx["arch"], ctx["model"]
    need = sum(a.served_flops(m, ctx["prompts"][r], n)
               for r, n in ctx["served"].items())
    return 100.0 * need / ctx["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
