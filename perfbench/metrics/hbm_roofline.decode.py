"""Share of the HBM roofline of a decode step: the bytes a step needs
(packed weights once, live cache rows, new rows; flops.decode_step_bytes
at the window's mean live rows) at the chip's HBM bandwidth, over the
mean device time of a call of the decode program in the trace.  Moves
tpot_p95_s."""
import flops
import xplane


def read(ctx):
    sec, calls = xplane.module_time(ctx.get("trace", {}), "decode")
    if not calls or not ctx["decode_steps"]:
        return None
    m = ctx["model"]
    rows = sum(flops.served_rows(ctx["prompts"][r], n)
               for r, n in ctx["served"].items()) / ctx["decode_steps"]
    need = flops.decode_step_bytes(m, ctx["slots"], rows)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / (sec / calls)
