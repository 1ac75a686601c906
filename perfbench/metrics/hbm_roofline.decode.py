"""Share of the HBM roofline of a decode step: the bytes a step needs
(the architecture's ``decode_step_bytes``: packed weights once, live cache
rows, new rows, at the window's mean live rows) at the chip's HBM
bandwidth, over the mean device time of a call of the decode program in
the trace.  Moves tpot_p95_s."""
import flops
import xplane


def read(ctx):
    sec, calls = xplane.module_time(ctx.get("trace", {}), "decode")
    if not calls or not ctx["decode_steps"]:
        return None
    rows = sum(flops.served_rows(ctx["prompts"][r], n)
               for r, n in ctx["served"].items()) / ctx["decode_steps"]
    need = ctx["arch"].decode_step_bytes(ctx["model"], ctx["slots"], rows)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / (sec / calls)
