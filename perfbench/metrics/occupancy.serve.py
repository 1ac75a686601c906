"""Mean active decode slots over slots across the window's ticks (the
scheduler's per-tick counter, serve/metrics.py ``active_depth``).  Moves
serve_tokens_per_s."""


def read(ctx):
    return 100.0 * ctx["occupancy"]
