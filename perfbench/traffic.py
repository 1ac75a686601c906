"""The one traffic generator: reads a traffic file's parameters.

Serving traffic is an open loop on the engine's scheduler tick (the only
arrival clock the engine has): a backlog present at tick 0 plus Poisson
arrivals per tick.  Every seed gets the same sizes and the same arrival
schedule, in another order: prompt and output lengths are the quantiles
of the file's clipped lognormals, dealt out by a seeded shuffle within
consecutive groups, so any prefix of the trace holds a near-full mix; the
arrival counts come from the file's own ``arrival_seed``.  The run's seed
picks the order and the prompt tokens.

Training traffic is the program's synthetic token stream
(``data/pipeline.py``), seeded by the run: fixed batch and sequence sizes.
The Poisson draw is the seeded per-tick Poisson of ``serve/workload.py``
and the percentile is ``serve/metrics.py``'s nearest rank, both copied
here so that the yardstick cannot move with the program.
"""
from __future__ import annotations

import math

import numpy as np

GROUP = 16          # sizes are dealt out in shuffled groups of this many


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """n lengths: the lognormal's quantiles at (i + 1/2) / n, clipped."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def arrival_rate(t: dict, slots: int, decode_chunk: int) -> float:
    """λ = load × slots / E[ticks a request holds a slot], with
    E[ticks] = 1 + E[ceil(out / decode_chunk)]."""
    o = t["output"]
    outs = lognormal_quantiles(4096, o["median"], o["sigma"], o["min"],
                               o["max"])
    ticks = 1.0 + float(np.mean(np.ceil(outs / decode_chunk)))
    return t["load"] * slots / ticks


def _dealt(n: int, spec: dict, rng: np.random.Generator) -> np.ndarray:
    """n lengths from ``spec``: the quantiles of GROUP-sized blocks of the
    distribution, each block shuffled by ``rng``."""
    base = lognormal_quantiles(GROUP, spec["median"], spec["sigma"],
                               spec["min"], spec["max"])
    out = [rng.permutation(base) for _ in range(-(-n // GROUP))]
    return np.concatenate(out)[:n] if out else np.zeros(0, int)


def arrivals(t: dict, slots: int, decode_chunk: int, ticks: int
             ) -> np.ndarray:
    """Arrival tick of every request in [0, ticks): the backlog at tick 0,
    then Poisson counts per tick from the traffic's own arrival seed."""
    lam = arrival_rate(t, slots, decode_chunk)
    g = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([t["arrival_seed"]])))
    counts = g.poisson(lam, size=max(ticks, 1))
    counts[0] = int(round(t["backlog"] * slots))
    return np.repeat(np.arange(len(counts)), counts)


def serve_requests(t: dict, seed: int, vocab: int, slots: int,
                   decode_chunk: int, ticks: int):
    """(arrival tick, prompt tokens, output length) of every request that
    arrives in [0, ticks)."""
    arr = arrivals(t, slots, decode_chunk, ticks)
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed % 2 ** 63, 1])))
    plens = _dealt(len(arr), t["prompt"], rng)
    outs = _dealt(len(arr), t["output"], rng)
    prompts = [rng.integers(0, vocab, size=int(p)).astype(np.int32)
               for p in plens]
    return [(int(a), p, int(o)) for a, p, o in zip(arr, prompts, outs)]


def percentile(values, p: float) -> float:
    """Nearest rank: the ceil(p/100 * n)-th smallest value (NaN if none)."""
    v = sorted(float(x) for x in values)
    if not v:
        return math.nan
    return v[max(0, min(int(math.ceil(p / 100.0 * len(v))) - 1,
                        len(v) - 1))]
