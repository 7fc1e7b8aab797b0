"""Traffic kind query_open: dashboard tiles in an open loop.

Each request is one tile: one month's file (3 units) under the cell's query
template (lib/querygen.py: filters and aggregates with values drawn per tile,
the zone with the corpus's own skew). `tiles` distinct tiles from the seed,
months dealt evenly. Arrivals are Poisson at the cell's fixed `rate_per_s`:
the gaps are the N quantile midpoints of the exponential law, in one order
fixed by the cell's `arrival_seed` and rotated by an offset the run's seed
draws. Every run so offers the same arrivals with the same bursts, begun at
another place — a tail is mostly its bursts, and a seed that reshuffled them
would change the work. Latency runs from the instant a request was DUE to its
last byte.
"""

from __future__ import annotations

import math

import serving
from querygen import make_queries
from quantile import quantile


def queries(ctx) -> list:
    import numpy as np

    months = np.random.default_rng([ctx.seed, 4]).permutation(np.arange(ctx.cell["tiles"]) % ctx.corpus["files"])
    return make_queries(ctx, ctx.cell["tiles"], lambda i: [int(months[i])])


def setup(ctx) -> None:
    import numpy as np

    serving.start(ctx)
    rate = ctx.cell["rate_per_s"]
    n = max(1, round(rate * ctx.args.seconds))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])
    np.random.default_rng(ctx.cell["arrival_seed"]).shuffle(gaps)
    gaps = np.roll(gaps, int(np.random.default_rng([ctx.seed, 3]).integers(n)))
    due = np.cumsum(gaps) - gaps[0]
    serving.launch(ctx, {"mode": "open", "due_s": [float(x) for x in due],
                         "seconds": ctx.args.seconds, "timeout_s": ctx.cell["timeout_s"]})


def window(ctx, seconds: float) -> dict:
    res = serving.run(ctx)
    recs = res["records"]
    ok = [r for r in recs if r["ok"]]
    lat = [(r["done_ns"] - r["due_ns"]) / 1e6 for r in ok]
    fifth = max(1, len(lat) // 5)
    ctx.say(f"offered {ctx.cell['rate_per_s']}/s, {len(recs)} requests, {len(recs) - len(ok)} failed; latency ms: "
            f"mean of first fifth {sum(lat[:fifth]) / fifth:.1f}, of last fifth {sum(lat[-fifth:]) / fifth:.1f}, "
            f"p50 {quantile(lat, 0.5):.1f}, p90 {quantile(lat, 0.9):.1f}, max {max(lat):.1f}")
    return {
        "attempted": len(recs), "failed": len(recs) - len(ok),
        "window_s": (max(r["done_ns"] for r in recs) - res["t0_ns"]) / 1e9,
        "metrics": {"latency_p50_ms": quantile(lat, 0.5), "latency_p90_ms": quantile(lat, 0.9)},
        "client": {"late_ms": [(r["sent_ns"] - r["due_ns"]) / 1e6 for r in recs if "sent_ns" in r],
                   "latency_ms": lat},
        "spans": res["spans"],
    }


def close(ctx) -> None:
    serving.stop(ctx)
