"""Traffic kind query_closed: whole-year queries in a closed loop.

`clients` clients each send their next query when the last one answered,
taking turns through `queries` distinct whole-year queries from the seed (all
12 files, 36 units) under the cell's query template (lib/querygen.py). Clients
stop sending at --seconds and the requests in flight run to their end:
completed_per_s is every correct answer over the time from the first send to
the last byte of the last answer, so no request is cut in half and none is
left out.
"""

from __future__ import annotations

import serving
from querygen import make_queries


def queries(ctx) -> list:
    return make_queries(ctx, ctx.cell["queries"], lambda i: list(range(ctx.corpus["files"])))


def setup(ctx) -> None:
    serving.start(ctx)
    serving.launch(ctx, {"mode": "closed", "clients": ctx.cell["clients"],
                         "seconds": ctx.args.seconds, "timeout_s": ctx.cell["timeout_s"]})


def window(ctx, seconds: float) -> dict:
    res = serving.run(ctx)
    recs = res["records"]
    ok = [r for r in recs if r["ok"]]
    elapsed = (max(r["done_ns"] for r in recs) - res["t0_ns"]) / 1e9
    return {
        "attempted": len(recs), "failed": len(recs) - len(ok), "window_s": elapsed,
        "metrics": {"completed_per_s": len(ok) / elapsed},
        "client": {"latency_ms": [(r["done_ns"] - r["sent_ns"]) / 1e6 for r in ok]},
        "spans": res["spans"],
    }


def close(ctx) -> None:
    serving.stop(ctx)
