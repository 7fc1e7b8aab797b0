"""Traffic kind query_stream: TPC-H's power-test shape, one query stream.

`streams` clients (the cell has one) each send their next query when the last
one answered: POST /v1/query over every file of the table (files x row groups
units, fanned over the daemon's pool), taking turns through `queries` distinct
draws from --seed of Q6's substitution parameters (lib/reference_tpch.py:
DATE x DISCOUNT x QUANTITY, 80 combinations), in seeded order, round robin. A
request carries Q6's predicate as five triples — DATE bounds as ISO strings,
decimal bounds as numeric strings — the cell's `aggregates`, and its own
timeout.

The window closes at the first answer whose last byte arrives at or after
--seconds (lib/loadgen.py's closed mode: a client sends no new query past the
window's end and the one in flight runs to its end), so no query is cut in
half: rows_per_s = correct answers x the table's rows over the time from the
first send to that byte. An answer is correct when its `result` equals the
reference's exactly (count, and revenue to the last digit: the corpus's
workers answered every query per file while the table was written) and its
`units` are the table's; the warm-up answer is also held to `rows_scanned` =
the table's rows (the load generator keeps `result` and `units` only).

The daemon traces every request in a trace of its own and keeps the stage
rollup in its flight recorder (obs/recorder.py); in a traced run the window's
records are folded into the run's trace afterwards (`fold_request_stages`), so
that the per-layer readers of stage seconds read the daemon's stages.

serving.start is bound to lib/reference.py's [fn, column] answers, so the
start-up is here; launch, run and stop are lib/serving.py's.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request
from pathlib import Path

import reference_tpch
import serving


def queries(ctx) -> list:
    import numpy as np

    order = np.random.default_rng([ctx.seed, 2]).permutation(len(reference_tpch.PARAMETERS))
    return [reference_tpch.PARAMETERS[int(i)] for i in order[: ctx.cell["queries"]]]


def start(ctx) -> None:
    """Daemon up, the reference's answers merged, warm-up requests answered
    and compared."""
    from parquet_tpu.serve.server import ScanServer, ServeConfig

    root = str(Path(ctx.facts["paths"][0]).parent)
    device = ctx.device if ctx.config["serve"]["device"] else None
    ctx.server = ScanServer(ServeConfig(host="127.0.0.1", port=0, root=root, device=device)).start_background()
    names = [Path(p).name for p in ctx.facts["paths"]]
    ctx.table_rows = sum(f["rows"] for f in ctx.facts["files"])
    ctx.table_units = len(names) * ctx.corpus["rows_per_file"] // ctx.corpus["row_group_rows"]
    answers = reference_tpch.expected(ctx.facts, len(ctx.queries))
    ctx.requests = [
        {"body": {"paths": names, "filters": reference_tpch.filters(q), "aggregates": ctx.cell["aggregates"],
                  "timeout_ms": int(ctx.cell["timeout_s"] * 1000)}, "want": a}
        for q, a in zip(ctx.queries, answers)
    ]
    for r in ctx.requests[: ctx.cell["warmup_requests"]]:
        with ctx.spans.span("warm-up request"):
            req = urllib.request.Request(ctx.server.url + "/v1/query", data=json.dumps(r["body"]).encode(),
                                         headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=600) as resp:
                got = json.loads(resp.read())
        if got.get("result") != r["want"]:
            raise SystemExit(f"bench: warm-up: the daemon answered {got.get('result')}, the reference {r['want']}")
        if (got.get("rows_scanned"), got.get("units")) != (ctx.table_rows, ctx.table_units):
            raise SystemExit(f"bench: warm-up: scanned {got.get('rows_scanned')} rows in {got.get('units')} units, "
                             f"the table holds {ctx.table_rows} in {ctx.table_units}")
    ctx.before_window = {r["id"] for r in ctx.server.service.recorder.list(limit=10**6)}
    ctx.say(f"warm-up: {ctx.cell['warmup_requests']} whole-table quer(ies) equal the reference to the last digit, "
            f"{ctx.table_rows} rows scanned in {ctx.table_units} units; {len(ctx.requests)} distinct queries ready")


def setup(ctx) -> None:
    try:
        start(ctx)
    except urllib.error.HTTPError as e:
        raise SystemExit(f"bench: warm-up: the daemon refused the query: {e.code} {e.read().decode()[:500]}") from None
    serving.launch(ctx, {"mode": "closed", "clients": ctx.cell["streams"],
                         "seconds": ctx.args.seconds, "timeout_s": ctx.cell["timeout_s"]})


def fold_request_stages(ctx) -> None:
    """Credit the stage seconds and bytes of every request the daemon
    recorded since the warm-up to the trace open on this thread (run.py's, in
    a --trace 1 run; nothing without one)."""
    from parquet_tpu.utils import trace

    recorder = ctx.server.service.recorder
    for summary in recorder.list(limit=10**6, endpoint="/v1/query"):
        record = None if summary["id"] in ctx.before_window else recorder.get(summary["id"])
        for name, s in ((record and record.stages) or {}).items():
            trace.add_seconds(name, s["seconds"], s["bytes"], record_span=False)


def window(ctx, seconds: float) -> dict:
    res = serving.run(ctx)
    fold_request_stages(ctx)
    recs = res["records"]
    good = [r for r in recs if r["ok"] and r["units"] == ctx.table_units]
    elapsed = (max(r["done_ns"] for r in recs) - min(r["sent_ns"] for r in recs)) / 1e9
    rows = len(good) * ctx.table_rows  # a wrong answer is missing from the rate
    ctx.say(f"the window held {len(recs)} whole quer(ies) in {elapsed:.3f} s, {len(good)} correct")
    return {
        "attempted": len(recs), "failed": len(recs) - len(good), "rows": rows, "window_s": elapsed,
        "metrics": {"rows_per_s": rows / elapsed},
        "client": {"latency_ms": [(r["done_ns"] - r["sent_ns"]) / 1e6 for r in good]},
        "spans": res["spans"],
    }


def close(ctx) -> None:
    serving.stop(ctx)
