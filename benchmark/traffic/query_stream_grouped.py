"""Traffic kind query_stream_grouped: TPC-H's power-test shape, one stream of
a GROUPED query — Q1, the Pricing Summary Report.

`streams` clients (the cell has one) each send their next query when the last
one answered: POST /v1/query over every file of the table (files x row groups
units, fanned over the daemon's pool), taking turns through `queries` distinct
draws from --seed of Q1's substitution parameter (lib/reference_tpch_q1.py:
DELTA in 60..120, 61 values), in seeded order, round robin. A request carries
Q1's predicate as one triple — the DATE bound as an ISO string — the cell's
`group_by` and `aggregates`, and its own timeout.

The window closes at the first answer whose last byte arrives at or after
--seconds (lib/loadgen_grouped.py: a client sends no new query past the
window's end and the one in flight runs to its end), so no query is cut in
half: rows_per_s = correct answers x the table's rows over the time from the
first send to that byte. An answer is correct when its `groups` equal the
reference's exactly — keys, their order, every aggregate's text, the count —
its `group_count` is their number and its `units` are the table's; the warm-up
answer is also held to `rows_scanned` = the table's rows.

query_stream's sibling (that kind and lib/loadgen.py compare `result`, which a
grouped answer does not have): the window, the fold of the daemon's
flight-recorder rollups into the run's trace and the close are query_stream's
own functions; the start-up and the launch of the grouped load generator are
here.
"""

from __future__ import annotations

import json
import subprocess
import sys
import urllib.error
import urllib.request
from pathlib import Path

import reference_tpch_q1
import serving
from byname import load_by_name
from loadgen_grouped import same_groups

# run the window, fold the daemon's stages, count whole correct queries, stop: query_stream's, as they are
_stream = load_by_name("traffic", "query_stream")
window, close, fold_request_stages = _stream.window, _stream.close, _stream.fold_request_stages


def queries(ctx) -> list:
    import numpy as np

    order = np.random.default_rng([ctx.seed, 2]).permutation(len(reference_tpch_q1.PARAMETERS))
    return [reference_tpch_q1.PARAMETERS[int(i)] for i in order[: ctx.cell["queries"]]]


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
    answers = reference_tpch_q1.expected(ctx.facts, len(ctx.queries))
    ctx.requests = [
        {"body": {"paths": names, "filters": reference_tpch_q1.filters(q), "group_by": ctx.cell["group_by"],
                  "aggregates": ctx.cell["aggregates"], "timeout_ms": int(ctx.cell["timeout_s"] * 1000)}, "want": a}
        for q, a in zip(ctx.queries, answers)
    ]
    for r in ctx.requests[: ctx.cell["warmup_requests"]]:
        with ctx.spans.span("warm-up request"):
            req = urllib.request.Request(ctx.server.url + "/v1/query", data=json.dumps(r["body"]).encode(),
                                         headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=600) as resp:
                got = json.loads(resp.read())
        if not same_groups(got, r["want"]):
            raise SystemExit(f"bench: warm-up: the daemon answered {got.get('groups')}, the reference {r['want']}")
        if (got.get("rows_scanned"), got.get("units")) != (ctx.table_rows, ctx.table_units):
            raise SystemExit(f"bench: warm-up: scanned {got.get('rows_scanned')} rows in {got.get('units')} units, "
                             f"the table holds {ctx.table_rows} in {ctx.table_units}")
    ctx.before_window = {r["id"] for r in ctx.server.service.recorder.list(limit=10**6)}
    ctx.say(f"warm-up: {ctx.cell['warmup_requests']} whole-table quer(ies) equal the reference group by group to the "
            f"last digit, {ctx.table_rows} rows scanned in {ctx.table_units} units; {len(ctx.requests)} distinct "
            "queries ready")


def launch(ctx, plan: dict) -> None:
    """serving.launch with the grouped load generator (that one starts
    lib/loadgen.py by name)."""
    ctx.plan_path = ctx.cache / "loadgen_plan.json"
    ctx.results_path = ctx.cache / "loadgen_results.json"
    ctx.results_path.unlink(missing_ok=True)
    ctx.plan_path.write_text(json.dumps(dict(plan, url=ctx.server.url, requests=ctx.requests)))
    generator = Path(serving.__file__).with_name("loadgen_grouped.py")
    ctx.child = subprocess.Popen([sys.executable, str(generator), str(ctx.plan_path), str(ctx.results_path)],
                                 stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    if ctx.child.stdout.readline().strip() != "ready":
        raise SystemExit("bench: the load generator did not start")


def setup(ctx) -> None:
    try:
        start(ctx)
    except urllib.error.HTTPError as e:
        raise SystemExit(f"bench: warm-up: the daemon refused the query: {e.code} {e.read().decode()[:500]}") from None
    launch(ctx, {"mode": "closed", "clients": ctx.cell["streams"], "seconds": ctx.args.seconds,
                 "timeout_s": ctx.cell["timeout_s"]})
