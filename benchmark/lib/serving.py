"""What the two query traffic kinds share: the daemon inside this process
(it holds the chip, so the profiler here can trace it), the warm-up requests,
and the load generator child (lib/loadgen.py, never imports jax)."""

from __future__ import annotations

import json
import subprocess
import sys
import urllib.request
from pathlib import Path

from reference import expected, request_body


def start(ctx) -> None:
    """Daemon up, answers computed, warm-up requests answered and checked,
    load generator loaded with its plan and waiting for "go"."""
    from parquet_tpu.serve.server import ScanServer, ServeConfig

    root = str(Path(ctx.facts["paths"][0]).parent)
    device = ctx.device if ctx.config["serve"]["device"] else None
    ctx.server = ScanServer(ServeConfig(host="127.0.0.1", port=0, root=root, device=device)).start_background()
    names = [Path(p).name for p in ctx.facts["paths"]]
    answers = expected(ctx.facts, len(ctx.queries))
    ctx.requests = [{"body": request_body(q, names), "want": a} for q, a in zip(ctx.queries, answers)]
    for r in ctx.requests[: ctx.cell["warmup_requests"]]:
        with ctx.spans.span("warm-up request"):
            req = urllib.request.Request(ctx.server.url + "/v1/query", data=json.dumps(r["body"]).encode(),
                                         headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=600) as resp:
                got = json.loads(resp.read())
        if got.get("result") != r["want"]:
            raise SystemExit(f"bench: warm-up: the daemon answered {got.get('result')}, pyarrow {r['want']}")
    ctx.say(f"warm-up: {ctx.cell['warmup_requests']} request(s) equal pyarrow's answers; "
            f"{len(ctx.requests)} distinct queries ready")


def launch(ctx, plan: dict) -> None:
    ctx.plan_path = ctx.cache / "loadgen_plan.json"
    ctx.results_path = ctx.cache / "loadgen_results.json"
    ctx.results_path.unlink(missing_ok=True)
    ctx.plan_path.write_text(json.dumps(dict(plan, url=ctx.server.url, requests=ctx.requests)))
    ctx.child = subprocess.Popen(
        [sys.executable, str(Path(__file__).with_name("loadgen.py")), str(ctx.plan_path), str(ctx.results_path)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
    )
    if ctx.child.stdout.readline().strip() != "ready":
        raise SystemExit("bench: the load generator did not start")


def run(ctx) -> dict:
    """Say go, wait for the generator to finish, return its records plus the
    spans they make: a request in flight, inside the idle between arrivals."""
    with ctx.spans.span("idle between arrivals"):
        ctx.child.stdin.write("go\n")
        ctx.child.stdin.flush()
        said = ctx.child.stdout.readline().strip()
    if said != "done" or ctx.child.wait(timeout=60) != 0:
        raise SystemExit("bench: the load generator failed")
    res = json.loads(ctx.results_path.read_text())
    res["records"].sort(key=lambda r: r["due_ns"])
    res["spans"] = [("request in flight", r["sent_ns"], r["done_ns"]) for r in res["records"] if "sent_ns" in r]
    for r in res["records"]:
        if not r["ok"]:
            ctx.say(f"request {r['i']} failed: status {r['status']} {r.get('error', '')} got {r.get('got')}")
    return res


def stop(ctx) -> None:
    child = getattr(ctx, "child", None)
    if child is not None:
        if child.poll() is None:
            child.kill()
        child.wait()
    server = getattr(ctx, "server", None)
    if server is not None:
        server.close()
