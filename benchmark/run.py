"""One run of one cell of the benchmark (BENCHMARK.json at the checkout's root).

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the chip, sets the cell up (corpus from --seed, native
build, warm-up of the cell's own shapes, full comparison with pyarrow),
measures one window, checks every delivery or response, and prints the
contract's one JSON object as the LAST line of stdout; its last key,
`compared`, holds every number the verdict compares beside its limit, and the
last lines of stderr repeat them. This file knows no cell and no table by
name. Everything particular is found by name:

    BENCHMARK.json                        cells, metrics, bounds
    benchmark/workloads/<cell>.json       the cell: config, traffic kind, parameters
    <config "file">                       the deployment: corpus, serve settings, guarantees
    benchmark/corpora/<corpus.kind>.py    the table: file_name / write_file [/ rehearsal] (lib/corpus.py)
    benchmark/traffic/<kind>.py           setup(ctx) / window(ctx, seconds) / close(ctx) [/ queries(ctx)]
    benchmark/layer_metrics/<name>.json   one per-layer metric: reader kind + arguments
    benchmark/readers/<kind>.py           read(obs, **arguments) -> number or None

`--trace 0` measures with every tracer off and reports the cell's end-to-end
metrics. `--trace 1` runs the program's decode_trace and jax's profiler over
the window and reports the per-layer metrics, the device's busy seconds and a
breakdown. No TPU, or fewer chips than the cell asks for: exit 2, no result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE / "lib"), str(ROOT)]  # spawn workers inherit this

from byname import load_by_name  # noqa: E402


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def plain(counter: str) -> str:
    """`events_total{event="host_decoded_pages"}` -> `host_decoded_pages`: a
    counter's short name for the result's `compared`."""
    return counter.partition('="')[2].rstrip('"}') or counter


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def device_section(jax, chips: int, rehearsal: bool) -> dict:
    devs = jax.devices()
    peak = "not measured"  # a CPU reading is never written under a device metric's name
    if not rehearsal:
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": peak}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="ROWS_PER_GROUP",
                    help="CPU rehearsal at a tiny corpus (needs JAX_PLATFORMS=cpu); the line says so")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override one parameter of the cell's file for this run (rate sweeps, rehearsals)")
    args = ap.parse_args()
    t_start = time.perf_counter()

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if entry is None:
        raise SystemExit(f"bench: no workload {args.workload!r} in BENCHMARK.json")
    cell = json.loads((HERE / "workloads" / f"{args.workload}.json").read_text())
    cell.update((k, json.loads(v)) for k, v in (kv.split("=", 1) for kv in args.set))
    config_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = json.loads((ROOT / config_entry["file"]).read_text())
    corpus = dict(config["corpus"])
    kind = load_by_name("corpora", corpus["kind"])
    if args.rehearse:
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
            raise SystemExit("bench: --rehearse is the CPU rehearsal: set JAX_PLATFORMS=cpu")
        if not hasattr(kind, "rehearsal"):
            raise SystemExit(f"bench: corpus kind {corpus['kind']!r} has no rehearsal(spec, rows): "
                             "it cannot be rehearsed")
        corpus, scale = kind.rehearsal(corpus, args.rehearse)
        # a cell's own row counts (batch_rows, ...) shrink with the corpus
        cell.update((k, max(1, int(v * scale))) for k, v in cell.items() if k.endswith("_rows"))
    traffic = load_by_name("traffic", entry["traffic"])
    cache = HERE / ".cache"
    ctx = SimpleNamespace(
        args=args, cell=cell, config=config, corpus=corpus, corpus_kind=kind, seed=args.seed,
        trace=bool(args.trace), rehearsal=bool(args.rehearse), cache=cache, say=say,
    )

    # the corpus is written by worker processes (numpy + pyarrow, never jax)
    # while this process imports jax, reaches the chip and builds the native library
    from corpus import CorpusJob
    from spans import CompileCounter, Spans

    if importlib.util.find_spec("parquet_tpu") is None:
        print("bench: the program (parquet_tpu/) is not in this checkout", file=sys.stderr)
        return 2
    ctx.queries = traffic.queries(ctx) if hasattr(traffic, "queries") else []
    job = CorpusJob(corpus, args.seed, ctx.queries, cache, workers=min(corpus["files"], os.cpu_count() or 1))
    try:
        try:
            import parquet_tpu.kernels.device_ops as dops  # x64 + compile cache, before any jnp array
            import jax
            from parquet_tpu.utils import metrics as pq_metrics
            from parquet_tpu.utils.native import require_native
            from parquet_tpu.utils.trace import decode_trace
        except ImportError as e:
            print(f"bench: the program is not in this checkout: {e}", file=sys.stderr)
            return 2
        facts = dops.device_facts()
        want = "cpu" if args.rehearse else "tpu"
        if facts["platform"] != want or facts["count"] < entry["chips"]:
            print(f"bench: needs {entry['chips']} {want} device(s); jax found "
                  f"{facts['count']} x {facts['platform']} ({facts['kind']})", file=sys.stderr)
            return 2
        if not args.rehearse:
            from peaks import peaks_for

            peaks_for(facts["kind"])  # an unknown kind is an error, not a default
        require_native()
        ctx.device = jax.devices()[0]
        ctx.compiles, ctx.spans = CompileCounter(), Spans()
        t_ready = time.perf_counter()
        ctx.facts = job.result()
    finally:
        job.close()
    say(f"device {facts['platform']} {facts['kind']!r} x{facts['count']}; corpus "
        f"{'reused' if job.reused else 'written'} ({sum(f['rows'] for f in ctx.facts['files'])} rows, "
        f"{sum(Path(p).stat().st_size for p in ctx.facts['paths'])} bytes on disk); "
        f"imports+chip+native {t_ready - t_start:.1f} s, corpus ready at {time.perf_counter() - t_start:.1f} s")

    trace_dir = cache / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        mark = ctx.compiles.mark()
        traffic.setup(ctx)
        say(f"set-up compile requests: {json.dumps(ctx.compiles.since(mark))}")
        setup_s = time.perf_counter() - t_start

        before = pq_metrics.snapshot()
        mark = ctx.compiles.mark()
        stages = {}
        if ctx.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            try:
                with decode_trace() as tr:
                    wall0 = time.time_ns()
                    with jax.profiler.TraceAnnotation("bench:window"):
                        out = traffic.window(ctx, args.seconds)
                    wall1 = time.time_ns()
            finally:
                jax.profiler.stop_trace()
            stages = {k: {"seconds": s.seconds, "bytes": s.bytes, "calls": s.calls}
                      for k, s in tr.stages.items()}
        else:
            out = traffic.window(ctx, args.seconds)
        in_window = ctx.compiles.since(mark)
        after = pq_metrics.snapshot()
        say(f"compile requests inside the window: {json.dumps(in_window)}")
        device = device_section(jax, entry["chips"], ctx.rehearsal)
    finally:
        traffic.close(ctx)

    # every number the verdict compares; each comparison is exact, so each limit is 0
    problems, compared = [], {"failed": out["failed"]}
    if not ctx.rehearsal:  # a tiny corpus has other shapes than the cell's
        compared["compilations_in_window"] = in_window["requests"]
        if in_window["requests"]:
            problems.append(f"{in_window['requests']} compilation(s) inside the measured window")
    counters = {k: after.get(k, 0) - before.get(k, 0) for k in after
                if isinstance(after[k], (int, float))}
    for key, why in cell.get("must_stay_zero", {}).items():
        compared[plain(key)] = counters.get(key, 0)
        if counters.get(key, 0):
            problems.append(f"{key} rose by {counters[key]} in the window: {why}")

    line = {"correct": False, "attempted": out["attempted"], "failed": out["failed"],
            "metrics": {}, "device": device}
    if ctx.rehearsal:
        line["rehearsal"] = "CPU rehearsal at a tiny corpus: nothing here is a device fact"
    if not ctx.trace:
        values = dict(out["metrics"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if applies(m, args.workload):
                line["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from xplane import newest_xplane, read_trace, reduce_intervals

        xp = None
        pb = newest_xplane(trace_dir)
        if pb is not None:
            devices, window = read_trace(pb)
            shift = 0
            if window is None:
                problems.append("the trace holds no bench:window span")
                window = (wall0, wall1)
            else:
                shift = window[0] - wall0  # wall clock -> the profiler's clock
            spans = [(n, s + shift, e + shift) for n, s, e in ctx.spans.items + out.get("spans", [])]
            xp = reduce_intervals(devices, window, spans)
            say(f"trace: {pb.stat().st_size} bytes, {xp['events']} device events, "
                f"profiler clock - wall clock = {shift / 1e6:.3f} ms")
        if ctx.rehearsal:
            device.update(busy_s="not measured", window_s="not measured")
        elif xp is None or xp["busy_s"] <= 0:
            problems.append("no operation ran on the device inside the traced window")
        if xp is not None and not ctx.rehearsal:
            device.update(busy_s=xp["busy_s"], window_s=xp["window_s"])
            line["breakdown"] = {"device_ops": xp["device_ops"], "idle_gaps": xp["idle_gaps"]}
        obs = SimpleNamespace(
            stages=stages, counters=counters, xplane=None if ctx.rehearsal else xp,
            rows=out.get("rows"), window_s=out["window_s"], client=out.get("client", {}),
        )
        for m in bench["per_layer"]:
            if not applies(m, args.workload):
                continue
            spec = json.loads((HERE / "layer_metrics" / f"{m['name']}.json").read_text())
            value = load_by_name("readers", spec["reader"]).read(obs, **spec.get("args", {}))
            if value is not None:
                line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    for p in problems:
        say(f"NOT CORRECT: {p}")
    line["correct"] = not problems and out["failed"] == 0 and out["attempted"] > 0
    line["compared"] = {name: {"value": v, "limit": 0} for name, v in compared.items()}
    print(json.dumps(line), flush=True)
    for name, v in compared.items():  # the contract's last lines of standard error
        print(f"bench: compared {name} = {v} (limit 0)", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
