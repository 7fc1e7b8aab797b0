"""Make the builder's runs of one cell and keep their last lines.

    python benchmark/selftest/measure.py --workload <cell> --seeds 11,12,13 \
        [--sets 2] [--trace 0|1] [--seconds <s>] [--tag <label>] [--describe-trace]

Each run is the benchmark's own command in a process of its own (this parent
never imports jax, so the child holds the chip alone). One record per run is
appended to chiprun_out/bench_runs/<cell>.jsonl: cell, seed, set, trace, tag,
seconds asked, exit code, wall seconds and the parsed last line.
check_bounds.py reads those records. --describe-trace also writes the planes
and lines of the newest trace to chiprun_out/bench_runs/<cell>.trace.json, to
be read by hand before the reduction is trusted.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from runner import ROOT, run_cell


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--tag", default="")
    ap.add_argument("--describe-trace", action="store_true")
    ap.add_argument("--extra", default="", help="further arguments for run.py (rehearsals)")
    a = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = a.seconds if a.seconds is not None else bench["run_seconds"]
    out_dir = ROOT / "chiprun_out" / "bench_runs"
    out_dir.mkdir(parents=True, exist_ok=True)
    bad = 0
    for s in range(a.sets):
        for seed in a.seeds.split(","):
            t0 = time.time()
            rc, lines, last, err = run_cell(a.workload, seed, seconds, a.trace, a.extra.split())
            rec = {"cell": a.workload, "seed": int(seed), "set": s, "trace": a.trace, "tag": a.tag,
                   "seconds": seconds, "rc": rc, "wall_s": time.time() - t0, "line": last}
            with open(out_dir / f"{a.workload}.jsonl", "a") as f:
                f.write(json.dumps(rec) + "\n")
            print("\n".join(x for x in lines if x.startswith("bench:")))
            print(f"measure: {a.workload} set {s} seed {seed} rc {rc} wall {rec['wall_s']:.1f}s "
                  f"{json.dumps(last)}", flush=True)
            if rc or last is None or not last.get("correct"):
                bad += 1
                print(err[-3000:], flush=True)
    if a.describe_trace:
        sys.path.insert(0, str(ROOT / "benchmark" / "lib"))
        from xplane import describe, newest_xplane

        pb = newest_xplane(ROOT / "benchmark" / ".cache" / "trace")
        if pb is not None:
            (out_dir / f"{a.workload}.trace.json").write_text(json.dumps(describe(pb), indent=1))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
