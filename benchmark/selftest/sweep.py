"""The rate sweep of an open-loop cell: the same cell at each offered rate,
one run each, to find the highest rate the system sustains.

    python benchmark/selftest/sweep.py --workload tlc-serve.tiles --rates 2,3,4,5,6,7 [--seconds 20]

A rate is sustained when no request fails and the queue does not grow: the
mean latency of the last fifth of the arrivals stays under twice that of the
first fifth. The cell's file then carries 0.8 x the highest sustained rate, as
a number; this script only prints and records (chiprun_out/bench_runs/
<cell>.sweep.jsonl, and the number in <cell>.rate.txt), it edits nothing.
"""

from __future__ import annotations

import argparse
import json
import re
import sys

from runner import ROOT, run_cell


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", default="2147483700")
    ap.add_argument("--extra", default="")
    a = ap.parse_args()
    out = ROOT / "chiprun_out" / "bench_runs"
    out.mkdir(parents=True, exist_ok=True)
    best = None
    for rate in a.rates.split(","):
        rc, lines, last, err = run_cell(a.workload, a.seed, a.seconds, 0,
                                        ["--set", f"rate_per_s={rate}", *a.extra.split()])
        said = next((x for x in lines if x.startswith("bench: offered")), "")
        m = re.search(r"first fifth ([\d.]+), of last fifth ([\d.]+)", said)
        sustained = bool(last and last["failed"] == 0 and m and float(m.group(2)) < 2 * float(m.group(1)))
        rec = {"cell": a.workload, "rate_per_s": float(rate), "seconds": a.seconds, "rc": rc,
               "sustained": sustained, "said": said, "line": last}
        with open(out / f"{a.workload}.sweep.jsonl", "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"sweep: rate {rate}/s sustained={sustained} {said}", flush=True)
        if rc:
            print(err[-2000:])
        if sustained:
            best = max(best or 0.0, float(rate))
    # what the cell's file is to carry, for the caller that proves the cell in the same chip call
    cell_rate = None if best is None else round(0.8 * best, 1)
    (out / f"{a.workload}.rate.txt").write_text("" if cell_rate is None else str(cell_rate))
    print(f"sweep: highest sustained rate {best}/s -> the cell's rate 0.8 x that = {cell_rate}/s")
    return 0 if best is not None else 1


if __name__ == "__main__":
    sys.exit(main())
