"""Every end-to-end bound beside the spread the builder measured, held to the
limits the driver's check applies. The check that sank PR 22.

    python benchmark/selftest/check_bounds.py [--records DIR] [--tag proof]

Reads the run records measure.py wrote (default benchmark/selftest/records/,
the builder's chip runs as committed; chiprun_out/bench_runs/ while working):
per cell two sets of runs with the same seeds. For every end-to-end metric and
every cell that reports it:

  spread   (third quartile - first quartile) / median of one set's runs, by
           statistics.quantiles(values, n=4);
  tight    the mean of the two sets' spreads, each set's run farthest from its
           median left out where that narrows its spread (the check's own
           words on a refusal in PERF_LEDGER.jsonl): the bound is TOO TIGHT if
           tight > bound / 2. The check's note on an accepted change ("the
           runs spread by ... more than 50% of what the bound will be") reads
           this same number, not a range: one such note gives 1.83 % of the
           median on a line whose whole-set spread is 4.1 %;
  loose    the wider of the two sets' spreads over all their runs: the bound is
           TOO LOOSE if it is over 8 x the widest `loose` over all cells,
           unless it is 1 % (never too loose);
  drift    |median of set 1 - median of set 0| / median of set 0, which may not
           pass the bound (setup_s: only getting worse counts).

setup_s leaves out the first run of the records (the one that compiles) and is
judged by drift alone; its bound is 0.25 by contract. Exits 1 if any pair
breaks a limit, 2 if a metric of BENCHMARK.json has no records in some cell.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def tight_spread(values: list) -> float:
    """spread() with the run farthest from the median left out, where that
    narrows it."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return min(spread(values), spread([v for i, v in enumerate(values) if i != far]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", default=str(ROOT / "benchmark" / "selftest" / "records"))
    ap.add_argument("--tag", default="proof")
    a = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    runs: dict = {}  # cell -> set -> [metrics dict], in the order run
    for path in sorted(Path(a.records).glob("*.jsonl")):
        for raw in path.read_text().splitlines():
            r = json.loads(raw)
            if r["tag"] == a.tag and r["trace"] == 0 and r["rc"] == 0 and r["line"] and r["line"]["correct"] \
                    and r["seconds"] == bench["run_seconds"]:
                runs.setdefault(r["cell"], {}).setdefault(r["set"], []).append(
                    {k: v["value"] for k, v in r["line"]["metrics"].items()})
    rc = 0
    print(f"{'metric':<16}{'cell':<20}{'n':>6}{'median0':>14}{'median1':>14}{'spread0':>9}{'spread1':>9}"
          f"{'tight':>8}{'loose':>8}{'drift':>8}")
    for m in bench["end_to_end"]:
        name, bound = m["name"], m["bound"]
        cells = [w["name"] for w in bench["workloads"] if w["name"] in m.get("workloads", [w["name"]])]
        widest_loose, verdicts = 0.0, []
        for cell in cells:
            sets = runs.get(cell, {})
            if len(sets) < 2:
                print(f"{name:<16}{cell:<20} no two sets of records")
                rc = max(rc, 2)
                continue
            s0, s1 = ([r[name] for r in sets[k]] for k in sorted(sets)[:2])
            if name == "setup_s":
                s0 = s0[1:]  # the first run compiles: recorded apart
            if min(len(s0), len(s1)) < 3:
                print(f"{name:<16}{cell:<20} fewer than 3 runs in a set")
                rc = max(rc, 2)
                continue
            m0, m1 = statistics.median(s0), statistics.median(s1)
            sp0, sp1 = spread(s0), spread(s1)
            tight = (tight_spread(s0) + tight_spread(s1)) / 2
            loose = max(sp0, sp1)
            drift = (m1 - m0) / m0
            worse = drift if m["better"] == "lower" else -drift
            print(f"{name:<16}{cell:<20}{f'{len(s0)}+{len(s1)}':>6}{m0:>14.4f}{m1:>14.4f}{sp0:>9.2%}{sp1:>9.2%}"
                  f"{tight:>8.2%}{loose:>8.2%}{drift:>+8.2%}")
            if name == "setup_s":
                if worse > bound:
                    verdicts.append(f"{cell}: set 1's median is {worse:.1%} worse than set 0's")
                continue
            widest_loose = max(widest_loose, loose)
            if tight > bound / 2:
                verdicts.append(f"TOO TIGHT in {cell}: tight spread {tight:.2%} > bound/2 = {bound / 2:.2%}")
            if abs(drift) > bound:
                verdicts.append(f"{cell}: the two sets' medians differ by {abs(drift):.2%} > bound")
        if name != "setup_s" and bound > max(8 * widest_loose, 0.01):
            verdicts.append(f"TOO LOOSE: bound {bound:.2%} > 8 x widest spread {widest_loose:.2%} "
                            f"= {8 * widest_loose:.2%} (and over 1 %)")
        if not 0.01 <= bound <= 0.25:
            verdicts.append(f"bound {bound} outside 1 %..25 %")
        note = "" if name == "setup_s" else (
            f"widest spread {widest_loose:.2%}: 5 x = {5 * widest_loose:.2%}, "
            f"at most 8 x = {max(8 * widest_loose, 0.01):.2%}")
        print(f"  -> {name}: bound {bound:.2%}  {note}  {'OK' if not verdicts else 'FAIL: ' + '; '.join(verdicts)}")
        if verdicts:
            rc = max(rc, 1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
