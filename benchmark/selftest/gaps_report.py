"""Where the device stood idle in the newest traced run, span by span: a
builder's and operator's tool, not a metric.

    python benchmark/selftest/gaps_report.py [--trace DIR_OR_PB] [--top 10] [--out FILE]

After a `run.py --trace 1` run on a chip (the trace is benchmark/.cache/trace):
the label table of lib/xsweep.py (every gap of the first device inside
bench:window under ONE label; seconds, share of the idle time, gaps, longest)
and the longest gaps, each with its label, its length, where it lies in the
window and every "pqt:" span open on any thread at its midpoint, outermost
first: thread, name, arguments (`parent` among them: the id of the span that
was open in the submitting context), and how long the span had been open.
With --out the same as one JSON object. Exits 1 where there is no trace, or
none with a window, device ops and annotations.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent / "lib")]

import xspans  # noqa: E402
import xsweep  # noqa: E402
from xplane import newest_xplane  # noqa: E402


def thread_labels(spans: list) -> dict:
    """{"name/id": a label to print}: the line's name where one thread has it
    (pqt-dispatch_0), name#k in order of first appearance where several do (a
    daemon's handler and pqt-serve threads all read "python")."""
    first: dict = {}
    for thread, _, _, start, _ in spans:
        first[thread] = min(start, first.get(thread, start))
    by_name: dict = {}
    for thread in sorted(first, key=first.get):
        by_name.setdefault(thread.rpartition("/")[0], []).append(thread)
    return {t: name if len(ts) == 1 else f"{name}#{k}" for name, ts in by_name.items() for k, t in enumerate(ts, 1)}


def report(xspace: bytes, top: int = 10) -> dict | None:
    t0 = time.perf_counter()
    trace = xspans.extract(xspace)
    t1 = time.perf_counter()
    if trace["window"] is None or not trace["spans"] or not trace["ops"]:
        return None
    gaps, winner = xsweep.label_gaps(trace)
    t2 = time.perf_counter()
    labels = (*xsweep.ORDER, xsweep.OUTSIDE)
    lo, hi = trace["window"]
    lengths = gaps[:, 1] - gaps[:, 0]
    idle = int(lengths.sum())
    table = []
    for k, label in enumerate(labels):
        mine = lengths[winner == k]
        if len(mine):
            table.append({"label": label, "seconds": int(mine.sum()) / 1e9, "share_of_idle": int(mine.sum()) / idle,
                          "gaps": len(mine), "longest_ms": int(mine.max()) / 1e6})
    table.sort(key=lambda row: -row["seconds"])
    spans = xsweep.thread_spans(xspace)
    short = thread_labels(spans)
    longest = []
    for g in lengths.argsort()[::-1][:top].tolist():
        s, e = (int(v) for v in gaps[g])
        mid = (s + e) // 2
        here = sorted((a, thread, name, args, b) for thread, name, args, a, b in spans if a <= mid < b)
        longest.append({
            "label": labels[winner[g]], "ms": (e - s) / 1e6, "at_s": (s - lo) / 1e9, "ends_the_window": e == hi,
            "open": [{"thread": short[thread], "name": name, "args": args, "open_for_ms": (mid - a) / 1e6,
                      "closes_in_ms": (b - mid) / 1e6} for a, thread, name, args, b in here],
        })
    return {
        "window_s": (hi - lo) / 1e9, "idle_s": idle / 1e9, "gaps": len(gaps), "device_events": len(trace["ops"]),
        "spans": len(trace["spans"]), "extract_s": t1 - t0, "sweep_s": t2 - t1, "labels": table, "longest": longest,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", type=Path, default=xspans.TRACE_DIR, help="a .xplane.pb, or a directory holding one")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--out", type=Path, help="write the report as JSON here too")
    args = ap.parse_args()
    pb = args.trace if args.trace.is_file() else (newest_xplane(args.trace) if args.trace.is_dir() else None)
    found = None if pb is None else report(pb.read_bytes(), args.top)
    if found is None:
        print(f"gaps_report: no trace with a window, device ops and pqt: spans under {args.trace}", file=sys.stderr)
        return 1
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps(found, indent=1) + "\n")
    print(f"gaps_report: {pb.name}: window {found['window_s']:.3f} s, idle {found['idle_s']:.3f} s "
          f"({100 * found['idle_s'] / found['window_s']:.1f} %) in {found['gaps']} gaps; {found['device_events']} "
          f"device events, {found['spans']} pqt: spans; extract {found['extract_s']:.1f} s, sweep {found['sweep_s']:.2f} s")
    for row in found["labels"]:
        print(f"  {row['label']:20s} {row['seconds']:9.4f} s  {100 * row['share_of_idle']:5.1f} %  "
              f"{row['gaps']:7d} gaps  longest {row['longest_ms']:9.3f} ms")
    for gap in found["longest"]:
        print(f"gap {gap['ms']:9.3f} ms at {gap['at_s']:8.3f} s -> {gap['label']}"
              + (" (the window's tail: after the device's last op)" if gap["ends_the_window"] else ""))
        for sp in gap["open"]:
            print(f"    {sp['thread']:16s} {sp['name']:20s} open {sp['open_for_ms']:9.3f} ms, "
                  f"closes in {sp['closes_in_ms']:9.3f} ms  {sp['args']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
