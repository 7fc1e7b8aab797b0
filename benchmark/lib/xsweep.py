"""Every idle gap of the device under the name of ONE host span, in one pass.

This module takes lib/xspans.py's lists (`xspans.load()`: `window`, `ops`,
`spans`) and costs a sort: a reduction that tests every interval of every
label for every gap takes minutes on a trace of the packed cell's size.

The rule. Every gap of the first device inside "bench:window" (the complement
of the union of its ops there) goes to exactly ONE label: the first of ORDER
whose "pqt:" annotation is open ON ANY THREAD at the gap's midpoint
(start <= midpoint < end), else OUTSIDE. So the labels sum to the idle time.
ORDER, by rank:

  1. producers, furthest down the reader's pipeline first: dispatch.upload,
     dispatch.launch, chunk.prepare, io.read (it nests in chunk.prepare; alone
     where a planner reads), deliver.pack, deliver;
  2. a query unit's own steps: query.sync, query.aggregate, query.mask,
     serve.open_reader, query.decode, serve.aggregate;
  3. a request's phases: serve.merge, serve.respond, serve.plan, serve.admit,
     serve.parse;
  4. the waits: plan.wait_dispatch, plan.wait_prepare. Reached only when no
     producer is open anywhere: the hop between two threads;
  5. OUTSIDE: no such span open. The caller's time: the benchmark's verify,
     the client between two answers, the daemon's _finish.

selftest/test_xsweep.py pins its numbers on a hand-built trace. Cost: each
label's intervals are merged once and every gap's midpoint is looked up in
them by binary search, all gaps of a label at a time:
O((gaps + spans) log spans) a label. `gaps()`
does it once a run for every metric of the family (readers/xplane_sweep.py).
"""

from __future__ import annotations

import functools
from pathlib import Path

import numpy as np

from xplane import clip, union
from xspans import PREFIX, TRACE_DIR, _events, _metadata, _text, fields, load

PRODUCERS = ("dispatch.upload", "dispatch.launch", "chunk.prepare", "io.read", "deliver.pack", "deliver")
QUERY_UNIT = ("query.sync", "query.aggregate", "query.mask", "serve.open_reader", "query.decode", "serve.aggregate")
REQUEST = ("serve.merge", "serve.respond", "serve.plan", "serve.admit", "serve.parse")
WAITS = ("plan.wait_dispatch", "plan.wait_prepare")
ORDER = PRODUCERS + QUERY_UNIT + REQUEST + WAITS
OUTSIDE = "outside"


def device_gaps(trace: dict) -> np.ndarray:
    """[[start_ns, end_ns]] of the first device's gaps inside the window, in
    time order."""
    lo, hi = trace["window"]
    busy = union(clip([(s, e) for _, s, e in trace["ops"]], lo, hi))
    out, edge = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    return np.asarray(out, dtype=np.int64).reshape(-1, 2)


def label_gaps(trace: dict) -> tuple:
    """(gaps, winner): the device's gaps and, for each, the index in ORDER
    of the label it goes to (len(ORDER) = no label open)."""
    gaps = device_gaps(trace)
    mids = (gaps[:, 0] + gaps[:, 1]) // 2
    by_label: dict = {}
    for name, s, e in trace["spans"]:
        by_label.setdefault(name, []).append((s, e))
    winner = np.full(len(gaps), len(ORDER), dtype=np.int64)
    for rank in range(len(ORDER) - 1, -1, -1):  # last to first: the first of the order wins
        merged = np.asarray(union(by_label.get(ORDER[rank], [])), dtype=np.int64).reshape(-1, 2)
        if not len(merged):
            continue
        at = np.searchsorted(merged[:, 0], mids, side="right") - 1
        winner[(at >= 0) & (mids < merged[np.maximum(at, 0), 1])] = rank
    return gaps, winner


def gap_seconds(trace: dict) -> dict | None:
    """{label: idle seconds} over ORDER's labels and OUTSIDE; they sum to the
    window's idle time. A label that never opens reads 0. None where the
    trace holds no window, no device op or no pqt: span at all."""
    if trace["window"] is None or not trace["spans"] or not trace["ops"]:
        return None
    gaps, winner = label_gaps(trace)
    sums = np.bincount(winner, weights=gaps[:, 1] - gaps[:, 0], minlength=len(ORDER) + 1)
    return {label: int(ns) / 1e9 for label, ns in zip((*ORDER, OUTSIDE), sums)}


@functools.lru_cache(maxsize=1)
def gaps(directory: Path = TRACE_DIR) -> dict | None:
    """gap_seconds of the newest trace under `directory`, once a run."""
    trace = load(directory)
    return None if trace is None else gap_seconds(trace)


def _arguments(event, stat_names: dict) -> str:
    """An XEvent's own stats (field 4: XStat metadata_id 1, uint64 3, int64 4,
    str 5, ref 7) as "key=value,...": where the runtime lifted an
    annotation's arguments out of its name."""
    found = []
    for f, v in fields(event):
        if f != 4:
            continue
        stat = dict(fields(v))
        value = (_text(stat[5]) if 5 in stat else stat_names.get(stat[7], "") if 7 in stat
                 else stat.get(3, stat.get(4, "")))
        found.append(f"{stat_names.get(stat.get(1, 0), '?')}={value}")
    return ",".join(found)


def thread_spans(xspace: bytes) -> list:
    """[(thread, name, arguments, start_ns, end_ns)] of every "pqt:" event of
    the host planes: what xspans.extract drops, for the report
    (selftest/gaps_report.py). A thread is its line's "name/id": the pools the
    program names are pqt-host_*, pqt-dispatch_0; a daemon's handler and
    pqt-serve threads all read "python". The arguments ("group=0,column=a,parent=7", ""
    where the annotation has none) are the text between '#'s in the
    annotation's name or, where the runtime lifted them, the event's stats."""
    out = []
    for no, plane in fields(memoryview(xspace)):
        if no != 1:
            continue
        plane = list(fields(plane))
        if next((_text(v) for f, v in plane if f == 2), "").startswith("/device:"):
            continue
        meta, stat_names = _metadata(plane)
        mine = {k: n[len(PREFIX):] for k, (n, _) in meta.items() if n.startswith(PREFIX)}
        for line in (list(fields(v)) for f, v in plane if f == 3):
            ident = next((v for f, v in line if f == 1), 0)
            thread = f"{next((_text(v) for f, v in line if f == 2), '')}/{ident}"
            raw = [v for f, v in line if f == 4]
            for event, (k, s, e) in zip(raw, _events(line)):
                if k in mine:
                    name, _, args = mine[k].partition("#")
                    out.append((thread, name, args.rstrip("#") or _arguments(event, stat_names), s, e))
    return out
