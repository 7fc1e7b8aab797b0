"""lib/xsweep.py and the readers over it, on traces whose numbers are known.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_xsweep.py -q     (CPU, half a minute)

1. On fixture.xspans.txt (selftest/xspans_check.py's hand-built trace) the
   sweep gives the fixture header's literal numbers.
2. On a hand-built daemon trace every label of the order wins the gap it
   should while every later one is open too, and the labels sum to window -
   busy.
3. On a synthetic trace of the packed cell's size (400,000 ops, 15,000 spans)
   the sweep returns. No time is asserted: a reduction that tests every span
   for every gap does not finish inside the suite's limit, and that is the
   check.
4. selftest/gaps_report.py on the same fixture: the label table, and the
   spans open at the longest gaps' midpoints.
5. A CPU rehearsal with --trace 1 of each cell reports the program-span
   metrics of the family and none of the eight gap_* (obs.xplane is None).
6. The harness's files: every layer_metrics/*.json names a reader that
   exists, every per-layer metric of BENCHMARK.json has its file, and no
   module under benchmark/ brings back the quadratic reduction (xspans'
   gap_seconds, retired for lib/xsweep.py).
tests/test_benchmark_selftest.py is tier-1's door to this file.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE.parent / "lib"), str(HERE.parent / "readers"), str(HERE)]

import xspans  # noqa: E402
import xsweep  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
QUERY_CELLS = ["tpch-sf10.q6", "tpch-sf10.q1"]  # the cells that go through the daemon
GAP_METRICS = ["gap_upload_ms_per_mrow", "gap_launch_ms_per_mrow", "gap_prepare_ms_per_mrow",
               "gap_deliver_ms_per_mrow", "gap_consumer_wait_ms_per_mrow", "gap_query_unit_ms_per_mrow",
               "gap_request_ms_per_mrow", "gap_outside_ms_per_mrow"]


def fixture_xspace() -> bytes:
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace((HERE / "fixture.xspans.txt").read_text())


def test_restricted_to_the_old_order_the_sweep_is_the_old_reduction():
    trace = xspans.extract(fixture_xspace())
    full = xsweep.gap_seconds(trace)
    assert set(full) == {*xsweep.ORDER, xsweep.OUTSIDE}
    assert {k: round(v * 1e9) for k, v in full.items() if v} == {  # the fixture header's numbers
        "dispatch.upload": 2500, "chunk.prepare": 1000, "deliver": 3000, "outside": 3500}
    # a program without annotations: nothing to read, nothing raised
    assert xsweep.gap_seconds(dict(trace, spans=[])) is None


def daemon_trace() -> tuple:
    """One gap a label, in the order's order: [1000 k, 1000 k + 400 + k), the
    device busy up to the next. At the gap's midpoint label k is open on one
    thread and every LATER label of the order on others, so k has to win; the
    last gap has nothing open."""
    labels = (*xsweep.ORDER, xsweep.OUTSIDE)
    ops, spans, want = [], [], {}
    for k, label in enumerate(labels):
        lo, hi = 1000 * k, 1000 * k + 400 + k
        ops.append(("", hi, 1000 * (k + 1)))
        want[label] = hi - lo
        mid = (lo + hi) // 2
        for later in xsweep.ORDER[k:]:
            spans.append((later, mid, mid + 1))  # open at the midpoint itself, closed one ns later
        spans.append(("dispatch.upload", hi, hi + 50))  # a span over busy time wins nothing
        spans.append(("dispatch", lo, hi))  # not a label
    return {"window": (0, 1000 * len(labels)), "ops": ops, "spans": spans}, want


def test_every_rank_wins_its_gap_and_the_labels_sum_to_the_idle_time():
    trace, want = daemon_trace()
    got = xsweep.gap_seconds(trace)
    assert {k: round(v * 1e9) for k, v in got.items()} == want
    lo, hi = trace["window"]
    busy = sum(e - s for _, s, e in trace["ops"])
    assert round(sum(got.values()) * 1e9) == (hi - lo) - busy
    gaps, winner = xsweep.label_gaps(trace)
    assert winner.tolist() == list(range(len(xsweep.ORDER) + 1))
    assert gaps[3].tolist() == [3000, 3403]
    # a span that ends AT the midpoint is not open there; one that starts at it is
    edge = {"window": (0, 100), "ops": [("", 40, 100)], "spans": [("deliver", 0, 20), ("chunk.prepare", 20, 30)]}
    assert xsweep.gap_seconds(edge)["chunk.prepare"] == 40 / 1e9


def test_the_readers_share_one_sweep_and_read_nothing_on_a_rehearsal(monkeypatch):
    import xplane_sweep

    trace, want = daemon_trace()
    calls = []
    monkeypatch.setattr(xsweep, "load", lambda directory=None: calls.append(1) or trace)
    xsweep.gaps.cache_clear()
    obs = SimpleNamespace(xplane={"busy_s": 1.0}, rows=2_000_000, window_s=1.0, counters={})
    by_file = {}
    for name in GAP_METRICS:
        spec = json.loads((HERE.parent / "layer_metrics" / f"{name}.json").read_text())
        assert spec["reader"] == "xplane_sweep"
        by_file[name] = xplane_sweep.read(obs, **spec["args"])
        assert xplane_sweep.read(SimpleNamespace(xplane=None), **spec["args"]) is None
    xsweep.gaps.cache_clear()
    assert calls == [1], "one sweep a run"
    # the eight metrics cover every label once: they sum to the idle time
    per_mrow = 1e3 / 2.0 / 1e9
    assert sum(by_file.values()) == pytest.approx(sum(want.values()) * per_mrow, rel=1e-12)
    assert by_file["gap_consumer_wait_ms_per_mrow"] == pytest.approx(
        (want["plan.wait_dispatch"] + want["plan.wait_prepare"]) * per_mrow)
    assert by_file["gap_request_ms_per_mrow"] == pytest.approx(sum(want[k] for k in xsweep.REQUEST) * per_mrow)
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name in GAP_METRICS:
        cells = CELLS if "query_unit" not in name and "request" not in name else QUERY_CELLS
        assert listed[name]["workloads"] == cells and listed[name]["source"] == "device_trace"


def test_a_trace_of_the_packed_cells_size_is_swept():
    rng = np.random.default_rng(37)
    n_ops, n_spans, hi = 400_000, 15_000, 30_000_000_000
    starts = np.sort(rng.integers(0, hi, n_ops))
    ops = [("", int(s), int(s) + int(d)) for s, d in zip(starts, rng.integers(1_000, 120_000, n_ops))]
    names = rng.integers(0, len(xsweep.ORDER), n_spans)
    at = rng.integers(0, hi, n_spans)
    spans = [(xsweep.ORDER[k], int(s), int(s) + int(d))
             for k, s, d in zip(names, at, rng.integers(10_000, 20_000_000, n_spans))]
    trace = {"window": (0, hi), "ops": ops, "spans": spans}
    got = xsweep.gap_seconds(trace)
    gaps = xsweep.device_gaps(trace)
    assert len(gaps) > 100_000
    assert round(sum(got.values()) * 1e9) == int((gaps[:, 1] - gaps[:, 0]).sum())
    assert got[xsweep.OUTSIDE] > 0 and got["dispatch.upload"] > 0


def test_thread_spans_keep_the_thread_and_the_arguments():
    spans = xsweep.thread_spans(fixture_xspace())
    assert ("pqt-dispatch_0/3", "dispatch.launch", "group=0,column=a", 8600, 9900) in spans
    assert ("python/1", "deliver", "", 13500, 16000) in spans
    assert sorted({t for t, *_ in spans}) == ["pqt-dispatch_0/3", "pqt-host_0/2", "python/1"]
    assert len(spans) == len(xspans.extract(fixture_xspace())["spans"])


def test_the_report_names_every_span_open_at_a_gaps_midpoint():
    import gaps_report

    found = gaps_report.report(fixture_xspace(), top=2)
    assert [(row["label"], round(row["seconds"] * 1e9), row["gaps"]) for row in found["labels"]] == [
        ("outside", 3500, 1), ("deliver", 3000, 1), ("dispatch.upload", 2500, 1), ("chunk.prepare", 1000, 1)]
    assert round(found["idle_s"] * 1e9) == 10000 and found["gaps"] == 4
    tail, deliver = found["longest"]
    assert (tail["label"], tail["open"], tail["ends_the_window"]) == ("outside", [], False)  # the last op runs past the window
    assert deliver["label"] == "deliver" and [(sp["thread"], sp["name"]) for sp in deliver["open"]] == [("python", "deliver")]
    assert gaps_report.report(b"") is None
    # threads of one name are told apart by a number, in order of first appearance
    spans = [("python/9", "a", "", 5, 6), ("python/4", "b", "", 1, 2), ("pqt-dispatch_0/7", "c", "", 3, 4)]
    assert gaps_report.thread_labels(spans) == {"python/4": "python#1", "python/9": "python#2", "pqt-dispatch_0/7": "pqt-dispatch_0"}


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_reports_the_span_metrics_and_no_gap_metric(cell):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearse", "4096"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and "rehearsal" in line
    metrics = line["metrics"]
    assert metrics["consumer_wait_ms_per_mrow"]["value"] > 0
    assert not set(GAP_METRICS) & set(metrics), "a device-trace metric on a CPU rehearsal"
    if cell in QUERY_CELLS:
        assert metrics["query_decode_ms_per_mrow"]["value"] > 0
        assert metrics["request_host_ms_per_query"] == {"value": metrics["request_host_ms_per_query"]["value"],
                                                         "unit": "ms/query"}
        assert metrics["request_host_ms_per_query"]["value"] > 0
    else:
        assert "query_decode_ms_per_mrow" not in metrics and "request_host_ms_per_query" not in metrics


def test_every_metric_file_names_a_reader_that_exists():
    readers = {p.stem for p in (HERE.parent / "readers").glob("*.py")}
    files = {p.stem: json.loads(p.read_text()) for p in (HERE.parent / "layer_metrics").glob("*.json")}
    assert {name: spec["reader"] for name, spec in files.items() if spec["reader"] not in readers} == {}
    assert all(spec["name"] == name for name, spec in files.items())
    assert {m["name"] for m in BENCH["per_layer"]} <= set(files), "a per-layer metric without its file"


def test_no_module_brings_back_the_quadratic_gap_reduction():
    assert not hasattr(xspans, "gap_seconds")
    found = {}
    for path in (ROOT / "benchmark").rglob("*.py"):
        text = path.read_text()
        hits = re.findall(r"xspans\.gap_seconds|from xspans import[^\n]*\bgap_seconds\b", text)
        if path != HERE.parent / "lib" / "xsweep.py":  # the sweep's own gap_seconds
            hits += re.findall(r"^def gap_seconds\b", text, flags=re.M)
        if hits:
            found[str(path.relative_to(ROOT))] = hits
    assert found == {}
