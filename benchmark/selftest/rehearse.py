"""CPU rehearsal of every cell, and proof that the harness grows by new files.

    JAX_PLATFORMS=cpu python benchmark/selftest/rehearse.py [--rows-per-group 16384]

1. Every cell of BENCHMARK.json, end to end at a tiny corpus, --trace 0 and
   --trace 1: exit 0, the last line parsed against the contract's keys, the
   metrics exactly the cell's end-to-end ones (trace 0) or among its per-layer
   ones (trace 1), `correct` true, and the device section saying "not
   measured" where a chip would have been read: a CPU number never appears
   under a device metric's name.
2. The real command on this CPU (no --rehearse): exit code other than 0 and no
   result line.
3. In a scratch copy (.bench_scratch/, git-ignored): a dummy corpus kind
   (selftest/dummy/: another table, files of unequal row counts), a dummy
   configuration that names it, a cell and a per-layer metric are added as
   NEW files plus BENCHMARK.json entries; the dummy cell reads the
   dummy table and reports the dummy metric; and no file that was there differs
   from the original. The cells that wait for their proof on the chip
   (selftest/waiting/) are added the same way and rehearsed too.
Not under tests/: it takes a minute and needs no pytest.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import shutil
import sys
from pathlib import Path

from runner import ROOT, run_cell

HERE = Path(__file__).resolve().parent
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(root: Path, cell: str, trace: int, extra: list):
    return run_cell(cell, 2**31 + 11, 2.0, trace, extra, root=root, env={"JAX_PLATFORMS": "cpu"})


def check_cell(root: Path, bench: dict, cell: str, extra: list) -> dict:
    out = {}
    for trace in (0, 1):
        rc, lines, line, err = run(root, cell, trace, extra)
        assert rc == 0 and line is not None, f"{cell} trace {trace}: exit {rc}\n{err[-2000:]}"
        assert KEYS <= set(line), f"{cell}: last line lacks {KEYS - set(line)}"
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, (cell, trace, lines[-6:])
        assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
        assert line["device"]["platform"] == "cpu" and line["device"]["memory_peak_bytes"] == "not measured"
        assert "rehearsal" in line
        mine = lambda ms: {m["name"]: m for m in ms if cell in m.get("workloads", [cell])}  # noqa: E731
        if trace == 0:
            want = mine(bench["end_to_end"])
            assert set(line["metrics"]) == set(want), (cell, set(line["metrics"]), set(want))
        else:
            want = mine(bench["per_layer"])
            assert line["metrics"] and set(line["metrics"]) <= set(want), (cell, set(line["metrics"]))
            assert line["device"]["busy_s"] == "not measured" and "breakdown" not in line
            for name in line["metrics"]:
                assert want[name]["source"] != "device_trace", f"{name}: a device metric from a CPU run"
        for name, v in line["metrics"].items():
            assert v["unit"] == want[name]["unit"] and isinstance(v["value"], (int, float)) and v["value"] > 0, (name, v)
        out[trace] = line
        print(f"rehearse: {cell} --trace {trace}: ok {json.dumps(line['metrics'])}", flush=True)
    return out


def digest_tree(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "benchmark").rglob("*"))
            if p.is_file() and ".cache" not in p.parts and "__pycache__" not in p.parts}


def merge_waiting(scratch: Path, grown: dict) -> list:
    """Add the cells that wait for their proof on the chip (selftest/waiting/:
    cell files plus the BENCHMARK.json entries that go with them) to the
    scratch copy, as new files and entries only, so that their traffic kinds
    and metrics are rehearsed too. Returns their names."""
    src = HERE / "waiting"
    if not (src / "entries.json").is_file():
        return []
    entries = json.loads((src / "entries.json").read_text())
    for f in (src / "cells").glob("*.json"):
        shutil.copy(f, scratch / "benchmark" / "workloads" / f.name)
    grown["configs"] += entries["configs"]
    grown["workloads"] += entries["workloads"]
    for kind in ("end_to_end", "per_layer"):
        have = {m["name"]: m for m in grown[kind]}
        for m in entries[kind]:
            if m["name"] in have:
                have[m["name"]].setdefault("workloads", []).extend(m["workloads"])
            else:
                grown[kind].append(m)
    return [w["name"] for w in entries["workloads"]]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows-per-group", type=int, default=16384)
    a = ap.parse_args()
    extra = ["--rehearse", str(a.rows_per_group)]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = json.loads((ROOT / "benchmark" / "workloads" / f"{w['name']}.json").read_text())
        assert (cell["name"], cell["config"], cell["traffic"]) == (w["name"], w["config"], w["traffic"]), \
            f"{w['name']}: the cell's file and BENCHMARK.json disagree"
        check_cell(ROOT, bench, w["name"], extra)

    rc, lines, line, _ = run(ROOT, bench["workloads"][0]["name"], 0, [])
    assert rc != 0 and line is None, "the real command must fail without a TPU"
    print(f"rehearse: without a TPU the command exits {rc} and prints no result: ok")

    scratch = ROOT / ".bench_scratch" / "ext"
    shutil.rmtree(scratch, ignore_errors=True)
    scratch.mkdir(parents=True)
    shutil.copytree(ROOT / "benchmark", scratch / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__", "records"))
    for name in ("parquet_tpu", "native"):
        (scratch / name).symlink_to(ROOT / name)
    before = digest_tree(scratch)
    first = bench["configs"][0]
    for name, where in (("parts_uneven.py", "corpora"), ("dummy-config.json", "configs"),
                        ("dummy.cell.json", "workloads")):
        shutil.copy(HERE / "dummy" / name, scratch / "benchmark" / where / name)
    (scratch / "benchmark/layer_metrics/dummy_calls_per_mrow.json").write_text(json.dumps(
        {"name": "dummy_calls_per_mrow", "reader": "stage_seconds", "args": {"stages": ["dispatch"], "per": "mrow"}}))
    grown = json.loads(json.dumps(bench))
    grown["configs"].append(dict(first, name="dummy-config", file="benchmark/configs/dummy-config.json"))
    grown["workloads"].append({"name": "dummy.cell", "config": "dummy-config", "traffic": "stream_reader",
                               "chips": 1, "why": "self-test"})
    for m in grown["end_to_end"]:
        if m["name"] == "rows_per_s":
            m["workloads"].append("dummy.cell")
    grown["per_layer"].append({"name": "dummy_calls_per_mrow", "unit": "ms/Mrow", "better": "lower",
                               "source": "program_span", "layer": "host-to-device transfer",
                               "moves": "rows_per_s", "workloads": ["dummy.cell"]})
    waiting = merge_waiting(scratch, grown)
    (scratch / "BENCHMARK.json").write_text(json.dumps(grown))
    lines = check_cell(scratch, grown, "dummy.cell", extra)
    assert "dummy_calls_per_mrow" in lines[1]["metrics"], "the new per-layer metric was not found by name"
    for name in waiting:
        check_cell(scratch, grown, name, extra)
    after = digest_tree(scratch)
    changed = [k for k in before if after.get(k) != before[k]]
    added = sorted(set(after) - set(before))
    assert not changed, f"files that were there changed: {changed}"
    print(f"rehearse: a corpus kind, a configuration, a cell and a per-layer metric added as new files only "
          f"({added}): ok")
    shutil.rmtree(scratch, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
