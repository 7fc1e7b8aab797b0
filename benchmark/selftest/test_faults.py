"""`correct` comes out false when the timed path is broken, and when a control
stands in the program's place (selftest/broken.py), in every cell of
BENCHMARK.json; and true on the same route with nothing planted.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_faults.py -q     (CPU, about a minute)

At a rehearsal size: the comparisons are exact (limit 0), so what they catch
does not depend on the size. One process at a time: the runs share
benchmark/.cache (one corpus: the same seed throughout, so it is written once).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELLS = {w["name"]: w for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}
# what each cell can suffer: its reader delivers optional columns (both) and DOUBLE (the wide one)
CASES = [(cell, fault) for cell in CELLS for fault in
         ("none", "altered_value", "group_left_out", "nulls_zero_filled",
          *(["doubles_bfloat16"] if cell == "tlc-wide.reader" else []))]


def test_every_cell_has_its_cases():
    assert set(CELLS) == {"tlc-stream.reader", "tlc-wide.reader"}, \
        "a new cell needs its faults and its control listed in CASES"


@pytest.mark.parametrize("cell,fault", CASES)
def test_correct_is_false_exactly_when_something_is_planted(cell, fault):
    p = subprocess.run(
        [sys.executable, "benchmark/selftest/broken.py", "--fault", fault, "--", "--workload", cell,
         "--seed", "2147483999", "--seconds", "1", "--trace", "0", "--rehearse", "4096"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and line["compared"]["failed"] == {"value": line["failed"], "limit": 0}
    assert p.stderr.strip().splitlines()[-len(line["compared"])].startswith("bench: compared failed = ")
    if fault == "none":
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    else:  # every delivery of the window is wrong, and none of them counts in the rate
        assert line["correct"] is False and line["failed"] == line["attempted"] > 0
        assert line["metrics"]["rows_per_s"]["value"] == 0
