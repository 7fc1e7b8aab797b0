"""`correct` comes out false when the Q1 cell's device query lane is broken
(selftest/broken_tpch_q1.py), and true on the same route with nothing planted,
on two seeds.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_faults_tpch_q1.py -q     (CPU, about a minute)

At a rehearsal size: the comparisons are exact (limit 0), so what they catch
does not depend on the size. One process at a time: the runs share
benchmark/.cache (one corpus a seed). tests/test_benchmark_selftest.py is
tier-1's door to this file.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "tpch-sf10.q1"
CASES = [("none", 2147483999), ("avg_of_unit_avgs", 2147483999), ("group_by_index", 2147483999),
         ("shipdate_upper_exclusive", 2147483999), ("charge_in_float32", 2147483999), ("none", 3000000019)]


@pytest.mark.parametrize("fault,seed", CASES, ids=[f"{f}-{s}" for f, s in CASES])
def test_q1_correct_is_false_exactly_when_something_is_planted(fault, seed):
    p = subprocess.run(
        [sys.executable, "benchmark/selftest/broken_tpch_q1.py", "--fault", fault, "--", "--workload", CELL,
         "--seed", str(seed), "--seconds", "2", "--trace", "0", "--rehearse", "4096"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and line["compared"]["failed"] == {"value": line["failed"], "limit": 0}
    for zero in ("host_fallback", "query_expr_overflow_declined", "query_group_declined", "host_decoded_pages"):
        assert line["compared"][zero] == {"value": 0, "limit": 0}
    if fault == "none":
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
        assert line["metrics"]["rows_per_s"]["value"] > 0
    else:  # a wrong answer is missing from the rate
        assert line["correct"] is False and 0 < line["failed"] <= line["attempted"]
        if fault != "shipdate_upper_exclusive":  # a DELTA whose last day ships no row of a small table survives it
            assert line["failed"] == line["attempted"] and line["metrics"]["rows_per_s"]["value"] == 0
