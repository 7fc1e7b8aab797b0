"""`correct` comes out false when the packed cell's timed path is broken, and
when a control stands in the program's place (selftest/broken_packed.py); and
true on the same route with nothing planted.

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest/test_faults_packed.py -q     (CPU, about a minute)

At a rehearsal size, and at a sequence length and batch that give the small
corpus whole batches, short batches and documents cut by sequences and by row
groups: the comparisons are exact (limit 0), so what they catch does not
depend on the size. One process at a time: the runs share benchmark/.cache
(one corpus: the same seed throughout, so it is written once).
tests/test_benchmark_selftest.py is tier-1's door to this file.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "tok-8k.packed"
FAULTS = ("none", "token_shifted", "sequence_left_out", "sequences_swapped", "segments_not_marked", "carry_dropped")


@pytest.mark.parametrize("fault", FAULTS)
def test_correct_is_false_exactly_when_something_is_planted(fault):
    p = subprocess.run(
        [sys.executable, "benchmark/selftest/broken_packed.py", "--fault", fault, "--", "--workload", CELL,
         "--seed", "2147483999", "--seconds", "1", "--trace", "0", "--rehearse", "1024",
         "--set", "seq_len=256", "--set", "batch_sequences=8"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and line["compared"]["failed"] == {"value": line["failed"], "limit": 0}
    assert line["compared"]["host_decoded_pages"] == {"value": 0, "limit": 0}
    if fault == "none":
        assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    else:  # every file of the window is wrong, and none of them counts in the rate
        assert line["correct"] is False and line["failed"] == line["attempted"] > 0
        assert line["metrics"]["rows_per_s"]["value"] == 0
