"""Run the benchmark's command once, in a process of its own, and parse its
last line: what measure.py, sweep.py and rehearse.py share. The caller never
imports jax, so the child holds the chip alone."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run_cell(cell: str, seed, seconds, trace: int, extra=(), root: Path = ROOT, env: dict | None = None):
    """(exit code, stdout lines, parsed last line or None, stderr)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cmd = [sys.executable, *bench["command"][1:], "--workload", cell, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), *extra]
    p = subprocess.run(cmd, cwd=root, capture_output=True, text=True, env=dict(os.environ, **(env or {})))
    lines = p.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1])
    except (IndexError, ValueError):
        last = None
    return p.returncode, lines, last, p.stderr
