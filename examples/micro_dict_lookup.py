"""Micro-run: a dictionary looked up by XLA's gather and by the dense lookup.

    chiprun -- python examples/micro_dict_lookup.py
    JAX_PLATFORMS=cpu python examples/micro_dict_lookup.py   # rehearsal: says so, times nothing worth reading

Per call of `--n` (2^20) random indices, int32 and int64 tables of 64 to
262,144 entries: milliseconds of `table[idx]` (what dict_gather_device ran
for every table through PR 39), of the dense formulation
(device_ops._dense_lookup, timed at EVERY size so that the table shows where
it stops winning) and of dict_gather_device as it stands, which picks between
the two by device_ops.dict_lookup_tier; each compiled program's
memory_analysis().temp_size_in_bytes; and whether each equals numpy bit for
bit. A time is the mean of `--reps` back-to-back calls after a warm-up, ended
by one block_until_ready: it includes the ~0.2 ms a jitted call costs to
dispatch from a one-chip machine's host. This table is where
DICT_DENSE_MAX comes from (PERF.md section 6): a size stays dense where the
dense formulation is at least 1.5 x faster than the gather.

Prints one JSON object and writes it to chiprun_out/micro_dict_lookup.json.
Needs the chip (device_ops.require_chip); where JAX_PLATFORMS names cpu
outright it runs as a rehearsal of the code path — 4,096 indices, tables up
to 4,096 entries, two repetitions, nothing written: XLA:CPU materialises what
the TPU fuses, so its dense timings say nothing about the chip's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

SIZES = (64, 128, 265, 512, 1024, 4096, 16384, 65536, 131072, 262144)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=7)
    a = ap.parse_args()

    import numpy as np

    import parquet_tpu.kernels.device_ops as dops  # x64 + compile cache first
    import jax
    import jax.numpy as jnp

    facts = dops.require_chip()
    rehearsal = facts["platform"] != "tpu"
    n, reps = (min(a.n, 4096), 2) if rehearsal else (a.n, a.reps)
    sizes = [size for size in SIZES if not rehearsal or size <= 4096]

    def timed(fn) -> float:
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        jax.block_until_ready([fn() for _ in range(reps)])
        return (time.perf_counter() - t0) * 1e3 / reps

    formulations = {
        "gather": jax.jit(lambda t, i: t[i]),
        "dense": jax.jit(dops._dense_lookup),
        "dict_gather_device": dops.dict_gather_device,
    }
    rng = np.random.default_rng(a.seed)
    rows = []
    for dt in (np.int32, np.int64):
        info = np.iinfo(dt)
        for size in sizes:
            table = rng.integers(info.min, info.max, size, dtype=dt, endpoint=True)
            idx = rng.integers(0, size, n).astype(np.int32)
            want = table[idx]
            t_dev, i_dev = jnp.asarray(table), jnp.asarray(idx)
            row = {"dtype": np.dtype(dt).name, "entries": size, "tier": dops.dict_lookup_tier(size, np.dtype(dt))}
            for name, fn in formulations.items():
                compiled = fn.lower(t_dev, i_dev).compile()
                memory = compiled.memory_analysis()
                row[name] = {
                    "ms": round(timed(lambda fn=fn: fn(t_dev, i_dev)), 4),
                    "temp_bytes": None if memory is None else int(memory.temp_size_in_bytes),
                    "equals_numpy": bool(np.array_equal(np.asarray(fn(t_dev, i_dev)), want)),
                }
            row["gather_over_dense"] = round(row["gather"]["ms"] / row["dense"]["ms"], 2)
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = {
        "device": facts, "rehearsal": rehearsal, "n": n, "reps": reps,
        "dense_band": {"min": dops.DICT_DENSE_MIN, "max": dops.DICT_DENSE_MAX},
        "all_equal_numpy": all(r[f]["equals_numpy"] for r in rows for f in formulations),
        "rows": rows,
    }
    if not rehearsal:
        target = ROOT / "chiprun_out" / "micro_dict_lookup.json"
        target.parent.mkdir(exist_ok=True)
        target.write_text(json.dumps(out, indent=1))
    print(json.dumps({k: out[k] for k in ("device", "rehearsal", "n", "dense_band", "all_equal_numpy")}))
    return 0 if out["all_equal_numpy"] else 1


if __name__ == "__main__":
    sys.exit(main())
