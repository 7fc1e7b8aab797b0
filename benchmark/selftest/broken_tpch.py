"""One run of a Q6 query cell with the device query lane broken underneath:
`correct` has to come out false. broken.py's sibling for cells whose entry
point is POST /v1/query.

    python benchmark/selftest/broken_tpch.py --fault <name> -- --workload tpch-sf10.q6 --seed <n> --seconds <s> [run.py's arguments]

Everything after `--` goes to benchmark/run.py's main, in this process; what is
planted wraps the program's per-unit device partial
(parquet_tpu.serve.query_device.device_unit_partial, which the executor looks
up for every query). The units of the warm-up query (the first files x row
groups calls of the process) are left whole, so that what fails is the
comparison the timed queries get: each response against the reference's
answer, counted in `failed`.

Faults (a harness that cannot see them proves nothing by `correct: true`):
  product_in_float32        the unit's revenue is the float32 sum of float32
                            products, rounded to the scale-4 decimal: what a
                            lane without the integer kernel would be tempted
                            to do on a chip with no float64
  shipdate_upper_inclusive  `l_shipdate < date` answered as `<=`: a year and a
                            day
  discount_bound_as_float   the discount bounds go through `float`: 0.05 is
                            then 0.05000000000000000277, between two cents,
                            and `>=` it is `>= 0.06` (six of Q6's eight
                            DISCOUNT values lose a bound's rows this way;
                            0.04 and 0.07 survive)
  none                      nothing planted: the same route comes out correct

selftest/test_faults_tpch.py runs each at a rehearsal size on the CPU, with a
second sound control (another seed); PERF.md section 2 has the readings on the
chip at the cell's own size.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
from decimal import Decimal
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def product_in_float32(real, reader, row_group, query, filters, device):
    import jax.numpy as jnp

    (groups, types), scanned, matched = real(reader, row_group, query, filters, device)
    columns, mask = reader.read_row_group_device(row_group, ["l_extendedprice", "l_discount"], device=device,
                                                 filters=filters)
    product = columns[("l_extendedprice",)].values.astype(jnp.float32) * columns[("l_discount",)].values.astype(jnp.float32)
    revenue = Decimal(int(jnp.sum(jnp.where(mask, product, jnp.float32(0))))).scaleb(-4)
    vals = [revenue if a.expr is not None and matched else v for a, v in zip(query.aggregates, groups[()])]
    return ({(): vals}, types), scanned, matched


def shipdate_upper_inclusive(real, reader, row_group, query, filters, device):
    wider = [(c, "<=" if (c, op) == ("l_shipdate", "<") else op, v) for c, op, v in filters]
    return real(reader, row_group, query, wider, device)


def discount_bound_as_float(real, reader, row_group, query, filters, device):
    floats = [(c, op, float(v) if c == "l_discount" else v) for c, op, v in filters]
    return real(reader, row_group, query, floats, device)


FAULTS = {f.__name__: f for f in (product_in_float32, shipdate_upper_inclusive, discount_bound_as_float)}


def plant(fault, whole_units: int) -> None:
    from parquet_tpu.serve import query_device

    real = query_device.device_unit_partial
    lock, calls = threading.Lock(), 0

    def broken(reader, row_group, query, filters, device=None):
        nonlocal calls
        with lock:
            calls += 1
            whole = calls <= whole_units
        if whole:
            return real(reader, row_group, query, filters, device)
        return fault(real, reader, row_group, query, filters, device)

    query_device.device_unit_partial = broken


def warmup_units(workload: str) -> int:
    """Units of the cell's warm-up queries: files x row groups a file each."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == workload)
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    corpus = json.loads((BENCH.parent / config["file"]).read_text())["corpus"]
    cell = json.loads((BENCH / "workloads" / f"{workload}.json").read_text())
    return cell["warmup_requests"] * corpus["files"] * (corpus["rows_per_file"] // corpus["row_group_rows"])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=[*FAULTS, "none"])
    a, rest = ap.parse_known_args()
    rest = [r for r in rest if r != "--"]
    sys.path[:0] = [str(BENCH), str(BENCH.parent)]
    import run

    if a.fault != "none":
        plant(FAULTS[a.fault], warmup_units(rest[rest.index("--workload") + 1]))
    sys.argv = [str(BENCH / "run.py"), *rest]
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
