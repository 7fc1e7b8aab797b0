"""One run of the Q1 cell with the device query lane broken underneath:
`correct` has to come out false. broken_tpch.py's sibling for the grouped
cell.

    python benchmark/selftest/broken_tpch_q1.py --fault <name> -- --workload tpch-sf10.q1 --seed <n> --seconds <s> [run.py's arguments]

Everything after `--` goes to benchmark/run.py's main, in this process; what is
planted wraps the program's per-unit device partial
(parquet_tpu.serve.query_device.device_unit_partial, which the executor looks
up for every query). The units of the warm-up query (the first files x row
groups calls of the process) are left whole, so that what fails is the
comparison the timed queries get: each response's groups against the
reference's, counted in `failed`.

Faults (a harness that cannot see them proves nothing by `correct: true`):
  avg_of_unit_avgs          a unit hands on its own average (at the sum's
                            scale) with a count of 1, so the merge averages
                            the units' averages: what a lane that rendered avg
                            per unit, or a router that merged rendered
                            documents, would answer
  group_by_index            a unit's slots are named from the FIRST unit's
                            dictionaries: a dictionary's order is first
                            appearance and differs from row group to row
                            group, so sums land under the wrong keys
  shipdate_upper_exclusive  `l_shipdate <= date` answered as `<`: a day's
                            rows missing
  charge_in_float32         a group's charge is the float32 sum of float32
                            products, rounded to the scale-6 decimal: what a
                            lane without the integer kernel would be tempted
                            to do on a chip with no float64
  none                      nothing planted: the same route comes out correct

selftest/test_faults_tpch_q1.py runs each at a rehearsal size on the CPU, with
a second sound control (another seed); PERF.md section 2 has the readings on
the chip at the cell's own size.
"""

from __future__ import annotations

import argparse
import sys
from decimal import Decimal
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH / "selftest"))

from broken_tpch import plant, warmup_units  # noqa: E402  (the wrapper that leaves the warm-up's units whole)

CHARGE = "l_extendedprice*(1-l_discount)*(1+l_tax)"
KEYS = ("l_returnflag", "l_linestatus")
FIRST: dict = {}  # group_by_index: the first planted unit's dictionaries


def avg_of_unit_avgs(real, reader, row_group, query, filters, device):
    (groups, types), scanned, matched = real(reader, row_group, query, filters, device)
    for vals in groups.values():
        for j, a in enumerate(query.aggregates):
            if a.op == "avg" and vals[j] is not None:
                total, count = vals[j]
                vals[j] = ((total / count).quantize(Decimal(1).scaleb(-types[j].scale)), 1)
    return (groups, types), scanned, matched


def _dictionaries(reader, row_group, device) -> list:
    columns = reader.read_row_group_device(row_group, list(KEYS), device=device)
    return [[v.decode() for v in columns[(k,)].dictionary.to_list()] for k in KEYS]


def group_by_index(real, reader, row_group, query, filters, device):
    (groups, types), scanned, matched = real(reader, row_group, query, filters, device)
    own = _dictionaries(reader, row_group, device)
    first = FIRST.setdefault("dictionaries", own)
    renamed = {}
    for key, vals in groups.items():
        at = [d.index(k) for d, k in zip(own, key)]
        renamed[tuple(f[i] if i < len(f) else k for f, i, k in zip(first, at, key))] = vals
    return (renamed, types), scanned, matched


def shipdate_upper_exclusive(real, reader, row_group, query, filters, device):
    narrower = [(c, "<" if (c, op) == ("l_shipdate", "<=") else op, v) for c, op, v in filters]
    return real(reader, row_group, query, narrower, device)


def charge_in_float32(real, reader, row_group, query, filters, device):
    import jax.numpy as jnp

    (groups, types), scanned, matched = real(reader, row_group, query, filters, device)
    columns, mask = reader.read_row_group_device(
        row_group, ["l_extendedprice", "l_discount", "l_tax", *KEYS], device=device, filters=filters)
    price, discount, tax = (columns[(c,)].values.astype(jnp.float32) for c in ("l_extendedprice", "l_discount", "l_tax"))
    product = price * (jnp.float32(100) - discount) * (jnp.float32(100) + tax)
    own = [[v.decode() for v in columns[(k,)].dictionary.to_list()] for k in KEYS]
    where = [j for j, a in enumerate(query.aggregates) if a.op == "sum" and a.column == CHARGE]
    for key, vals in groups.items():
        here = mask
        for k, d, v in zip(KEYS, own, key):
            here = here & (columns[(k,)].indices == d.index(v))
        charge = Decimal(int(jnp.sum(jnp.where(here, product, jnp.float32(0))))).scaleb(-6)
        for j in where:
            vals[j] = charge
    return (groups, types), scanned, matched


FAULTS = {f.__name__: f for f in (avg_of_unit_avgs, group_by_index, shipdate_upper_exclusive, charge_in_float32)}


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
