"""One run of one cell with the timed path broken underneath, or with a control
in the program's place: `correct` has to come out false.

    python benchmark/selftest/broken.py --fault <name> -- --workload <cell> --seed <n> --seconds <s> [run.py's arguments]

Everything after `--` goes to benchmark/run.py's main, in this process; what is
planted wraps the cell's entry point, `FileReader.read_row_groups_device`,
where a delivery is produced. The first delivery of the process (the warm-up
file, compared value by value during set-up) is left whole, so that what
fails is the comparison the timed deliveries get: the per-delivery digests
against the reference, counted in `failed`.

Faults (a harness that cannot see them proves nothing by `correct: true`):
  altered_value     one value of one column of every delivery is off by one
  group_left_out    every delivery lacks its last row group
Controls (a guarantee of the configuration broken, the step a later PR could
be tempted to take):
  nulls_zero_filled every optional column arrives dense, nulls as 0 (what the
                    loader's nullable="zero" gives): null positions are lost
  doubles_bfloat16  every DOUBLE arrives as the bfloat16 rounding of its
                    float32: the nearest precision below the stated form
  none              nothing planted: the same route comes out correct

selftest/test_faults.py runs each at a rehearsal size on the CPU; PERF.md
section 2 has the controls' readings on the chip at the cells' own size.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def altered_value(groups):
    dc = next(dc for dc in groups[0].values() if dc.values is not None)
    dc.values = dc.values.at[0].add(1)
    return groups


def group_left_out(groups):
    return groups[:-1]


def nulls_zero_filled(groups):
    import jax.numpy as jnp
    import numpy as np

    for g in groups:
        for dc in g.values():
            present = np.asarray(dc.def_levels) == 1
            if dc.values is not None and not present.all():
                dc.values = jnp.zeros(dc.num_values, dc.values.dtype).at[np.flatnonzero(present)].set(dc.values)
                dc.def_levels = np.ones_like(np.asarray(dc.def_levels))
    return groups


def doubles_bfloat16(groups):
    import jax.numpy as jnp

    for g in groups:
        for dc in g.values():
            if dc.double_form == "float32":
                dc.values = dc.values.astype(jnp.bfloat16).astype(jnp.float32)
    return groups


FAULTS = {f.__name__: f for f in (altered_value, group_left_out, nulls_zero_filled, doubles_bfloat16)}


def plant(fault) -> None:
    from parquet_tpu import FileReader

    whole = FileReader.read_row_groups_device
    deliveries = 0

    def broken(self, *args, **kwargs):
        nonlocal deliveries
        groups = whole(self, *args, **kwargs)
        deliveries += 1
        return groups if deliveries == 1 else fault(groups)

    FileReader.read_row_groups_device = broken


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=[*FAULTS, "none"])
    a, rest = ap.parse_known_args()
    sys.path[:0] = [str(BENCH), str(BENCH.parent)]
    import run

    if a.fault != "none":
        plant(FAULTS[a.fault])
    sys.argv = [str(BENCH / "run.py"), *(r for r in rest if r != "--")]
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
