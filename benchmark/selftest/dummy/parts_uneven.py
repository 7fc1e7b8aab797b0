"""Corpus kind parts_uneven: a small table that is not the trip record, kept
here to prove that a corpus kind is a new file (selftest/rehearse.py step 3
and selftest/test_corpora.py copy it into a scratch tree's benchmark/corpora/).

Three int64-shaped columns of its own — a required key, an optional quantity,
a DELTA_BINARY_PACKED timestamp — and files of UNEQUAL row counts
(`groups_per_file`, in row groups, need not be whole). Its facts carry what
traffic kind stream_reader compares a delivery with: one wrapped int64 sum and
the null count per column. Host only: numpy + pyarrow.
"""

from __future__ import annotations

from pathlib import Path

COLUMNS = ("part_key", "quantity", "shipped_at")


def file_name(index: int) -> str:
    return f"parts-{index:03d}.parquet"


def build_table(spec: dict, seed: int, index: int):
    import numpy as np
    import pyarrow as pa

    n = int(spec["groups_per_file"][index] * spec["row_group_rows"])
    rng = np.random.default_rng([seed, index, 77])
    shipped = 1_700_000_000_000_000 + np.cumsum(rng.integers(0, 5_000_000, n))
    return pa.table({
        "part_key": pa.array(rng.integers(1, 200_000, n)),
        "quantity": pa.array(rng.integers(1, 51, n), mask=rng.random(n) < 0.1),
        "shipped_at": pa.array(shipped).cast(pa.timestamp("us")),
    })


def write_file(spec: dict, seed: int, index: int, directory: str, queries: list) -> dict:
    import numpy as np
    import pyarrow.parquet as pq

    table = build_table(spec, seed, index)
    pq.write_table(table, str(Path(directory) / file_name(index)), compression="snappy",
                   row_group_size=spec["row_group_rows"], use_dictionary=["part_key", "quantity"],
                   column_encoding={"shipped_at": "DELTA_BINARY_PACKED"})
    ints = {c: table[c].cast("int64").fill_null(0).to_numpy(zero_copy_only=False) for c in COLUMNS}
    return {"index": index, "rows": table.num_rows,
            "sums": {c: [int(v.sum(dtype=np.int64))] for c, v in ints.items()},
            "nulls": {c: table[c].null_count for c in COLUMNS}}


def rehearsal(spec: dict, rows: int) -> tuple:
    return dict(spec, row_group_rows=rows), rows / spec["row_group_rows"]
