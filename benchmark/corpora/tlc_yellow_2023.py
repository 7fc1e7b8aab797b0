"""Corpus kind tlc_yellow_2023: a seeded NYC-TLC-shaped year, one Parquet file
per month.

Host only (numpy + pyarrow; never jax), so the worker processes of
lib/corpus.py and the load generator can load it. Everything a run compares
against is computed here, by numpy/pyarrow on the table in memory while it is
written: per-column wrapped int64 sums per batch of `sum_rows` rows, and each
file's share of the reference answers to the cell's queries (lib/reference.py).

Every seed gives the same SHAPES in another order: each row group holds exactly
`nulls_per_group` nulls in the optional columns and every value of every small
domain, so the device path sees the same array sizes whatever the seed, and a
seed changes values and positions only.
"""

from __future__ import annotations

from pathlib import Path

MONEY = (
    "fare_amount", "extra", "mta_tax", "tip_amount", "tolls_amount",
    "improvement_surcharge", "total_amount", "congestion_surcharge", "airport_fee",
)
COLUMNS = (
    "VendorID", "tpep_pickup_datetime", "tpep_dropoff_datetime", "passenger_count",
    "trip_distance", "RatecodeID", "store_and_fwd_flag", "PULocationID",
    "DOLocationID", "payment_type", *MONEY,
)
ZONES = 265
JAN_1_2023_US = 1_672_531_200_000_000
MONTH_US = 2_629_800_000_000  # a twelfth of a year


def zone_weights():
    """The corpus's own zone skew, shared with the tile generator: harmonic,
    floor high enough that every zone appears in every row group."""
    import numpy as np

    w = 1.0 / (np.arange(ZONES) + 10.0)
    return w / w.sum()


def build_table(spec: dict, seed: int, index: int):
    """Month `index` of the year as a pyarrow table, from (seed, index)."""
    import numpy as np
    import pyarrow as pa

    n, group = spec["rows_per_file"], spec["row_group_rows"]
    rng = np.random.default_rng([seed, index])
    null = np.zeros(n, dtype=bool)
    for start in range(0, n, group):
        size = min(group, n - start)
        k = spec["nulls_per_group"] * size // group
        null[start + rng.choice(size, k, replace=False)] = True

    def pick(values, probs):
        return np.asarray(values, dtype=np.int64)[rng.choice(len(values), n, p=probs)]

    zones = np.arange(1, ZONES + 1)
    gaps = rng.poisson(MONTH_US / 1e6 / n, n)  # whole seconds, as TLC's are
    pickup = JAN_1_2023_US + index * MONTH_US + (np.cumsum(gaps) + rng.integers(-60, 61, n)) * 1_000_000
    dropoff = pickup + (60 + rng.gamma(2.0, 420.0, n).astype(np.int64)) * 1_000_000

    def money(shape, scale, step=0.01):
        return np.round(rng.gamma(shape, scale, n) / step) * step

    fare = money(2.0, 9.0)
    tip = np.where(rng.random(n) < 0.7, np.round(fare * 0.2, 2), 0.0)
    tolls = np.where(rng.random(n) < 0.08, 6.55, 0.0)
    extra = pick([0, 1, 2, 5], [0.4, 0.3, 0.2, 0.1]) * 0.5
    cong = np.where(rng.random(n) < 0.9, 2.5, 0.0)
    airport = np.where(rng.random(n) < 0.08, 1.75, 0.0)
    cols = {
        "VendorID": pa.array(pick([1, 2, 6], [0.27, 0.7295, 0.0005])),
        "tpep_pickup_datetime": pa.array(pickup).cast(pa.timestamp("us")),
        "tpep_dropoff_datetime": pa.array(dropoff).cast(pa.timestamp("us")),
        "passenger_count": pa.array(
            pick(range(7), [0.015, 0.73, 0.15, 0.04, 0.025, 0.02, 0.02]), mask=null),
        "trip_distance": pa.array(money(1.5, 2.3)),
        "RatecodeID": pa.array(
            pick([1, 2, 3, 4, 5, 6, 99], [0.93, 0.04, 0.005, 0.005, 0.01, 0.005, 0.005]), mask=null),
        "store_and_fwd_flag": pa.array(np.where(rng.random(n) < 0.006, "Y", "N")),
        "PULocationID": pa.array(zones[rng.choice(ZONES, n, p=zone_weights())]),
        "DOLocationID": pa.array(zones[rng.choice(ZONES, n, p=zone_weights()[::-1])]),
        "payment_type": pa.array(pick(range(6), [0.03, 0.78, 0.16, 0.01, 0.015, 0.005])),
        "fare_amount": pa.array(fare), "extra": pa.array(extra),
        "mta_tax": pa.array(np.full(n, 0.5)), "tip_amount": pa.array(tip),
        "tolls_amount": pa.array(tolls), "improvement_surcharge": pa.array(np.full(n, 1.0)),
        "total_amount": pa.array(np.round(fare + extra + tip + tolls + cong + airport + 1.5, 2)),
        "congestion_surcharge": pa.array(cong), "airport_fee": pa.array(airport),
    }
    return pa.table({c: cols[c] for c in COLUMNS})


def file_name(index: int) -> str:
    return f"yellow_tripdata_2023-{index + 1:02d}.parquet"


def write_file(spec: dict, seed: int, index: int, directory: str, queries: list) -> dict:
    """Write one month and return what later comparisons need of it."""
    import numpy as np
    import pyarrow.parquet as pq

    from reference import partial_answers  # benchmark/lib is on sys.path

    table = build_table(spec, seed, index)
    delta = list(spec["delta_columns"])
    pq.write_table(
        table, str(Path(directory) / file_name(index)),
        compression=spec["compression"], row_group_size=spec["row_group_rows"],
        use_dictionary=[c for c in COLUMNS if c not in delta],
        column_encoding={c: "DELTA_BINARY_PACKED" for c in delta},
    )
    sums = {}
    for c in spec["sum_columns"]:
        col = table[c].combine_chunks()
        if col.type != "int64":
            col = col.cast("int64")
        v = col.fill_null(0).to_numpy(zero_copy_only=False)
        sums[c] = v.reshape(-1, spec["sum_rows"]).sum(axis=1, dtype=np.int64).tolist()
    return {
        "index": index, "rows": table.num_rows, "sums": sums,
        "nulls": {c: table[c].null_count for c in spec["sum_columns"]},
        "partials": partial_answers(table, file_name(index), queries),
    }


def rehearsal(spec: dict, rows: int) -> tuple:
    """The year at `rows` rows a group, for a CPU rehearsal: (spec, scale),
    where `scale` is what a cell's own `*_rows` shrink by beside it."""
    scale = rows / spec["row_group_rows"]
    return dict(spec, row_group_rows=rows, rows_per_file=3 * rows,
                nulls_per_group=int(spec["nulls_per_group"] * scale),
                sum_rows=max(1, int(spec["sum_rows"] * scale))), scale
