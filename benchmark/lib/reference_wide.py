"""The plain reference of the wide stream cell: pyarrow's read of the same
file, reduced per column to what a delivery is compared with.

The corpus facts (corpora/tlc_yellow_2023.py) hold sums of the 8 integer columns only, and
the corpus object is shared key for key with tlc-year-stream, so the other 11
columns' reference is taken here, from the files, while the benchmark sets up:
per file and per column the row count, the non-null count and one wrapped
uint64 sum of the bit patterns AS DELIVERED —

    int64, timestamp[us]  the int64 values, viewed uint64
    double                doubles "float32": numpy astype(float32), viewed
                          uint32; doubles "bits": the float64 viewed uint64
    string                each row's first byte (the flag's "Y" / "N")

`FileDigests` reads the files in worker threads (pyarrow and numpy release the
GIL); `patterns` gives one column's delivered bit patterns in full, for the
warm-up file's value-by-value comparison. Host only: never jax.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

MASK64 = 0xFFFFFFFFFFFFFFFF


def patterns(col, doubles: str):
    """The non-null values of a pyarrow column as the unsigned bit patterns
    the device path delivers; None for a string column (`first_bytes`)."""
    import numpy as np
    import pyarrow as pa

    col = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    if pa.types.is_string(col.type) or pa.types.is_binary(col.type):
        return None
    if pa.types.is_timestamp(col.type):
        col = col.cast("int64")
    v = col.drop_null().to_numpy(zero_copy_only=False)
    if v.dtype == np.float64:
        if doubles == "float32":
            with np.errstate(over="ignore"):  # overflow to inf is the stated result
                return v.astype(np.float32).view(np.uint32)
        return v.view(np.uint64)
    return v.view(np.uint64)


def first_bytes(col):
    """First byte of every non-null, non-empty string of a pyarrow column."""
    import numpy as np
    import pyarrow as pa

    col = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    col = col.drop_null().cast(pa.binary())
    offsets = np.frombuffer(col.buffers()[1], dtype=np.int32)[col.offset : col.offset + len(col) + 1]
    data = np.frombuffer(col.buffers()[2], dtype=np.uint8) if col.buffers()[2] is not None else np.zeros(0, np.uint8)
    starts = offsets[:-1][np.diff(offsets) > 0]
    return data[starts]


def column_digest(col, doubles: str) -> tuple:
    """(rows, non-null rows, wrapped uint64 sum of the delivered patterns)."""
    import numpy as np

    p = patterns(col, doubles)
    if p is None:
        p = first_bytes(col)
    return len(col), len(col) - col.null_count, int(p.sum(dtype=np.uint64)) & MASK64


def file_digest(path: str, columns: list, doubles: str) -> dict:
    import pyarrow.parquet as pq

    table = pq.read_table(path, columns=columns)
    return {c: column_digest(table[c], doubles) for c in columns}


class FileDigests:
    """Every file's digest, taken by worker threads while the caller warms
    the device path up. `result()` waits: {file index: {column: digest}}."""

    def __init__(self, paths: list, columns: list, doubles: str, workers: int = 4):
        self.pool = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="bench-ref")
        self.futures = [self.pool.submit(file_digest, p, columns, doubles) for p in paths]

    def result(self) -> dict:
        try:
            return {i: f.result() for i, f in enumerate(self.futures)}
        finally:
            self.close()

    def close(self) -> None:
        self.pool.shutdown(wait=True, cancel_futures=True)
