"""Where a one-chunk row group is prepared, and how far ahead.

A row group of one column is one chunk. With a host pool, staging it is ONE
pool task that prepares the chunk and enqueues its dispatch: the thread that
stages it (the consumer of lists="pack", two groups ahead) prepares nothing,
and a prepare error waits in the group's future for its own delivery. With no
pool (PQT_HOST_THREADS=1) the chunk is prepared inline, as before. The
counter pooled_single_chunk_stages says which way a stage went.
"""

import importlib.util
import threading
import time
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import parquet_tpu.kernels.device_ops as dops  # noqa: F401  x64 on, before any jnp array
from parquet_tpu import FileReader, PackedBatch
from parquet_tpu.core import reader as reader_mod
from parquet_tpu.core.chunk import ChunkError
from parquet_tpu.utils.trace import decode_trace

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "reference_packed", ROOT / "benchmark" / "lib" / "reference_packed.py")
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

BATCH, SEQ_LEN = 2, 16
COUNTER = "pooled_single_chunk_stages"


def write(path, groups: int, seed: int = 0, **options) -> str:
    """`groups` row groups of one LIST<int32> column; each holds exactly
    BATCH * SEQ_LEN tokens, so every group completes one whole batch."""
    rng = np.random.default_rng(seed)
    schema = pa.schema([("input_ids", pa.list_(pa.int32()))])
    with pq.ParquetWriter(str(path), schema, **options) as w:
        for _ in range(groups):
            docs = [rng.integers(0, 5000, k).tolist() for k in (10, 12, 10)]
            w.write_table(pa.table({"input_ids": pa.array(docs, type=schema.field(0).type)}, schema=schema))
    return str(path)


def packed(path: str, **reader_kw):
    """Yield the lists="pack" batches of `path` one at a time."""
    with FileReader(path, **reader_kw) as r:
        yield from r.iter_device_batches(BATCH, columns=["input_ids"], lists="pack", seq_len=SEQ_LEN,
                                         drop_remainder=False)


def lanes(trace, name: str) -> set:
    """The thread names on which the spans called `name` ran."""
    doc = trace.to_chrome_trace()
    threads = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    return {threads[e["tid"]] for e in doc["traceEvents"] if e["ph"] == "X" and e["name"] == name}


@pytest.fixture
def pool(monkeypatch):
    monkeypatch.setenv("PQT_HOST_THREADS", "4")
    assert reader_mod._host_pool() is not None


@pytest.fixture
def no_pool(monkeypatch):
    monkeypatch.setenv("PQT_HOST_THREADS", "1")
    assert reader_mod._host_pool() is None


def test_a_packed_read_prepares_every_group_on_the_pool(tmp_path, pool):
    groups = 7
    path = write(tmp_path / "t.parquet", groups)
    with decode_trace() as tr:
        got = list(packed(path))
    assert reader_mod._PACKED_LOOKAHEAD == 2
    assert tr.counters()[COUNTER] == groups
    prepared_on = lanes(tr, "chunk.prepare")
    assert prepared_on and all(name.startswith("pqt-host") for name in prepared_on), prepared_on
    assert lanes(tr, "io.read") <= prepared_on
    assert all(name.startswith("pqt-dispatch") for name in lanes(tr, "dispatch"))
    # bit for bit the batches of the path that stages nothing ahead
    ceiling = list(packed(path, max_memory=64 << 20))
    assert len(got) == len(ceiling) == groups
    for k, (a, b) in enumerate(zip(got, ceiling)):
        assert isinstance(a, PackedBatch)
        for name, x, y in zip(PackedBatch._fields, a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y)), f"batch {k}: {name} differs"
    want = reference.pack(pq.read_table(path)["input_ids"], SEQ_LEN)
    for name, w, x in zip(PackedBatch._fields, want, np.concatenate([np.asarray(b) for b in got], axis=1)):
        assert np.array_equal(x, w), name


def test_without_a_pool_the_chunk_is_prepared_inline(tmp_path, no_pool):
    path = write(tmp_path / "t.parquet", 4)
    with decode_trace() as tr:
        got = list(packed(path))
    assert len(got) == 4
    assert tr.counters().get(COUNTER, 0) == 0
    assert lanes(tr, "chunk.prepare") == {"MainThread"}


def _corrupt_group(path: str, k: int) -> None:
    """Flip the last byte of row group k's one chunk: its page's CRC fails."""
    cc = pq.ParquetFile(path).metadata.row_group(k).column(0)
    start = cc.dictionary_page_offset if cc.has_dictionary_page else cc.data_page_offset
    with open(path, "r+b") as f:
        f.seek(start + cc.total_compressed_size - 1)
        byte = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([byte[0] ^ 0xFF]))


@pytest.mark.parametrize("k", [2, 5])
def test_a_corrupt_group_raises_at_its_own_delivery(tmp_path, pool, k):
    path = write(tmp_path / "t.parquet", 7, write_page_checksum=True)
    clean = list(packed(path, validate_crc=True))
    _corrupt_group(path, k)
    got = []
    with pytest.raises(ChunkError, match="CRC mismatch"):
        for batch in packed(path, validate_crc=True):
            got.append(batch)
    # every group before k delivered its whole batch first (staged two ahead,
    # group k's prepare failed while group k - 2 was being delivered)
    assert len(got) == k
    for a, b in zip(got, clean):
        for x, y in zip(a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_a_consumer_that_stops_early_leaves_no_prepare_running(tmp_path, pool, monkeypatch):
    path = write(tmp_path / "t.parquet", 7)
    first = pq.ParquetFile(path).metadata.row_group(0).column(0)
    closed, late = threading.Event(), []
    fetch = FileReader._fetch_chunk

    def slow_after_the_first(self, offset, size):
        if offset > first.data_page_offset:  # a staged group's read outlasts the first delivery
            time.sleep(0.3)
            if closed.is_set():
                late.append(offset)
        return fetch(self, offset, size)

    monkeypatch.setattr(FileReader, "_fetch_chunk", slow_after_the_first)
    r = FileReader(path)
    batches = r.iter_device_batches(BATCH, columns=["input_ids"], lists="pack", seq_len=SEQ_LEN)
    next(batches)
    batches.close()
    closed.set()
    r.close()
    time.sleep(0.5)
    assert late == []


def test_a_one_column_device_read_is_staged_on_the_pool(tmp_path, pool):
    path = write(tmp_path / "t.parquet", 3)
    with decode_trace() as tr, FileReader(path) as r:
        one = r.read_row_group_device(1)
    assert tr.counters()[COUNTER] == 1
    assert lanes(tr, "chunk.prepare") and all(n.startswith("pqt-host") for n in lanes(tr, "chunk.prepare"))
    with FileReader(path) as r:
        assert one.keys() == r.read_row_group_device(1).keys()


def test_a_group_of_several_chunks_takes_the_pool_path_it_took(tmp_path, pool):
    table = pa.table({"a": np.arange(64, dtype=np.int64), "b": np.arange(64, dtype=np.int32)})
    path = str(tmp_path / "t.parquet")
    pq.write_table(table, path, row_group_size=16)
    with decode_trace() as tr, FileReader(path) as r:
        groups = r.read_row_groups_device()
        single = r.read_row_groups_device([2], columns=["a"])
    assert len(groups) == 4
    # several chunks in one stage never count; one group of one column does
    assert tr.counters().get(COUNTER, 0) == 1
    assert np.array_equal(np.asarray(single[0][("a",)].values), np.arange(32, 48))
