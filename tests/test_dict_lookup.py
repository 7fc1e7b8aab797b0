"""dict_gather_device against numpy, bit for bit, at every tier of the lookup.

The contract (device_ops.dict_gather_device): for every table length and
every index, np.asarray(table)[np.clip(idx, 0, len(table) - 1)] — whichever
formulation the static (length, dtype) picks: XLA's gather, or the dense
compare-and-contract over byte planes that holds no gather. A CPU run
executes the same programs the chip does (no backend test picks a tier), so
what is exact here is exact there; the chip's own proof at n = 2^20 is
chip_smoke.py's dict_lookup leg."""

import numpy as np
import pytest

import parquet_tpu.kernels.device_ops as dops  # x64 on, before any jnp array
import jax.numpy as jnp

# value dtype -> the unsigned pattern a dictionary of it travels as (floats
# upload as their uint views: pipeline._ChunkPlan.dispatch_device)
_DTYPES = {
    "int32": (np.int32, np.int32),
    "uint32": (np.uint32, np.uint32),
    "int64": (np.int64, np.int64),
    "uint64": (np.uint64, np.uint64),
    "float32": (np.float32, np.uint32),
    "float64": (np.float64, np.uint64),
}
_EDGES = sorted({edge + d for edge in (dops.DICT_DENSE_MIN, dops.DICT_DENSE_MAX) for d in (-1, 0, 1)})
_LENGTHS = sorted({1, 2, 64, 65, 127, 128, 265, 512, 513, 2526, 4096, 4097, *_EDGES})
_INDEX_SETS = ("all_hit", "only_first", "only_last", "random", "out_of_range", "zero_tail")
_NS = (4096, 65536)


def _table(name: str, length: int) -> np.ndarray:
    """A table of `length` entries as it uploads: every byte plane busy, the
    dtype's extremes, and for floats NaNs with payloads, +-0, +-inf."""
    dt, view = _DTYPES[name]
    rng = np.random.default_rng(length * 31 + len(name))
    bits = np.dtype(view).itemsize * 8
    pattern = rng.integers(0, 1 << bits, length, dtype=np.uint64 if bits == 64 else np.uint32)
    if np.dtype(dt).kind == "f":
        special = np.array([np.nan, -np.nan, 0.0, -0.0, np.inf, -np.inf, np.finfo(dt).tiny / 4], dtype=dt)
        payload = special.view(pattern.dtype).copy()
        payload[0] |= 0x1234  # a NaN that is not the canonical one
        payload[1] |= 0x7
        pattern[: min(len(payload), length)] = payload[:length]
    else:
        info = np.iinfo(dt)
        special = np.array([info.min, info.max, 0, 1], dtype=dt).view(pattern.dtype)
        pattern[: min(4, length)] = special[:length]
    return pattern.view(view)


def _indices(kind: str, length: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(n + length)
    if kind == "all_hit":  # every entry, in order, over and over
        return (np.arange(n) % length).astype(np.int32)
    if kind == "only_first":
        return np.zeros(n, np.int32)
    if kind == "only_last":
        return np.full(n, length - 1, np.int32)
    if kind == "random":
        return rng.integers(0, length, n).astype(np.int32)
    if kind == "out_of_range":  # past the table (XLA's gather clamps), and below it
        idx = rng.integers(0, length, n).astype(np.int64)
        idx[::3] = length + rng.integers(0, 1 << 20, len(idx[::3]))
        idx[1::7] = -1 - rng.integers(0, 1 << 20, len(idx[1::7]))
        idx[:4] = [length, np.iinfo(np.int32).max, -1, np.iinfo(np.int32).min]
        return idx.astype(np.int32)
    assert kind == "zero_tail"  # a padded delivery: real indices, then the pad's zeros
    idx = rng.integers(0, length, n).astype(np.int32)
    idx[n - n // 3 :] = 0
    return idx


@pytest.mark.parametrize("n", _NS)
@pytest.mark.parametrize("kind", _INDEX_SETS)
@pytest.mark.parametrize("length", _LENGTHS)
@pytest.mark.parametrize("name", list(_DTYPES))
def test_lookup_equals_numpy_bit_for_bit(name, length, kind, n):
    table = _table(name, length)
    idx = _indices(kind, length, n)
    got = np.asarray(dops.dict_gather_device(jnp.asarray(table), jnp.asarray(idx)))
    want = table[np.clip(idx, 0, length - 1)]
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("length", (64, 265, 4096))
def test_a_float32_table_keeps_its_bits(length):
    """serve/query_device hands a FLOAT dictionary over as float32 values,
    not as their pattern: NaN payloads, -0.0 and inf come back bit for bit."""
    table = _table("float32", length).view(np.float32)
    idx = _indices("random", length, 4096)
    got = np.asarray(dops.dict_gather_device(jnp.asarray(table), jnp.asarray(idx)))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), table[idx].view(np.uint32))


@pytest.mark.parametrize("n", (1, 127, 129, 40_000, 70_001))
def test_a_length_that_is_no_multiple_of_a_block(n):
    """A chunk's non-null count is data: the dense tier pads its last block
    and cuts it off again."""
    table = _table("int64", 265)
    idx = _indices("random", 265, n)
    got = np.asarray(dops.dict_gather_device(jnp.asarray(table), jnp.asarray(idx)))
    np.testing.assert_array_equal(got, table[idx])


@pytest.mark.parametrize("itemsize", (4, 8))
def test_tier_is_a_pure_function_of_length_and_dtype(itemsize):
    """dict_lookup_tier: what pipeline's counters and dict_gather_device
    itself decide from. One contiguous dense band a width, the gather below
    and above it, the same answer for every dtype of a width."""
    dtypes = [dt for dt, _ in _DTYPES.values() if np.dtype(dt).itemsize == itemsize and dt is not np.float64]
    top = dops.DICT_DENSE_MAX
    assert dops.DICT_DENSE_MIN == 65 and top >= 4096
    for length in (0, 1, 2, 64, 65, 128, 265, 512, 513, 4096, 1 << 16, top - 1, top, top + 1, 1 << 18):
        tiers = {dops.dict_lookup_tier(length, np.dtype(dt)) for dt in dtypes}
        tiers |= {dops.dict_lookup_tier(length, jnp.dtype(dt)) for dt in dtypes}
        assert tiers == {"dense" if 65 <= length <= top else "gather"}, (length, tiers)
    # a float64 TABLE cannot be split on a TPU (no f64 <-> u64 bitcast): the
    # device road ships DOUBLE dictionaries as uint64 patterns, which are dense;
    # narrower entries are nobody's dictionary
    assert dops.dict_lookup_tier(265, np.dtype(np.float64)) == "gather"
    assert dops.dict_lookup_tier(265, np.dtype(np.int16)) == "gather"
    assert dops.dict_lookup_tier(265, np.dtype(np.uint8)) == "gather"


def test_a_tlc_shaped_file_counts_its_chunks_by_tier(tmp_path):
    """dict_lookup_dense_chunks / dict_lookup_gather_chunks, chunk by chunk:
    a 265-entry int64 column (the TLC location codes) and a 12-bit DOUBLE
    dictionary delivered as float32 leave the gather, an 8-entry column
    stays on it, a byte-array dictionary is nobody's lookup; every value
    stays pyarrow's bit for bit."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu import FileReader
    from parquet_tpu.kernels.pipeline import _ChunkPlan
    from parquet_tpu.utils import metrics
    from parquet_tpu.utils.trace import decode_trace

    rows, group = 60_000, 20_000
    rng = np.random.default_rng(11)
    zone = rng.integers(1, 266, rows).astype(np.int64) * 1_000_003
    zone[:265] = zone[group : group + 265] = zone[2 * group : 2 * group + 265] = np.arange(1, 266) * 1_000_003
    tip = rng.integers(0, 3000, rows) / 100.0  # ~3,000 distinct amounts a group: a 12-bit index stream
    table = pa.table({
        "zone": pa.array(zone),
        "rate": pa.array(rng.integers(1, 9, rows).astype(np.int64)),
        "tip": pa.array(tip, mask=rng.random(rows) < 0.04),
        "flag": pa.array(rng.choice(["N", "Y"], rows)),
    })
    path = str(tmp_path / "tlc.parquet")
    pq.write_table(table, path, row_group_size=group, use_dictionary=True, compression="snappy")

    seen = []
    real = _ChunkPlan._lookup

    def spy(plan, idx):
        seen.append((plan.column.path_str, plan.dict_dev.shape[0], plan.dict_dev.dtype.name))
        return real(plan, idx)

    names = ['events_total{event="dict_lookup_%s_chunks"}' % t for t in ("dense", "gather")]
    before = [metrics.snapshot().get(n, 0) for n in names]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(_ChunkPlan, "_lookup", spy)
        with decode_trace() as tr:
            with FileReader(path) as r:
                groups = r.read_row_groups_device(doubles="float32")
    counters = tr.counters()
    assert "host_decoded_pages" not in counters
    assert counters["dict_lookup_dense_chunks"] == 6 and counters["dict_lookup_gather_chunks"] == 3
    assert [metrics.snapshot().get(n, 0) - b for n, b in zip(names, before)] == [6, 3]
    # chunk by chunk: the table dict_gather_device saw, and the tier it implies
    by_column = {}
    for column, length, dtype in seen:
        by_column.setdefault(column, set()).add((dops.dict_lookup_tier(length, np.dtype(dtype)), dtype))
    assert by_column == {
        "zone": {("dense", "int64")}, "rate": {("gather", "int64")}, "tip": {("dense", "uint32")},
    }
    assert len(seen) == 9 and {n for c, n, _ in seen if c == "zone"} == {265}
    assert {n for c, n, _ in seen if c == "tip"} == {4096}  # padded to its 12-bit index width
    for name in ("zone", "rate"):
        got = np.concatenate([np.asarray(g[(name,)].values) for g in groups])
        np.testing.assert_array_equal(got, table.column(name).to_numpy())
    got = np.concatenate([np.asarray(g[("tip",)].values) for g in groups])
    want = table.column("tip").drop_null().to_numpy().astype(np.float32)
    assert got.dtype == np.float32 and got.tobytes() == want.tobytes()
