"""TPU decoder backend: byte-identical parity vs the host path.

The write-side oracle of the north star (BASELINE.json): for every supported
shape, FileReader(backend="tpu_roundtrip") must produce byte-identical ChunkData to the
host path. On CPU the device ops run through the same XLA code path (jit on the
cpu backend); bench.py exercises the same code on the real chip.
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from parquet_tpu.core.arrays import ByteArrayData
from parquet_tpu.core.reader import FileReader


def assert_chunks_identical(a, b):
    assert a.num_values == b.num_values
    if isinstance(a.values, ByteArrayData) or isinstance(b.values, ByteArrayData):
        assert isinstance(a.values, ByteArrayData) and isinstance(b.values, ByteArrayData)
        np.testing.assert_array_equal(a.values.offsets, b.values.offsets)
        assert a.values.data == b.values.data
    else:
        av, bv = np.asarray(a.values), np.asarray(b.values)
        assert av.dtype == bv.dtype
        if av.dtype.kind == "f":
            np.testing.assert_array_equal(
                av.view(np.uint32 if av.itemsize == 4 else np.uint64),
                bv.view(np.uint32 if bv.itemsize == 4 else np.uint64),
            )
        else:
            np.testing.assert_array_equal(av, bv)
    for lv in ("def_levels", "rep_levels"):
        la, lb = getattr(a, lv), getattr(b, lv)
        assert (la is None) == (lb is None)
        if la is not None:
            np.testing.assert_array_equal(la, lb)


def both_backends(path):
    with FileReader(path, backend="host") as r:
        host = {i: r.read_row_group(i) for i in range(r.num_row_groups)}
    with FileReader(path, backend="tpu_roundtrip") as r:
        tpu = {i: r.read_row_group(i) for i in range(r.num_row_groups)}
    assert host.keys() == tpu.keys()
    for i in host:
        assert host[i].keys() == tpu[i].keys()
        for col_path in host[i]:
            assert_chunks_identical(host[i][col_path], tpu[i][col_path])
    return host


rng = np.random.default_rng(11)


class TestTpuParity:
    def test_plain_int64(self, tmp_path):
        # BASELINE config 1: PLAIN int64 flat, uncompressed, V1
        t = pa.table({"x": pa.array(rng.integers(-(2**62), 2**62, 20_000), pa.int64())})
        path = str(tmp_path / "c1.parquet")
        pq.write_table(t, path, use_dictionary=False, compression="none")
        both_backends(path)

    def test_dict_int32_snappy_v2(self, tmp_path):
        # BASELINE config 2 shape: hybrid int32, SNAPPY, V2 pages
        t = pa.table({"x": pa.array(rng.integers(0, 1000, 50_000).astype(np.int32))})
        path = str(tmp_path / "c2.parquet")
        pq.write_table(t, path, compression="snappy", data_page_version="2.0")
        both_backends(path)

    def test_dict_strings_100k(self, tmp_path):
        # BASELINE config 3 shape: dictionary strings
        keys = [f"key_{i:05d}" for i in range(5000)]
        vals = [keys[i % 5000] for i in range(60_000)]
        t = pa.table({"s": pa.array(vals)})
        path = str(tmp_path / "c3.parquet")
        pq.write_table(t, path, compression="snappy")
        both_backends(path)

    def test_delta_int64_gzip(self, tmp_path):
        # BASELINE config 4: delta-bp int64 timestamps, GZIP
        ts = (1_600_000_000_000_000 + np.cumsum(rng.integers(0, 1000, 30_000))).astype(np.int64)
        t = pa.table({"ts": pa.array(ts)})
        path = str(tmp_path / "c4.parquet")
        pq.write_table(
            t, path, compression="gzip", use_dictionary=False,
            column_encoding={"ts": "DELTA_BINARY_PACKED"},
        )
        both_backends(path)

    def test_nested_list_levels(self, tmp_path):
        # BASELINE config 5: nested LIST<int32> with R/D levels
        data = [list(range(i % 6)) if i % 7 else None for i in range(5000)]
        t = pa.table({"l": pa.array(data, pa.list_(pa.int32()))})
        path = str(tmp_path / "c5.parquet")
        pq.write_table(t, path, compression="snappy")
        both_backends(path)

    def test_nullable_dict_column(self, tmp_path):
        vals = [f"v{i % 50}" if i % 3 else None for i in range(10_000)]
        t = pa.table({"s": pa.array(vals)})
        path = str(tmp_path / "nd.parquet")
        pq.write_table(t, path)
        both_backends(path)

    def test_multi_page_dict(self, tmp_path):
        t = pa.table({"x": pa.array(rng.integers(0, 100, 40_000).astype(np.int64))})
        path = str(tmp_path / "mp.parquet")
        pq.write_table(t, path, data_page_size=2048)
        both_backends(path)

    def test_multi_row_group(self, tmp_path):
        t = pa.table({"x": pa.array(rng.integers(0, 30, 10_000).astype(np.int64)),
                      "y": pa.array(rng.standard_normal(10_000))})
        path = str(tmp_path / "mrg.parquet")
        pq.write_table(t, path, row_group_size=1111)
        both_backends(path)

    def test_plain_doubles_floats(self, tmp_path):
        t = pa.table({
            "f": pa.array(rng.standard_normal(8000).astype(np.float32)),
            "d": pa.array(np.concatenate([rng.standard_normal(7999), [np.nan]])),
        })
        path = str(tmp_path / "fd.parquet")
        pq.write_table(t, path, use_dictionary=False)
        both_backends(path)

    def test_byte_arrays_fall_back_to_host(self, tmp_path):
        # plain (non-dict) strings: host fallback path inside tpu backend
        t = pa.table({"s": pa.array([f"unique_{i}" for i in range(40_000)])})
        path = str(tmp_path / "ba.parquet")
        pq.write_table(t, path)  # 40k uniques > dict? pyarrow spills to plain
        both_backends(path)

    def test_empty_and_all_null(self, tmp_path):
        t = pa.table({"x": pa.array([None] * 100, pa.int64())})
        path = str(tmp_path / "an.parquet")
        pq.write_table(t, path)
        both_backends(path)

    def test_rows_match_through_assembly(self, tmp_path):
        t = pa.table({
            "id": pa.array(range(5000), pa.int64()),
            "cat": pa.array([f"c{i%7}" for i in range(5000)]),
        })
        path = str(tmp_path / "rows.parquet")
        pq.write_table(t, path, compression="snappy")
        with FileReader(path, backend="tpu_roundtrip") as r:
            rows = list(r.iter_rows())
        assert rows == t.to_pylist()


class TestDeviceOpBuckets:
    def test_bucket_reuse_avoids_recompiles(self, tmp_path):
        # different data sizes should land in a bounded set of compiled shapes
        from parquet_tpu.kernels.pipeline import _bucket

        assert _bucket(1000) == 1024
        assert _bucket(1024) == 1024
        assert _bucket(1025) == 2048
        assert _bucket(3) == 1024

    def test_delta_multi_page_segmented_cumsum(self, tmp_path):
        # many small pages force per-page segmentation inside one device batch
        v = rng.integers(-(2**40), 2**40, 50_000).astype(np.int64)
        t = pa.table({"x": pa.array(v)})
        path = str(tmp_path / "dseg.parquet")
        pq.write_table(
            t, path, use_dictionary=False, data_page_size=2048,
            column_encoding={"x": "DELTA_BINARY_PACKED"},
        )
        both_backends(path)

    def test_delta_int32_negatives(self, tmp_path):
        v = rng.integers(-(2**30), 2**30, 20_000).astype(np.int32)
        t = pa.table({"x": pa.array(v)})
        path = str(tmp_path / "d32.parquet")
        pq.write_table(
            t, path, use_dictionary=False,
            column_encoding={"x": "DELTA_BINARY_PACKED"},
        )
        both_backends(path)

    def test_delta_batch_split_at_bits_cap(self, tmp_path, monkeypatch):
        from parquet_tpu.kernels import pipeline
        from parquet_tpu.kernels.pipeline import TpuDecodeStats, plan_chunk_tpu

        v = np.cumsum(rng.integers(-500, 500, 30_000)).astype(np.int64)
        t = pa.table({"x": pa.array(v)})
        path = str(tmp_path / "dsplit.parquet")
        pq.write_table(
            t, path, use_dictionary=False, data_page_size=2048,
            column_encoding={"x": "DELTA_BINARY_PACKED"},
        )
        monkeypatch.setattr(pipeline, "_BATCH_BITS_CAP", 4096 * 8)
        stats = TpuDecodeStats()
        with FileReader(path) as r:
            cc = r.row_group(0).columns[0]
            col = r.schema.column(("x",))
            tpu_chunk = plan_chunk_tpu(r._f, cc, col, stats=stats).finalize()
        assert stats.device_batches > 1
        with FileReader(path, backend="host") as r:
            host_chunk = r.read_row_group(0)[("x",)]
        assert_chunks_identical(host_chunk, tpu_chunk)

    def test_hybrid_batch_split_at_bits_cap(self, tmp_path, monkeypatch):
        # Force the int32-safety batch cap down so one chunk needs several
        # device batches; output must stay byte-identical.
        from parquet_tpu.kernels import pipeline
        from parquet_tpu.kernels.pipeline import TpuDecodeStats, plan_chunk_tpu

        t = pa.table({"x": pa.array(rng.integers(0, 100, 40_000).astype(np.int64))})
        path = str(tmp_path / "split.parquet")
        pq.write_table(t, path, data_page_size=2048)
        monkeypatch.setattr(pipeline, "_BATCH_BITS_CAP", 4096 * 8)
        stats = TpuDecodeStats()
        with FileReader(path) as r:
            cc = r.row_group(0).columns[0]
            col = r.schema.column(("x",))
            plan = plan_chunk_tpu(r._f, cc, col, stats=stats)
            tpu_chunk = plan.finalize()
        assert stats.device_batches > 1
        with FileReader(path, backend="host") as r:
            host_chunk = r.read_row_group(0)[("x",)]
        assert_chunks_identical(host_chunk, tpu_chunk)


def device_vs_host(path):
    """Check read_row_group_device delivers the same values as the host path."""
    with FileReader(path, backend="host") as r:
        host = {i: r.read_row_group(i) for i in range(r.num_row_groups)}
    with FileReader(path) as r:
        dev = {i: r.read_row_group_device(i) for i in range(r.num_row_groups)}
    for i in host:
        assert host[i].keys() == dev[i].keys()
        for p in host[i]:
            h, d = host[i][p], dev[i][p]
            assert d.num_values == h.num_values
            if d.indices is not None:  # dictionary-encoded byte arrays
                idx = np.asarray(d.indices)
                got = d.dictionary.take(idx.astype(np.int64))
                assert isinstance(h.values, ByteArrayData)
                np.testing.assert_array_equal(got.offsets, h.values.offsets)
                assert got.data == h.values.data
                # device-side dictionary copy matches too
                np.testing.assert_array_equal(
                    np.asarray(d.dict_offsets), d.dictionary.offsets
                )
                assert bytes(np.asarray(d.dict_data)) == d.dictionary.data
            elif d.offsets is not None:  # byte arrays uploaded flat
                assert isinstance(h.values, ByteArrayData)
                np.testing.assert_array_equal(np.asarray(d.offsets), h.values.offsets)
                assert bytes(np.asarray(d.data)) == h.values.data
            else:
                got = np.asarray(d.values)
                want = np.asarray(h.values)
                assert got.dtype == want.dtype
                if got.dtype.kind == "f":
                    u = np.uint32 if got.itemsize == 4 else np.uint64
                    np.testing.assert_array_equal(got.view(u), want.view(u))
                else:
                    np.testing.assert_array_equal(got, want)
            for lv in ("def_levels", "rep_levels"):
                la, lb = getattr(h, lv), getattr(d, lv)
                assert (la is None) == (lb is None)
                if la is not None:
                    np.testing.assert_array_equal(la, lb)


class TestDecodeToDevice:
    def test_numeric_dict_column(self, tmp_path):
        t = pa.table({"x": pa.array(rng.integers(0, 500, 30_000).astype(np.int64))})
        path = str(tmp_path / "dd.parquet")
        pq.write_table(t, path, compression="snappy")
        device_vs_host(path)

    def test_string_dict_column_stays_encoded(self, tmp_path):
        vals = [f"cat_{i % 40}" for i in range(20_000)]
        t = pa.table({"s": pa.array(vals)})
        path = str(tmp_path / "ds.parquet")
        pq.write_table(t, path)
        with FileReader(path) as r:
            dc = r.read_row_group_device(0)[("s",)]
        assert dc.indices is not None  # delivered Arrow-dictionary style
        device_vs_host(path)

    def test_delta_and_plain_numeric(self, tmp_path):
        ts = (10**15 + np.cumsum(rng.integers(0, 900, 25_000))).astype(np.int64)
        t = pa.table({
            "ts": pa.array(ts),
            "v": pa.array(rng.standard_normal(25_000)),
            "f": pa.array(rng.standard_normal(25_000).astype(np.float32)),
        })
        path = str(tmp_path / "dp.parquet")
        pq.write_table(
            t, path, use_dictionary=False,
            column_encoding={"ts": "DELTA_BINARY_PACKED", "v": "PLAIN", "f": "PLAIN"},
        )
        device_vs_host(path)

    def test_plain_strings_upload_path(self, tmp_path):
        t = pa.table({"s": pa.array([f"unique_{i}" for i in range(15_000)])})
        path = str(tmp_path / "du.parquet")
        pq.write_table(t, path, use_dictionary=False)
        device_vs_host(path)

    def test_optional_and_nested(self, tmp_path):
        data = [list(range(i % 5)) if i % 6 else None for i in range(4000)]
        t = pa.table({
            "l": pa.array(data, pa.list_(pa.int64())),
            "o": pa.array([i if i % 4 else None for i in range(4000)], pa.int64()),
        })
        path = str(tmp_path / "don.parquet")
        pq.write_table(t, path, compression="snappy")
        device_vs_host(path)

    def test_all_null_dict_column(self, tmp_path):
        # regression: every page is kind 'empty' — must not crash on concat
        t = pa.table({"s": pa.array([None] * 5000, pa.string())})
        path = str(tmp_path / "allnull.parquet")
        pq.write_table(t, path)
        device_vs_host(path)

    def test_oversized_page_falls_back_to_host(self, tmp_path, monkeypatch):
        # regression: a single page above the int32 bit-offset cap must be
        # host-decoded, not silently wrapped into negative offsets
        from parquet_tpu.kernels import pipeline
        from parquet_tpu.kernels.pipeline import TpuDecodeStats, plan_chunk_tpu

        t = pa.table({
            "x": pa.array(rng.integers(0, 64, 20_000).astype(np.int64)),
            "ts": pa.array(np.cumsum(rng.integers(0, 9, 20_000)).astype(np.int64)),
        })
        path = str(tmp_path / "big.parquet")
        pq.write_table(
            t, path, data_page_size=1 << 30,
            use_dictionary=["x"], column_encoding={"ts": "DELTA_BINARY_PACKED"},
        )
        monkeypatch.setattr(pipeline, "_BATCH_BITS_CAP", 128)  # absurdly small
        with FileReader(path, backend="host") as r:
            host = r.read_row_group(0)
        with FileReader(path) as r:
            for j, cc in enumerate(r.row_group(0).columns):
                p = tuple(cc.meta_data.path_in_schema)
                stats = TpuDecodeStats()
                plan = plan_chunk_tpu(r._f, cc, r.schema.column(p), stats=stats)
                assert stats.host_fallback_pages > 0, p
                assert_chunks_identical(host[p], plan.finalize())

    def test_mixed_string_chunk_splits_on_device(self, tmp_path):
        """A byte-array chunk mixing dictionary-coded and PLAIN pages
        (pyarrow's mid-chunk fallback when the dict page overflows) keeps
        the dict pages' index batches on device; PLAIN pages upload raw and
        a ragged device gather merges both in output-index space. The
        finalize (roundtrip) oracle stays byte-identical."""
        import jax

        from parquet_tpu.kernels.pipeline import plan_chunk_tpu

        rng = np.random.default_rng(3)
        # mostly-unique strings overflow a tiny dictionary page quickly
        t = pa.table({"s": pa.array([f"v{int(x):08d}" for x in rng.integers(0, 1 << 30, 20_000)])})
        path = str(tmp_path / "mixed.parquet")
        pq.write_table(t, path, use_dictionary=["s"], dictionary_pagesize_limit=4096)
        with FileReader(path, backend="host") as r:
            host = r.read_row_group(0)
        with FileReader(path) as r:
            cc = r.row_group(0).columns[0]
            p = tuple(cc.meta_data.path_in_schema)
            plan = plan_chunk_tpu(r._f, cc, r.schema.column(p))
            kinds = {k for _, _, _, k, _ in plan.page_infos if k != "empty"}
            if len(kinds) <= 1:
                pytest.skip(
                    "pyarrow no longer mixes page encodings under "
                    f"dictionary_pagesize_limit (kinds={kinds}); regression "
                    "guard needs a new trigger"
                )
            assert plan.dev_hybrid  # dict pages device-bound, not demoted
            dc = plan.device_column()
            assert isinstance(dc.data, jax.Array) and isinstance(dc.offsets, jax.Array)
            hv = host[p].values
            off = np.asarray(dc.offsets)
            np.testing.assert_array_equal(off, hv.offsets)
            # data may carry padding past offsets[-1]; the extent must match
            np.testing.assert_array_equal(
                np.asarray(dc.data)[: off[-1]],
                np.frombuffer(hv.data, dtype=np.uint8),
            )
        with FileReader(path, backend="tpu_roundtrip") as r:
            assert_chunks_identical(host[p], r.read_row_group(0)[p])

    def test_mixed_numeric_chunk_merges_on_device(self, tmp_path):
        """A numeric chunk mixing dictionary pages with a mid-chunk PLAIN
        fallback keeps dict pages on the device (expansion + gather) and
        merges PLAIN pages in output-index order — no value round-trips to
        the host (the split replacing the old demote-everything policy)."""
        import jax

        from parquet_tpu.kernels.pipeline import TpuDecodeStats, plan_chunk_tpu

        rng = np.random.default_rng(11)
        # mostly-unique int64s overflow a tiny dictionary page mid-chunk
        t = pa.table({"x": pa.array(rng.integers(0, 1 << 60, 30_000).astype(np.int64))})
        path = str(tmp_path / "mixnum.parquet")
        pq.write_table(t, path, use_dictionary=["x"], dictionary_pagesize_limit=4096)
        with FileReader(path, backend="host") as r:
            host = r.read_row_group(0)
        with FileReader(path) as r:
            cc = r.row_group(0).columns[0]
            p = tuple(cc.meta_data.path_in_schema)
            stats = TpuDecodeStats()
            plan = plan_chunk_tpu(r._f, cc, r.schema.column(p), stats=stats)
            kinds = {k for _, _, _, k, _ in plan.page_infos if k != "empty"}
            if kinds != {"dict", "values"}:
                pytest.skip(
                    "pyarrow no longer mixes page encodings under "
                    f"dictionary_pagesize_limit (kinds={kinds})"
                )
            assert plan.dev_hybrid  # dict pages stayed on device
            assert stats.host_fallback_pages == 0
            dc = plan.device_column()
            assert isinstance(dc.values, jax.Array)
            np.testing.assert_array_equal(
                np.asarray(dc.values), np.asarray(host[p].values)
            )
        # the roundtrip oracle agrees too
        with FileReader(path, backend="tpu_roundtrip") as r:
            assert_chunks_identical(host[p], r.read_row_group(0)[p])

    def test_mixed_numeric_chunks_compile_a_bounded_set(self, tmp_path):
        """Two files whose mixed chunks differ in dictionary size, dictionary
        rows and PLAIN rows — each inside one bucket — run the same programs:
        the counts reach the merge as data (the segment table), so the second
        read lowers nothing. Before PR 36 the exact-length slice of the
        expansion and three eager pads compiled once a chunk. What is left is
        the delivery's exact length: the `[:n_rows]` cut after the merge is a
        program a row count, as after every device kernel, so a third file
        with another row count lowers that cut and nothing else."""
        from jax import monitoring

        from parquet_tpu.kernels.pipeline import plan_chunk_tpu

        def write(name, seed, repeated, rows):
            rng = np.random.default_rng(seed)
            x = rng.integers(0, 1 << 60, rows).astype(np.int64)
            # rows drawn from 100 values: the dictionary reaches its limit later
            x[:repeated] = x[rng.integers(0, 100, repeated)]
            path = str(tmp_path / name)
            pq.write_table(
                pa.table({"x": x}), path, use_dictionary=["x"],
                dictionary_pagesize_limit=40_000, data_page_size=8 << 10,
            )
            return path, x

        lowered: list = []

        def listen(name, seconds, **kw):
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                lowered.append(kw.get("fun_name"))

        counts = []
        monitoring.register_event_duration_secs_listener(listen)
        try:
            for name, seed, repeated, rows in (
                ("a.parquet", 1, 0, 30_000), ("b.parquet", 2, 2_000, 30_000), ("c.parquet", 3, 1_000, 29_000),
            ):
                path, x = write(name, seed, repeated, rows)
                with FileReader(path) as r:
                    cc = r.row_group(0).columns[0]
                    plan = plan_chunk_tpu(r._f, cc, r.schema.column(("x",)))
                    kinds = {k for _, _, _, k, _ in plan.page_infos if k != "empty"}
                    if kinds != {"dict", "values"}:
                        pytest.skip(f"pyarrow no longer mixes page encodings (kinds={kinds})")
                    counts.append((len(plan.dictionary), plan.padded_totals[1], plan.padded_totals[0]))
                    lowered.clear()
                    dc = r.read_row_group_device(0)[("x",)]
                    np.testing.assert_array_equal(np.asarray(dc.values), x)
                    assert dc.mixed
                    if name == "a.parquet":
                        assert "jit(merge_mixed_numeric_device)" in lowered, lowered
                    elif name == "b.parquet":
                        assert lowered == [], lowered
            # another row count in the same bucket: only the delivery's cut
            assert lowered == ["jit(dynamic_slice)"], lowered
        finally:
            monitoring.unregister_event_duration_listener(listen)
        for field in zip(*counts):  # dictionary entries, dictionary rows, PLAIN rows
            assert len(set(map(str, field))) == len(counts), counts

    def test_values_live_on_device(self, tmp_path):
        import jax

        t = pa.table({"x": pa.array(np.arange(1000, dtype=np.int64))})
        path = str(tmp_path / "dev.parquet")
        pq.write_table(t, path, use_dictionary=False)
        with FileReader(path) as r:
            dc = r.read_row_group_device(0)[("x",)]
        assert isinstance(dc.values, jax.Array)
        # usable directly by jitted compute without a host trip
        total = jax.jit(lambda a: a.sum())(dc.values)
        assert int(total) == int(np.arange(1000).sum())


class TestDeviceBatches:
    """iter_device_batches: the file as fixed-size HBM-resident batches."""

    def _file(self, tmp_path, n=10_000, rg=3_000):
        t = pa.table({
            "x": pa.array(np.arange(n, dtype=np.int64)),
            "v": pa.array([f"k{i%7}" for i in range(n)]),
        })
        path = str(tmp_path / "b.parquet")
        pq.write_table(t, path, row_group_size=rg, use_dictionary=["v"])
        return path

    def test_static_shapes_and_values(self, tmp_path):
        import jax

        path = self._file(tmp_path)
        with FileReader(path) as r:
            batches = list(r.iter_device_batches(1024))
        assert len(batches) == 10_000 // 1024
        seen = []
        for b in batches:
            assert isinstance(b[("x",)], jax.Array)
            assert b[("x",)].shape == (1024,) and b[("v",)].shape == (1024,)
            seen.append(np.asarray(b[("x",)]))
        flat = np.concatenate(seen)
        assert np.array_equal(flat, np.arange(len(flat)))  # order preserved

    def test_remainder_modes(self, tmp_path):
        path = self._file(tmp_path, n=2_500, rg=1_000)
        with FileReader(path) as r:
            dropped = list(r.iter_device_batches(1_000))
            assert [b[("x",)].shape[0] for b in dropped] == [1_000, 1_000]
        with FileReader(path) as r:
            kept = list(r.iter_device_batches(1_000, drop_remainder=False))
            assert [b[("x",)].shape[0] for b in kept] == [1_000, 1_000, 500]
            assert int(np.asarray(kept[-1][("x",)])[-1]) == 2_499

    def test_batch_spans_row_groups(self, tmp_path):
        path = self._file(tmp_path, n=5_000, rg=700)  # batches cross rg edges
        with FileReader(path) as r:
            batches = list(r.iter_device_batches(1_999, drop_remainder=False))
        flat = np.concatenate([np.asarray(b[("x",)]) for b in batches])
        assert np.array_equal(flat, np.arange(5_000))

    def test_raw_byte_array_rejected_and_projectable(self, tmp_path):
        t = pa.table({
            "x": pa.array(np.arange(1000, dtype=np.int64)),
            "s": pa.array([f"unique-{i}" for i in range(1000)]),  # no dict win
        })
        path = str(tmp_path / "raw.parquet")
        pq.write_table(t, path, use_dictionary=False)
        with FileReader(path) as r:
            with pytest.raises(ValueError):
                list(r.iter_device_batches(100))
        with FileReader(path) as r:
            batches = list(r.iter_device_batches(100, columns=["x"]))
        assert len(batches) == 10 and set(batches[0]) == {("x",)}

    def test_feeds_jitted_step(self, tmp_path):
        import jax

        path = self._file(tmp_path, n=4_096, rg=2_048)

        @jax.jit
        def step(batch):
            return batch[("x",)].sum()

        with FileReader(path) as r:
            total = sum(int(step(b)) for b in r.iter_device_batches(512))
        assert total == sum(range(4_096))

    def test_nullable_column_rejected(self, tmp_path):
        t = pa.table({
            "x": pa.array(np.arange(1000, dtype=np.int64)),
            "n": pa.array([None if i % 5 == 0 else i for i in range(1000)], pa.int64()),
        })
        path = str(tmp_path / "nulls.parquet")
        pq.write_table(t, path, use_dictionary=False)
        with FileReader(path) as r:
            with pytest.raises(ValueError, match="nulls"):
                list(r.iter_device_batches(100))
        # projecting the nullable column out makes it batchable again
        with FileReader(path) as r:
            assert len(list(r.iter_device_batches(100, columns=["x"]))) == 10

    def test_repeated_column_rejected(self, tmp_path):
        t = pa.table({
            "x": pa.array(np.arange(100, dtype=np.int64)),
            "l": pa.array([[i, i + 1] for i in range(100)], pa.list_(pa.int32())),
        })
        path = str(tmp_path / "lst.parquet")
        pq.write_table(t, path)
        with FileReader(path) as r:
            with pytest.raises(ValueError, match="repeated"):
                list(r.iter_device_batches(10))
        with FileReader(path) as r:
            assert len(list(r.iter_device_batches(10, columns=["x"]))) == 10

    def test_invalid_batch_size_raises_eagerly(self, tmp_path):
        path = self._file(tmp_path, n=100, rg=100)
        with FileReader(path) as r:
            with pytest.raises(ValueError):
                r.iter_device_batches(0)  # raises at call, not first next()


class TestWorkerPoolPath:
    """The multi-worker prepare branch never runs on a 1-core host by
    default; force it so the pool + dispatch-thread interplay is tested."""

    def test_parallel_prepare_parity(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PQT_HOST_THREADS", "4")
        import parquet_tpu.core.reader as reader_mod

        # fresh pool under the forced knob
        monkeypatch.setattr(reader_mod, "_pool", None)
        t = pa.table({
            "a": pa.array(rng.integers(0, 50, 30_000).astype(np.int64)),
            "b": pa.array([f"k{i%11}" for i in range(30_000)]),
            "c": pa.array(np.cumsum(rng.integers(0, 9, 30_000)).astype(np.int64)),
            "d": pa.array(rng.standard_normal(30_000)),
        })
        path = str(tmp_path / "pool.parquet")
        pq.write_table(
            t, path, row_group_size=7_000, compression="snappy",
            use_dictionary=["b"], column_encoding={"c": "DELTA_BINARY_PACKED"},
        )
        assert reader_mod._host_pool() is not None  # the branch under test
        both_backends(path)
        with FileReader(path) as r:
            groups = r.read_row_groups_device()
        assert sum(g[("a",)].num_values for g in groups) == 30_000
        monkeypatch.setattr(reader_mod, "_pool", None)  # don't leak the pool


def test_sharded_batches_over_mesh(tmp_path):
    """Batches lay out over a data-parallel mesh axis and feed a
    shard_map-style jitted step (the distributed input pipeline)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    t = pa.table({"x": pa.array(np.arange(8_192, dtype=np.int64))})
    path = str(tmp_path / "shard.parquet")
    pq.write_table(t, path, row_group_size=4_096, use_dictionary=False)
    mesh = Mesh(np.array(jax.devices("cpu")[:8]), ("data",))
    sharding = NamedSharding(mesh, P("data"))

    @jax.jit
    def step(b):
        return b[("x",)].sum()

    total = 0
    with FileReader(path) as r:
        for b in r.iter_device_batches(2_048, sharding=sharding):
            arr = b[("x",)]
            assert arr.sharding == sharding and arr.shape == (2_048,)
            total += int(step(b))
    assert total == sum(range(8_192))


def test_sharded_remainder_batch_keeps_sharding(tmp_path):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    t = pa.table({"x": pa.array(np.arange(2_560, dtype=np.int64))})
    path = str(tmp_path / "shard_rem.parquet")
    pq.write_table(t, path, use_dictionary=False)
    mesh = Mesh(np.array(jax.devices("cpu")[:8]), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    with FileReader(path) as r:
        batches = list(
            r.iter_device_batches(1_024, drop_remainder=False, sharding=sharding)
        )
    assert [b[("x",)].shape[0] for b in batches] == [1_024, 1_024, 512]
    assert all(b[("x",)].sharding == sharding for b in batches)  # incl. the tail


def test_sharded_indivisible_remainder_delivered_unsharded(tmp_path):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    t = pa.table({"x": pa.array(np.arange(2_500, dtype=np.int64))})
    path = str(tmp_path / "shard_odd.parquet")
    pq.write_table(t, path, use_dictionary=False)
    mesh = Mesh(np.array(jax.devices("cpu")[:8]), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    with FileReader(path) as r:
        batches = list(
            r.iter_device_batches(1_024, drop_remainder=False, sharding=sharding)
        )
    assert [b[("x",)].shape[0] for b in batches] == [1_024, 1_024, 452]
    assert batches[0][("x",)].sharding == sharding
    # 452 % 8 != 0: the tail arrives, just without the mesh layout
    assert int(np.asarray(batches[-1][("x",)])[-1]) == 2_499


def test_nullable_batches_masked_mean_over_mesh(tmp_path):
    """A nullable int64 column streams as MaskedColumn (device-expanded
    values + validity mask) through a jitted masked-mean step over the
    8-device mesh — the TPU-native null representation (real training data
    has nulls; an error is not an answer)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from parquet_tpu import MaskedColumn

    n = 8_192
    vals = [None if i % 5 == 0 else i for i in range(n)]
    t = pa.table({"x": pa.array(vals, pa.int64())})
    path = str(tmp_path / "nullable.parquet")
    pq.write_table(t, path, row_group_size=4_096, use_dictionary=False)
    mesh = Mesh(np.array(jax.devices("cpu")[:8]), ("data",))
    sharding = NamedSharding(mesh, P("data"))

    @jax.jit
    def masked_mean(b):
        col = b[("x",)]
        m = col.mask
        return jnp.where(m, col.values, 0).sum(), m.sum()

    total = cnt = 0
    with FileReader(path) as r:
        for b in r.iter_device_batches(2_048, sharding=sharding, nullable="mask"):
            col = b[("x",)]
            assert isinstance(col, MaskedColumn)
            assert col.values.sharding == sharding and col.mask.sharding == sharding
            s, c = masked_mean(b)
            total += int(s)
            cnt += int(c)
    expect = [v for v in vals if v is not None]
    assert total == sum(expect) and cnt == len(expect)
    # values row-aligned: null rows zero-filled, non-null rows in place
    with FileReader(path) as r:
        b = next(r.iter_device_batches(4_096, nullable="mask"))
        col = b[("x",)]
        got = np.asarray(col.values)
        mask = np.asarray(col.mask)
        ref = np.array([0 if v is None else v for v in vals[:4_096]])
        np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(mask, [v is not None for v in vals[:4_096]])


def test_nullable_batches_default_still_errors(tmp_path):
    t = pa.table({"x": pa.array([1, None, 3], pa.int64())})
    path = str(tmp_path / "nerr.parquet")
    pq.write_table(t, path)
    from parquet_tpu.meta import ParquetFileError

    with FileReader(path) as r:
        with pytest.raises(ParquetFileError):
            next(r.iter_device_batches(2, nullable="error"))


def test_device_batches_filter_pushdown(tmp_path):
    """filters= on iter_device_batches prunes row groups (stats + bloom)
    before any prepare/upload; surviving groups stream whole."""
    from parquet_tpu import FileWriter
    from parquet_tpu.schema.dsl import parse_schema

    schema = parse_schema("message m { required int64 id; }")
    path = str(tmp_path / "push.parquet")
    with FileWriter(
        path, schema, bloom_filters=True, use_dictionary=False
    ) as w:
        for base in (0, 100_000, 200_000):
            # even ids only: odd values inside [min, max] exist for the
            # bloom (and only the bloom) to exclude
            w.write_column(
                "id", np.arange(base, base + 8_192, 2, dtype=np.int64)
            )
            w.flush_row_group()
    with FileReader(path) as r:
        batches = list(
            r.iter_device_batches(4_096, filters=[("id", ">=", 200_000)])
        )
        assert len(batches) == 1
        np.testing.assert_array_equal(
            np.asarray(batches[0][("id",)]),
            np.arange(200_000, 208_192, 2, dtype=np.int64),
        )
        # bloom-only exclusion: an ODD value inside group 1's [min, max] —
        # statistics admit it, only the bloom can prove it absent
        assert r.prune_row_groups([("id", "==", 100_001)]) == []
        assert list(
            r.iter_device_batches(4_096, filters=[("id", "==", 100_001)])
        ) == []
        # and a present value keeps exactly its group
        assert len(list(
            r.iter_device_batches(4_096, filters=[("id", "==", 100_002)])
        )) == 1
        # no filters: everything streams
        assert len(list(r.iter_device_batches(4_096))) == 3


def test_ragged_device_batches(tmp_path):
    """LIST columns batch as RaggedColumn: values row-padded on device to
    [rows, max_list_len], lengths per row; null/empty lists -> length 0."""
    import jax
    import jax.numpy as jnp

    from parquet_tpu import RaggedColumn

    n = 5_000
    lists = [
        None if i % 13 == 0 else [int(x) for x in range(i % 6)] for i in range(n)
    ]
    t = pa.table({
        "tags": pa.array(lists, pa.list_(pa.int32())),
        "id": pa.array(range(n), pa.int64()),
    })
    path = str(tmp_path / "ragged.parquet")
    pq.write_table(t, path, row_group_size=2_000, use_dictionary=False)

    @jax.jit
    def masked_sum(b):
        col = b[("tags", "list", "element")]
        k = col.values.shape[1]
        m = jnp.arange(k)[None, :] < col.lengths[:, None]
        return jnp.where(m, col.values, 0).sum()

    total = 0
    seen = 0
    with FileReader(path) as r:
        for b in r.iter_device_batches(1_000, lists="pad", max_list_len=8):
            col = b[("tags", "list", "element")]
            assert isinstance(col, RaggedColumn)
            assert col.values.shape == (1_000, 8)
            total += int(masked_sum(b))
            # row alignment with the flat column
            ids = np.asarray(b[("id",)])
            lens = np.asarray(col.lengths)
            for rid in (0, 500, 999):
                row = lists[int(ids[rid])]
                assert lens[rid] == (len(row) if row else 0)
            seen += 1_000
    expect = sum(sum(x) for x in lists[:seen] if x)
    assert total == expect
    # exactness of padded values for a spot row
    with FileReader(path) as r:
        b = next(r.iter_device_batches(1_000, lists="pad", max_list_len=8))
        vals = np.asarray(b[("tags", "list", "element")].values)
        assert vals[5].tolist() == [0, 1, 2, 3, 4, 0, 0, 0]  # row 5: range(5)


def test_ragged_rejects_oversize_and_bad_args(tmp_path):
    t = pa.table({"l": pa.array([[1] * 20], pa.list_(pa.int32()))})
    path = str(tmp_path / "big.parquet")
    pq.write_table(t, path, use_dictionary=False)
    from parquet_tpu.meta import ParquetFileError

    with FileReader(path) as r:
        with pytest.raises(ParquetFileError, match="max_list_len"):
            next(r.iter_device_batches(1, lists="pad", max_list_len=8,
                                       drop_remainder=False))
        with pytest.raises(ValueError, match="max_list_len"):
            r.iter_device_batches(1, lists="pad")
        with pytest.raises(ValueError, match="lists"):
            r.iter_device_batches(1, lists="bogus")


def test_ragged_null_elements_and_nested_rejected(tmp_path):
    """Null elements INSIDE lists would silently left-shift positions; the
    ragged path refuses them. Nested list<list<>> fails eagerly at the call
    (review regressions)."""
    from parquet_tpu.meta import ParquetFileError

    t = pa.table({"l": pa.array([[1, None, 3]], pa.list_(pa.int32()))})
    p1 = str(tmp_path / "nullelem.parquet")
    pq.write_table(t, p1, use_dictionary=False)
    with FileReader(p1) as r:
        with pytest.raises(ParquetFileError, match="null elements"):
            next(r.iter_device_batches(1, lists="pad", max_list_len=4,
                                       drop_remainder=False))
    t2 = pa.table({
        "ll": pa.array([[[1, 2]]], pa.list_(pa.list_(pa.int32())))
    })
    p2 = str(tmp_path / "nested.parquet")
    pq.write_table(t2, p2, use_dictionary=False)
    with FileReader(p2) as r:
        with pytest.raises(ParquetFileError, match="single-level"):
            r.iter_device_batches(1, lists="pad", max_list_len=4)  # EAGER
