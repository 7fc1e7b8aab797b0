"""parquet_tpu.io tests: sources, the retry ladder, range planning,
coalescing, block/footer caches, and the reader/dataset wiring.

The retry matrix (transient EIO / short reads / latency / permanent
failure) runs a fast subset in tier-1 and the extended seed sweep under
`slow` (`make fuzz` includes it). Acceptance pins from the issue:

  * a projected 2-of-8-column read through the planner fetches < 40% of
    the file's bytes (io_bytes_read_total vs file size);
  * re-opening a file against a warm footer + block cache performs ZERO
    source reads.
"""

import io as _stdio
import os
from pathlib import Path

import numpy as np
import pytest

from parquet_tpu.core.reader import FileReader
from parquet_tpu.io import (
    BlockCache,
    FooterCache,
    LocalFileSource,
    MemorySource,
    Readahead,
    RetryingSource,
    SourceError,
    SourceFile,
    coalesce,
    fetch_ranges,
    open_source,
    plan_ranges,
)
from parquet_tpu.io.source import FileObjectSource
from parquet_tpu.testing.flaky import FlakySource
from parquet_tpu.utils import metrics

NOSLEEP = lambda s: None  # retry ladders sweep in microseconds under test


@pytest.fixture(scope="module")
def eight_col(tmp_path_factory):
    """An 8-column incompressible file: projection leaves real byte gaps."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = tmp_path_factory.mktemp("io") / "eight.parquet"
    rng = np.random.default_rng(3)
    t = pa.table(
        {
            f"c{k}": pa.array(rng.integers(0, 1 << 62, 30_000).astype(np.int64))
            for k in range(8)
        }
    )
    pq.write_table(t, path, compression="none", use_dictionary=False,
                   row_group_size=15_000)
    return str(path)


@pytest.fixture
def blob(tmp_path):
    data = np.random.default_rng(7).integers(0, 256, 1 << 16).astype(np.uint8)
    p = tmp_path / "blob.bin"
    p.write_bytes(data.tobytes())
    return p, data.tobytes()


class TestSources:
    def test_local_file_source(self, blob):
        p, data = blob
        with LocalFileSource(p) as src:
            assert src.size() == len(data)
            assert src.read_at(0, 16) == data[:16]
            assert src.read_at(100, 1000) == data[100:1100]
            assert src.read_at(5, 0) == b""
            got = src.read_ranges([(0, 4), (64, 8), (len(data) - 4, 4)])
            assert [bytes(b) for b in got] == [data[:4], data[64:72], data[-4:]]
            with pytest.raises(SourceError):
                src.read_at(len(data) - 2, 4)  # past EOF
            with pytest.raises(ValueError):
                src.read_at(-1, 4)
        with pytest.raises(SourceError):
            src.read_at(0, 4)  # closed
        src.close()  # idempotent

    def test_local_source_id_pins_generation(self, tmp_path):
        p = tmp_path / "g.bin"
        p.write_bytes(b"generation-one")
        id1 = LocalFileSource(p).source_id
        p.write_bytes(b"generation-two!!")  # different size
        id2 = LocalFileSource(p).source_id
        assert id1 != id2

    def test_memory_source(self):
        src = MemorySource(b"hello world")
        assert src.size() == 11
        assert src.read_at(6, 5) == b"world"
        with pytest.raises(SourceError):
            src.read_at(8, 10)

    def test_file_object_source_without_fileno(self, blob):
        _p, data = blob

        class NoFd:  # a seekable file-like with no real fd
            def __init__(self, b):
                self._b = _stdio.BytesIO(b)

            def read(self, n=-1):
                return self._b.read(n)

            def seek(self, *a):
                return self._b.seek(*a)

            def tell(self):
                return self._b.tell()

        src = FileObjectSource(NoFd(data))
        assert src.size() == len(data)
        assert src.read_at(10, 20) == data[10:30]

    def test_source_file_adapter(self, blob):
        p, data = blob
        f = SourceFile(LocalFileSource(p))
        assert f.read(4) == data[:4]
        assert f.tell() == 4
        assert f.seek(0, 2) == len(data)
        assert f.read(10) == b""  # EOF clamps, file semantics
        f.seek(-4, 2)
        assert f.read() == data[-4:]
        f.seek(2, 0)
        f.seek(2, 1)
        assert f.tell() == 4

    def test_open_source_shapes(self, blob):
        p, data = blob
        src, owns = open_source(str(p))
        assert isinstance(src, LocalFileSource) and owns
        src.close()
        src, owns = open_source(Path(p))
        assert isinstance(src, LocalFileSource) and owns
        src.close()
        src, owns = open_source(data)
        assert isinstance(src, MemorySource) and owns
        src, owns = open_source(_stdio.BytesIO(data))
        assert isinstance(src, MemorySource) and owns
        ms = MemorySource(data)
        src, owns = open_source(ms)
        assert src is ms and not owns
        with open(p, "rb") as fobj:
            src, owns = open_source(fobj)
            assert isinstance(src, FileObjectSource) and not owns
        with pytest.raises(TypeError):
            open_source(42)


class TestRetryLadder:
    def test_transient_eio_recovers_byte_identical(self, blob):
        p, data = blob
        inner = FlakySource(LocalFileSource(p), seed=2, error_rate=0.5)
        src = RetryingSource(inner, attempts=16, sleep=NOSLEEP, seed=1)
        s0 = metrics.snapshot()
        got = b"".join(
            bytes(b) for b in src.read_ranges([(0, 1 << 12), (1 << 12, 1 << 12)])
        )
        assert got == data[: 1 << 13]
        d = metrics.delta(s0)
        assert d.get('io_retries_total{reason="EIO"}', 0) >= 1
        assert inner.faults_injected >= 1

    def test_short_read_recovers_byte_identical(self, blob):
        p, data = blob
        src = RetryingSource(
            FlakySource(LocalFileSource(p), seed=3, short_rate=0.6),
            attempts=32, sleep=NOSLEEP, seed=2,
        )
        s0 = metrics.snapshot()
        assert src.read_at(128, 4096) == data[128 : 128 + 4096]
        d = metrics.delta(s0)
        assert d.get('io_retries_total{reason="short_read"}', 0) >= 1

    def test_permanent_failure_raises_typed_after_budget(self, blob):
        p, _data = blob
        inner = FlakySource(LocalFileSource(p), seed=0, permanent=True)
        src = RetryingSource(inner, attempts=5, sleep=NOSLEEP, seed=3)
        s0 = metrics.snapshot()
        with pytest.raises(SourceError) as exc:
            src.read_at(0, 64)
        assert "5 attempt" in str(exc.value)
        assert inner.reads == 5
        d = metrics.delta(s0)
        assert d.get('io_retries_total{reason="EIO"}', 0) == 5

    def test_deadline_cuts_the_ladder_short(self, blob):
        p, _data = blob
        inner = FlakySource(LocalFileSource(p), seed=0, permanent=True)
        src = RetryingSource(
            inner, attempts=100, deadline_s=0.05, base_delay_s=0.1,
            jitter=0.0, sleep=NOSLEEP,
        )
        with pytest.raises(SourceError):
            src.read_at(0, 64)
        assert inner.reads == 1  # first backoff would already blow the deadline

    def test_terminal_source_error_not_retried(self, blob):
        """A SourceError from the inner source (past-EOF, closed, an inner
        ladder's exhausted budget) is deterministic — backing off cannot
        help, so it propagates on the FIRST attempt."""
        p, _data = blob
        inner = FlakySource(LocalFileSource(p))  # counts reads, no faults
        src = RetryingSource(inner, attempts=8, sleep=NOSLEEP)
        with pytest.raises(SourceError):
            src.read_at(1 << 20, 64)  # far past EOF
        assert inner.reads == 1

    def test_latency_injection_still_correct(self, blob):
        p, data = blob
        waited = []
        src = FlakySource(
            LocalFileSource(p), seed=4, latency_s=0.001,
            latency_jitter_s=0.001, sleep=waited.append,
        )
        assert src.read_at(0, 32) == data[:32]
        assert len(waited) == 1 and 0.001 <= waited[0] <= 0.002

    def test_reader_end_to_end_over_flaky_source(self, eight_col):
        with FileReader(eight_col) as r:
            want = [r.read_row_group(i) for i in range(r.num_row_groups)]
        src = RetryingSource(
            FlakySource(LocalFileSource(eight_col), seed=6, error_rate=0.25,
                        short_rate=0.1),
            attempts=32, sleep=NOSLEEP, seed=4,
        )
        with FileReader(src) as r:
            got = [r.read_row_group(i) for i in range(r.num_row_groups)]
        src.close()
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g.keys() == w.keys()
            for path in w:
                assert np.array_equal(
                    np.asarray(g[path].values), np.asarray(w[path].values)
                ), path

    @pytest.mark.slow
    @pytest.mark.parametrize("seed", range(8))
    @pytest.mark.parametrize(
        "faults",
        [
            dict(error_rate=0.4),
            dict(short_rate=0.4),
            dict(error_rate=0.25, short_rate=0.25),
            dict(error_rate=0.15, latency_s=0.0002),
        ],
    )
    def test_retry_matrix_typed_or_identical(self, eight_col, seed, faults):
        """Every (seed, fault mix): the read either raises the typed
        SourceError (budget exhausted) or returns byte-identical data —
        never a raw errno leak, never silently wrong bytes."""
        with FileReader(eight_col, columns=["c0"]) as r:
            want = np.asarray(r.read_row_group(0)[("c0",)].values)
        src = RetryingSource(
            FlakySource(LocalFileSource(eight_col), seed=seed, **faults),
            attempts=6, sleep=NOSLEEP, seed=seed,
        )
        try:
            with FileReader(src, columns=["c0"]) as r:
                got = np.asarray(r.read_row_group(0)[("c0",)].values)
        except SourceError:
            return  # typed exhaustion is a legal outcome
        finally:
            src.close()
        assert np.array_equal(got, want)


class TestCoalesce:
    def test_adjacent_and_gap_merge(self):
        runs = coalesce([(0, 10), (10, 10), (30, 10)], gap=10)
        assert [(o, n) for o, n, _ in runs] == [(0, 40)]
        runs = coalesce([(0, 10), (30, 10)], gap=9)
        assert [(o, n) for o, n, _ in runs] == [(0, 10), (30, 10)]

    def test_gap_boundary_inclusive(self):
        # gap exactly equal to the threshold merges; one byte more splits
        runs = coalesce([(0, 10), (74, 10)], gap=64)
        assert len(runs) == 1
        runs = coalesce([(0, 10), (75, 10)], gap=64)
        assert len(runs) == 2

    def test_max_run_caps_merging(self):
        runs = coalesce([(0, 60), (60, 60)], gap=1024, max_run=100)
        assert len(runs) == 2

    def test_overlap_and_duplicates_always_merge(self):
        runs = coalesce([(0, 100), (50, 100), (0, 100)], gap=0, max_run=10)
        assert [(o, n) for o, n, _ in runs] == [(0, 150)]

    def test_members_preserved(self):
        runs = coalesce([(100, 5), (0, 10), (12, 4)], gap=4)
        assert runs[0][2] == [(0, 10), (12, 4)]
        assert runs[1][2] == [(100, 5)]

    def test_empty(self):
        assert coalesce([]) == []
        assert coalesce([(0, 0)]) == []

    def test_fetch_ranges_slices_and_caches(self, blob):
        p, data = blob
        cache = BlockCache(1 << 20)
        with LocalFileSource(p) as src:
            s0 = metrics.snapshot()
            out = fetch_ranges(src, [(0, 8), (16, 8)], cache=cache, gap=64)
            assert bytes(out[(0, 8)]) == data[:8]
            assert bytes(out[(16, 8)]) == data[16:24]
            # coalesced: ONE source read covered both members
            assert metrics.delta(s0).get("io_read_calls_total") == 1
            s1 = metrics.snapshot()
            out = fetch_ranges(src, [(0, 8), (16, 8)], cache=cache, gap=64)
            assert bytes(out[(0, 8)]) == data[:8]
            assert "io_read_calls_total" not in metrics.delta(s1)  # all cached

    def test_a_range_that_is_its_whole_run_is_cached_without_a_copy(self, blob):
        """A chunk fetched alone is one run: the cache keeps the buffer the
        source read. Members cut from a longer run are copied, so that a
        cached block never pins the run it came from."""
        p, data = blob
        cache = BlockCache(1 << 20)
        with LocalFileSource(p) as src:
            alone = fetch_ranges(src, [(32, 16)], cache=cache, gap=0)[(32, 16)]
            assert cache.get(src.source_id, 32, 16) is alone.obj
            run = fetch_ranges(src, [(0, 8), (16, 8)], cache=cache, gap=64)
            for key in ((0, 8), (16, 8)):
                held = cache.get(src.source_id, *key)
                assert held == data[key[0] : key[0] + 8] and len(held) == 8
                assert held is not run[key].obj


class TestPlanRanges:
    def test_full_vs_projected(self, eight_col):
        meta = FileReader.open_metadata(eight_col)
        full = plan_ranges(meta)
        assert len(full) == 16  # 8 columns x 2 row groups
        proj = plan_ranges(meta, columns={("c0",), ("c1",)})
        assert len(proj) == 4
        assert set(proj) <= set(full)
        assert sum(n for _o, n in proj) < 0.3 * sum(n for _o, n in full)

    def test_row_group_subset(self, eight_col):
        meta = FileReader.open_metadata(eight_col)
        g0 = plan_ranges(meta, row_groups=[0])
        assert len(g0) == 8
        assert set(g0) <= set(plan_ranges(meta))

    def test_page_index_ranges(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        p = tmp_path / "idx.parquet"
        pq.write_table(
            pa.table({"v": np.arange(1000, dtype=np.int64)}), p,
            write_page_index=True,
        )
        meta = FileReader.open_metadata(str(p))
        base = plan_ranges(meta)
        with_idx = plan_ranges(meta, page_index=True)
        assert len(with_idx) > len(base)


class TestBlockCache:
    def test_hit_miss_and_gauge(self):
        c = BlockCache(1 << 10)
        s0 = metrics.snapshot()
        assert c.get("s", 0, 4) is None
        c.put("s", 0, 4, b"abcd")
        assert c.get("s", 0, 4) == b"abcd"
        d = metrics.delta(s0)
        assert d.get("io_cache_hits_total") == 1
        assert d.get("io_cache_misses_total") == 1
        assert metrics.get("io_cache_bytes") >= 4

    def test_lru_eviction_under_budget(self):
        c = BlockCache(100)
        for k in range(10):
            c.put("s", k * 40, 40, bytes(40))
        st = c.stats()
        assert st["bytes"] <= 100
        assert c.get("s", 0, 40) is None  # oldest evicted
        assert c.get("s", 9 * 40, 40) is not None

    def test_oversize_block_skipped(self):
        c = BlockCache(10)
        c.put("s", 0, 100, bytes(100))
        assert c.stats()["blocks"] == 0

    def test_invalidate_one_source(self):
        c = BlockCache(1 << 10)
        c.put("a", 0, 4, b"aaaa")
        c.put("b", 0, 4, b"bbbb")
        c.invalidate("a")
        assert c.get("a", 0, 4) is None
        assert c.get("b", 0, 4) == b"bbbb"


class TestFooterCache:
    def test_warm_hit_performs_zero_source_reads(self, eight_col):
        fc = FooterCache()
        m1 = FileReader.open_metadata(eight_col, footer_cache=fc)
        s0 = metrics.snapshot()
        m2 = FileReader.open_metadata(eight_col, footer_cache=fc)
        d = metrics.delta(s0)
        assert m2 is m1
        assert "io_bytes_read_total" not in d
        assert "io_read_calls_total" not in d
        assert d.get("io_footer_cache_hits_total") == 1

    def test_rewrite_invalidates(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        p = tmp_path / "r.parquet"
        pq.write_table(pa.table({"v": np.arange(10, dtype=np.int64)}), p)
        fc = FooterCache()
        FileReader.open_metadata(str(p), footer_cache=fc)
        pq.write_table(pa.table({"v": np.arange(999, dtype=np.int64)}), p)
        os.utime(p)  # force a fresh mtime even on coarse filesystems
        m = FileReader.open_metadata(str(p), footer_cache=fc)
        assert m.num_rows == 999

    def test_max_entries_lru(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        fc = FooterCache(max_entries=2)
        for i in range(3):
            p = tmp_path / f"f{i}.parquet"
            pq.write_table(pa.table({"v": np.arange(4, dtype=np.int64)}), p)
            FileReader.open_metadata(str(p), footer_cache=fc)
        assert len(fc) == 2


class TestReaderWiring:
    def test_projected_read_fetches_under_40pct(self, eight_col):
        """Acceptance: a 2-of-8-column read through the planner fetches
        < 40% of the file's bytes (io_bytes_read_total vs file size)."""
        fsize = os.path.getsize(eight_col)
        s0 = metrics.snapshot()
        with FileReader(eight_col, columns=["c0", "c1"]) as r:
            rows = sum(
                next(iter(r.read_row_group(i).values())).num_values
                for i in range(r.num_row_groups)
            )
        assert rows == 30_000
        read = metrics.delta(s0).get("io_bytes_read_total", 0)
        assert 0 < read < 0.4 * fsize, (read, fsize)

    def test_warm_caches_reopen_zero_source_reads(self, eight_col):
        """Acceptance: re-opening against a warm footer + block cache
        performs zero source reads (footer included)."""
        cache = BlockCache(64 << 20)
        fc = FooterCache()
        with FileReader(eight_col, block_cache=cache, footer_cache=fc) as r:
            want = [r.read_row_group(i) for i in range(r.num_row_groups)]
        s0 = metrics.snapshot()
        with FileReader(eight_col, block_cache=cache, footer_cache=fc) as r:
            got = [r.read_row_group(i) for i in range(r.num_row_groups)]
        d = metrics.delta(s0)
        assert "io_bytes_read_total" not in d, d
        assert "io_read_calls_total" not in d
        for g, w in zip(got, want):
            for path in w:
                assert np.array_equal(
                    np.asarray(g[path].values), np.asarray(w[path].values)
                )

    def test_reader_accepts_bytes_and_sources(self, eight_col):
        data = Path(eight_col).read_bytes()
        with FileReader(eight_col) as r:
            want = r.read_row_group(0)
        for source in (data, MemorySource(data), _stdio.BytesIO(data)):
            with FileReader(source) as r:
                got = r.read_row_group(0)
            for path in want:
                assert np.array_equal(
                    np.asarray(got[path].values), np.asarray(want[path].values)
                )

    def test_memory_ceiling_still_enforced(self, eight_col):
        from parquet_tpu.core.alloc import AllocError

        with FileReader(eight_col, max_memory=1024) as r:
            with pytest.raises(AllocError):
                r.read_row_group(0)

    def test_truncated_file_stays_typed(self, eight_col):
        """The planner path must not leak SourceError for a truncated file:
        corruption keeps the decode ladder's typed error family."""
        from parquet_tpu.core.reader import PARQUET_ERRORS

        data = Path(eight_col).read_bytes()
        with pytest.raises(PARQUET_ERRORS):
            with FileReader(data[: len(data) // 2]) as r:
                r.read_row_group(0)

    def test_zero_length_chunk_stays_typed_and_quarantines(self, eight_col):
        """A lying footer claiming total_compressed_size == 0 must surface
        as the typed decode error (and quarantine under on_error='skip'),
        not a raw KeyError out of the batched-fetch path."""
        from parquet_tpu.core.reader import PARQUET_ERRORS

        with FileReader(eight_col) as r:
            r.metadata.row_groups[0].columns[0].meta_data.total_compressed_size = 0
            with pytest.raises(PARQUET_ERRORS):
                r.read_row_group(0)
        with FileReader(eight_col, on_error="skip") as r:
            r.metadata.row_groups[0].columns[0].meta_data.total_compressed_size = 0
            assert r.read_row_group(0) == {}  # group quarantined, typed path

    def test_io_spans_land_in_trace(self, eight_col):
        from parquet_tpu.utils.trace import decode_trace

        with decode_trace() as t:
            with FileReader(eight_col, columns=["c0"]) as r:
                r.read_row_group(0)
        assert "io.read" in t.stages
        assert "io.coalesce" in t.stages
        names = {e[0] for e in t._events}
        assert "io.read" in names


class TestReadahead:
    def test_fetches_into_cache(self, eight_col):
        meta = FileReader.open_metadata(eight_col)
        ranges = plan_ranges(meta, row_groups=[0])
        cache = BlockCache(64 << 20)
        ra = Readahead(cache)
        assert ra.schedule(eight_col, ranges)
        ra.drain()
        assert cache.stats()["blocks"] >= 1
        # a reader over the same file now decodes group 0 with zero source
        # reads past the footer
        with FileReader(eight_col, block_cache=cache) as r:
            s0 = metrics.snapshot()
            r.read_row_group(0)
            assert "io_bytes_read_total" not in metrics.delta(s0)

    def test_budget_overflow_drops(self, eight_col):
        cache = BlockCache(64 << 20)
        ra = Readahead(cache, budget_bytes=16)
        s0 = metrics.snapshot()
        assert not ra.schedule(eight_col, [(0, 1 << 20)])
        assert metrics.delta(s0).get("io_readahead_dropped_total") == 1

    def test_errors_swallowed_and_counted(self, tmp_path):
        cache = BlockCache(1 << 20)
        ra = Readahead(cache)
        s0 = metrics.snapshot()
        assert ra.schedule(str(tmp_path / "missing.parquet"), [(0, 128)])
        ra.drain()
        assert metrics.delta(s0).get("io_readahead_errors_total") == 1


class TestDatasetIO:
    @pytest.fixture
    def shards(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        rng = np.random.default_rng(0)
        for i in range(4):
            t = pa.table(
                {
                    "a": pa.array(rng.integers(0, 100, 600).astype(np.int64)),
                    "b": pa.array(rng.standard_normal(600).astype(np.float32)),
                }
            )
            pq.write_table(t, tmp_path / f"s-{i}.parquet", row_group_size=200)
        return str(tmp_path / "s-*.parquet")

    def test_second_epoch_hits_block_cache(self, shards):
        from parquet_tpu.data import ParquetDataset

        ds = ParquetDataset(
            shards, batch_size=300, num_epochs=2, prefetch=2,
            cache_bytes=32 << 20,
        )
        s0 = metrics.snapshot()
        with ds:
            rows = sum(
                next(iter(b.values())).shape[0] for b in ds
            )
        assert rows == 2 * 4 * 600
        d = metrics.delta(s0)
        assert d.get("io_cache_hits_total", 0) > 0

    def test_readahead_scheduled_for_upcoming_units(self, shards):
        from parquet_tpu.data import ParquetDataset

        ds = ParquetDataset(
            shards, batch_size=300, num_epochs=1, prefetch=2,
            cache_bytes=32 << 20,
        )
        s0 = metrics.snapshot()
        with ds:
            for _ in ds:
                pass
            ds._readahead.drain()
        d = metrics.delta(s0)
        assert (
            d.get("io_readahead_fetched_total", 0)
            + d.get("io_readahead_dropped_total", 0)
        ) >= 1

    def test_stream_identical_with_and_without_cache(self, shards):
        from parquet_tpu.data import ParquetDataset

        def drain(**kw):
            ds = ParquetDataset(
                shards, batch_size=250, num_epochs=1, shuffle=True, seed=3,
                prefetch=2, **kw,
            )
            with ds:
                return [
                    {p: a.copy() for p, a in b.items()} for b in ds
                ]

        plain = drain()
        cached = drain(cache_bytes=32 << 20)
        assert len(plain) == len(cached)
        for b0, b1 in zip(plain, cached):
            assert b0.keys() == b1.keys()
            for p in b0:
                assert np.array_equal(b0[p], b1[p])
