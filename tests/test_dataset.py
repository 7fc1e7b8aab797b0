"""parquet_tpu.data: the streaming dataset subsystem's contracts.

Pinned here:
  * plan determinism: glob order, unit layout, filter pruning, corrupt-file
    skipping at plan time;
  * sharding: every unit visited by exactly one shard per epoch, shuffled
    or not, for shard counts 1/2/4 (and the worker sub-split);
  * the batch stream equals the source rows, rebatched with carry across
    unit boundaries; remainder modes drop/keep/pad;
  * mid-epoch checkpoint/resume reproduces the remaining batch stream
    BYTE-IDENTICALLY across shuffle seeds and shard counts — including a
    cursor inside a unit;
  * on_error="skip": a corrupt page quarantines only its row group, an
    unreadable footer drops only its file, and every clean row still
    arrives exactly once;
  * the prefetch pipeline survives concurrency (two iterators on two
    threads, bounded queue) under a watchdog — a deadlock fails fast
    instead of hanging CI;
  * device delivery: batches land as jax arrays (and sharded over a mesh)
    with the same values as host delivery.
"""

from __future__ import annotations

import glob
import shutil
import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from parquet_tpu.core.reader import FileReader
from parquet_tpu.data import ParquetDataset, build_plan, expand_paths
from parquet_tpu.meta.file_meta import ParquetFileError
from parquet_tpu.utils import metrics

WATCHDOG_SECONDS = 60.0

N_FILES = 5
ROWS = [700, 800, 900, 1000, 1100]  # per file; row_group_size=300 -> 3-4 units
ROW_GROUP = 300


def _write_shards(d, rows=ROWS, seed=0, nulls=False):
    rng = np.random.default_rng(seed)
    paths = []
    for i, n in enumerate(rows):
        mask = (rng.random(n) < 0.2) if nulls else None
        t = pa.table(
            {
                "x": pa.array(
                    rng.standard_normal(n).astype(np.float32), mask=mask
                ),
                "y": pa.array(rng.integers(0, 1 << 40, n).astype(np.int64)),
            }
        )
        p = str(d / f"shard-{i:03d}.parquet")
        pq.write_table(t, p, row_group_size=ROW_GROUP)
        paths.append(p)
    return paths


@pytest.fixture(scope="module")
def shard_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("dataset_shards")
    _write_shards(d)
    return d


@pytest.fixture(scope="module")
def pattern(shard_dir):
    return str(shard_dir / "shard-*.parquet")


def _source_rows(pattern):
    """Concatenated source columns in file-major order (the no-shuffle
    stream's reference)."""
    xs, ys = [], []
    for p in sorted(glob.glob(pattern)):
        t = pq.read_table(p)
        xs.append(t.column("x").to_numpy())
        ys.append(t.column("y").to_numpy())
    return np.concatenate(xs), np.concatenate(ys)


def _drain(it):
    return [{k: np.asarray(v) for k, v in b.items()} for b in it]


def _batches_equal(a, b):
    assert len(a) == len(b), (len(a), len(b))
    for ba, bb in zip(a, b):
        assert ba.keys() == bb.keys()
        for k in ba:
            assert np.array_equal(ba[k], bb[k]), k


def with_watchdog(fn, timeout: float = WATCHDOG_SECONDS):
    """Run fn on a daemon thread; a hang FAILS loudly instead of stalling
    the suite (same harness shape as test_faults)."""
    result: dict = {}

    def target():
        try:
            result["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised on main thread
            result["error"] = e

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(timeout)
    if t.is_alive():
        pytest.fail(f"watchdog: dataset still running after {timeout}s (hang)")
    if "error" in result:
        raise result["error"]
    return result.get("value")


class TestPlan:
    def test_units_and_rows(self, pattern):
        plan = build_plan(pattern)
        assert plan.num_units == sum(-(-n // ROW_GROUP) for n in ROWS)
        assert plan.total_rows == sum(ROWS)
        # file-major, group-minor, lexicographic file order
        assert [u.row_group for u in plan.units[:3]] == [0, 1, 2]
        assert plan.units[0].path <= plan.units[-1].path

    def test_expand_paths_sorted_and_errors(self, pattern, shard_dir):
        files = expand_paths(pattern)
        assert files == sorted(files) and len(files) == N_FILES
        assert expand_paths(files[0]) == [files[0]]
        with pytest.raises(FileNotFoundError):
            expand_paths(str(shard_dir / "nope-*.parquet"))
        with pytest.raises(ValueError):
            expand_paths([])

    def test_filters_prune_units(self, pattern):
        # y >= 0 admits everything; an impossible predicate prunes all units
        assert build_plan(pattern, filters=[("y", ">=", 0)]).num_units > 0
        assert build_plan(pattern, filters=[("y", "<", -1)]).num_units == 0

    def test_epoch_order_is_seed_epoch_function(self, pattern):
        plan = build_plan(pattern)
        a = plan.epoch_order(3, seed=5, shuffle=True)
        b = plan.epoch_order(3, seed=5, shuffle=True)
        c = plan.epoch_order(4, seed=5, shuffle=True)
        d = plan.epoch_order(3, seed=6, shuffle=True)
        assert a == b
        assert a != c and a != d  # different epoch/seed reshuffle
        assert sorted(a) == list(range(plan.num_units))

    @pytest.mark.parametrize("shuffle", [False, True])
    @pytest.mark.parametrize("count", [1, 2, 4])
    def test_shards_partition_exactly_once(self, pattern, shuffle, count):
        plan = build_plan(pattern)
        seen = []
        for i in range(count):
            seen.extend(
                plan.epoch_order(
                    1, seed=2, shuffle=shuffle, shard_index=i, shard_count=count
                )
            )
        assert sorted(seen) == list(range(plan.num_units))

    def test_worker_subsplit_partitions(self, pattern):
        plan = build_plan(pattern)
        units = []
        for si in range(2):
            for wi in range(2):
                ds = ParquetDataset(
                    pattern, batch_size=64, shard=(si, 2), worker=(wi, 2),
                    shuffle=True, seed=1,
                )
                units.extend(ds.epoch_order(0))
        assert sorted(units) == list(range(plan.num_units))


class TestStream:
    @pytest.mark.parametrize("prefetch", [0, 2])
    def test_matches_source_order(self, pattern, prefetch):
        xs, ys = _source_rows(pattern)
        ds = ParquetDataset(
            pattern, batch_size=256, prefetch=prefetch, remainder="keep"
        )
        got = _drain(iter(ds))
        gx = np.concatenate([b[("x",)] for b in got])
        gy = np.concatenate([b[("y",)] for b in got])
        assert np.array_equal(gx, xs) and np.array_equal(gy, ys)
        assert all(b[("x",)].shape[0] == 256 for b in got[:-1])

    def test_remainder_modes(self, pattern):
        total = sum(ROWS)
        b = 512
        full = total // b
        drop = _drain(iter(ParquetDataset(pattern, batch_size=b)))
        assert len(drop) == full and all(
            x[("x",)].shape[0] == b for x in drop
        )
        keep = _drain(
            iter(ParquetDataset(pattern, batch_size=b, remainder="keep"))
        )
        assert len(keep) == full + 1
        assert keep[-1][("x",)].shape[0] == total - full * b
        pad = _drain(
            iter(ParquetDataset(pattern, batch_size=b, remainder="pad"))
        )
        assert len(pad) == full + 1
        assert pad[-1][("x",)].shape[0] == b
        tail = total - full * b
        assert np.all(pad[-1][("x",)][tail:] == 0)
        assert np.array_equal(pad[-1][("x",)][:tail], keep[-1][("x",)])

    def test_carry_crosses_unit_boundaries(self, pattern):
        # batch > unit size forces every batch to span units
        ds = ParquetDataset(pattern, batch_size=450, remainder="keep")
        xs, _ = _source_rows(pattern)
        got = np.concatenate([np.asarray(b[("x",)]) for b in ds])
        assert np.array_equal(got, xs)

    def test_multi_epoch_reshuffles(self, pattern):
        ds = ParquetDataset(
            pattern, batch_size=300, shuffle=True, seed=4, num_epochs=2,
            remainder="keep",
        )
        batches = _drain(iter(ds))
        half = len(batches) // 2
        e0 = np.concatenate([b[("y",)] for b in batches[:half]])
        e1 = np.concatenate([b[("y",)] for b in batches[half:]])
        assert not np.array_equal(e0, e1)  # different epoch order
        assert np.array_equal(np.sort(e0), np.sort(e1))  # same multiset

    def test_nulls_raise_by_default_and_zero_fill(self, tmp_path):
        _write_shards(tmp_path, rows=[600], nulls=True)
        p = str(tmp_path / "shard-000.parquet")
        with pytest.raises(ParquetFileError, match="nulls"):
            _drain(iter(ParquetDataset(p, batch_size=100)))
        ds = ParquetDataset(p, batch_size=100, nullable="zero")
        got = np.concatenate([np.asarray(b[("x",)]) for b in ds])
        want = pq.read_table(p).column("x").to_numpy(zero_copy_only=False)
        want = np.where(np.isnan(want), 0, want).astype(np.float32)
        assert np.array_equal(got, want[: len(got)])

    def test_schema_mismatch_across_files(self, tmp_path):
        _write_shards(tmp_path, rows=[400])
        t = pa.table({"x": pa.array(np.arange(400, dtype=np.int32)),
                      "y": pa.array(np.arange(400, dtype=np.int64))})
        pq.write_table(t, tmp_path / "shard-zzz.parquet", row_group_size=200)
        ds = ParquetDataset(
            str(tmp_path / "shard-*.parquet"), batch_size=128
        )
        with pytest.raises(ParquetFileError, match="schema mismatch"):
            _drain(iter(ds))

    def test_bad_projection_raises_even_under_skip(self, pattern):
        """A misspelled columns= or filter column is a CONFIG error, not
        corruption: on_error='skip' must not quarantine every unit into a
        silently empty dataset."""
        ds = ParquetDataset(
            pattern, batch_size=128, columns=["nope"], on_error="skip"
        )
        with pytest.raises(ParquetFileError, match="not in schema"):
            ds.plan  # noqa: B018
        with pytest.raises(ValueError):
            build_plan(pattern, filters=[("nope", ">=", 0)], on_error="skip")

    def test_closed_dataset_refuses_iteration(self, pattern):
        ds = ParquetDataset(pattern, batch_size=128, prefetch=2)
        it = iter(ds)
        next(it)
        it.close()  # releases its in-flight prefetch accounting
        ds.close()
        ds.close()  # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            iter(ds)

    def test_config_validation(self, pattern):
        with pytest.raises(ValueError):
            ParquetDataset(pattern, batch_size=0)
        with pytest.raises(ValueError):
            ParquetDataset(pattern, batch_size=8, remainder="nope")
        with pytest.raises(ValueError):
            ParquetDataset(pattern, batch_size=8, on_error="null")
        with pytest.raises(ValueError):
            ParquetDataset(pattern, batch_size=8, shard=(2, 2))
        with pytest.raises(ValueError):
            ParquetDataset(pattern, batch_size=8, prefetch=-1)
        with pytest.raises(ValueError, match='only shard= accepts "jax"'):
            ParquetDataset(pattern, batch_size=8, worker="jax")

    def test_sync_path_records_wait(self, pattern):
        """prefetch=0 blocks on every decode — wait_share must say so, not
        read 0% at the one depth where starvation is total."""
        s0 = metrics.snapshot()
        _drain(iter(ParquetDataset(pattern, batch_size=512, prefetch=0)))
        d = metrics.delta(s0)
        assert d.get("dataset_wait_seconds_count", 0) > 0
        assert d.get("dataset_wait_seconds_sum", 0) > 0


class TestCheckpoint:
    @pytest.mark.parametrize("count", [1, 2, 4])
    @pytest.mark.parametrize("seed", [0, 7])
    def test_resume_byte_identical(self, pattern, count, seed):
        for index in range(count):
            ds = ParquetDataset(
                pattern, batch_size=192, shuffle=True, seed=seed,
                shard=(index, count), num_epochs=2, remainder="keep",
            )
            it = iter(ds)
            consumed = 0
            head = []
            # cut mid-epoch, mid-unit: 192 does not divide the 300-row units
            for b in it:
                head.append(b)
                consumed += 1
                if consumed == 3:
                    break
            state = it.state_dict()
            rest = _drain(it)
            it2 = ParquetDataset(
                pattern, batch_size=192, shuffle=True, seed=seed,
                shard=(index, count), num_epochs=2, remainder="keep",
                prefetch=0,  # prefetch config is free to differ on resume
            ).iterator(state=state)
            _batches_equal(rest, _drain(it2))

    def test_state_covers_delivered_batches_only(self, pattern):
        ds = ParquetDataset(pattern, batch_size=256, num_epochs=1)
        it = iter(ds)
        s0 = it.state_dict()
        assert (s0["epoch"], s0["unit_pos"], s0["row_offset"]) == (0, 0, 0)
        first = next(it)
        s1 = it.state_dict()
        it2 = ds.iterator(state=s1)
        rest1 = _drain(it)
        rest2 = _drain(it2)
        _batches_equal(rest1, rest2)
        # and resuming from s0 replays the FIRST batch too
        replay = next(ds.iterator(state=s0))
        assert np.array_equal(
            np.asarray(replay[("x",)]), np.asarray(first[("x",)])
        )

    def test_exhausted_state_resumes_empty(self, pattern):
        ds = ParquetDataset(pattern, batch_size=512, num_epochs=1)
        it = iter(ds)
        _drain(it)
        state = it.state_dict()
        assert state["exhausted"]
        assert _drain(ds.iterator(state=state)) == []

    def test_mismatched_config_rejected(self, pattern):
        ds = ParquetDataset(pattern, batch_size=128)
        state = iter(ds).state_dict()
        for kw in (
            {"batch_size": 64},
            {"batch_size": 128, "seed": 9, "shuffle": True},
            {"batch_size": 128, "shard": (0, 2)},
        ):
            other = ParquetDataset(pattern, **kw)
            with pytest.raises(ValueError, match="mismatch"):
                other.iterator(state=state)

    def test_changed_file_set_rejected_moved_dir_accepted(self, tmp_path):
        """Same aggregate counts, different unit list: the fingerprint
        digest must reject the cursor (renamed shards are the classic
        re-materialization trap); moving the intact directory must NOT
        (basenames, not full paths, are pinned)."""
        _write_shards(tmp_path, rows=[600, 600])
        pat = str(tmp_path / "shard-*.parquet")
        ds = ParquetDataset(pat, batch_size=100, remainder="keep")
        it = iter(ds)
        for _ in range(3):
            next(it)
        state = it.state_dict()
        rest = _drain(it)
        # whole-directory move with names intact: resume byte-identical
        moved = tmp_path / "moved"
        moved.mkdir()
        for p in sorted(tmp_path.glob("shard-*.parquet")):
            p.rename(moved / p.name)
        at_new_home = ParquetDataset(
            str(moved / "shard-*.parquet"), batch_size=100, remainder="keep"
        )
        _batches_equal(rest, _drain(at_new_home.iterator(state=state)))
        # renaming one shard reorders/renames the unit list: rejected even
        # though files/units/rows all still match
        (moved / "shard-000.parquet").rename(moved / "shard-009.parquet")
        renamed = ParquetDataset(
            str(moved / "shard-*.parquet"), batch_size=100, remainder="keep"
        )
        with pytest.raises(ValueError, match="plan mismatch"):
            renamed.iterator(state=state)

    def test_started_iterator_rejects_load(self, pattern):
        ds = ParquetDataset(pattern, batch_size=128)
        it = iter(ds)
        state = it.state_dict()
        next(it)
        with pytest.raises(RuntimeError):
            it.load_state_dict(state)


class TestFaults:
    def test_skip_delivers_clean_rows_exactly_once(self, tmp_path):
        paths = _write_shards(tmp_path)
        # corrupt ONE row group of one extra file: stomp its first data page
        bad_page = tmp_path / "zz-badpage.parquet"
        shutil.copy(paths[0], bad_page)
        meta = FileReader.open_metadata(bad_page)
        cc = meta.row_groups[0].columns[0].meta_data
        with open(bad_page, "r+b") as f:
            f.seek(cc.data_page_offset + 16)
            f.write(b"\xff" * 64)
        # and one file whose footer is garbage
        bad_footer = tmp_path / "zz-badfooter.parquet"
        bad_footer.write_bytes(b"PAR1this is not a parquet footerPAR1")

        everything = str(tmp_path / "*.parquet")
        with pytest.raises(ParquetFileError):
            ParquetDataset(everything, batch_size=100).plan  # noqa: B018

        s0 = metrics.snapshot()
        ds = ParquetDataset(
            everything, batch_size=100, on_error="skip", shuffle=True,
            seed=11, remainder="keep",
        )
        got = np.concatenate([np.asarray(b[("y",)]) for b in ds])
        d = metrics.delta(s0)
        assert d.get('events_total{event="dataset_files_skipped"}') == 1
        assert d.get('events_total{event="dataset_units_skipped"}') == 1
        assert [p for p, _ in ds.plan.skipped_files] == [str(bad_footer)]

        # clean shards' rows exactly once, plus bad_page's SURVIVING groups
        clean_y = [
            pq.read_table(p).column("y").to_numpy() for p in paths
        ]
        surviving = pq.read_table(paths[0]).column("y").to_numpy()[ROW_GROUP:]
        want = np.sort(np.concatenate(clean_y + [surviving]))
        assert np.array_equal(np.sort(got), want)

    def test_corpus_shard_degrades(self, tmp_path):
        """One shard from the committed corrupt corpus rides a clean glob:
        the dataset's skip accounting must agree exactly with FileReader's
        own quarantine of the same file (clean file's rows + the corrupt
        file's surviving rows, nothing twice)."""
        import os

        corpus = os.path.join(
            os.path.dirname(__file__), "data", "corrupt"
        )
        shutil.copy(os.path.join(corpus, "pristine.parquet"),
                    tmp_path / "a-clean.parquet")
        # page_header_garbage: footer intact (units planned), a page fails
        # at decode -> its row group quarantines; truncated_mid_page: footer
        # gone -> whole file skipped at plan time
        for name in ("page_header_garbage", "truncated_mid_page"):
            shutil.copy(os.path.join(corpus, f"{name}.parquet"),
                        tmp_path / f"b-{name}.parquet")
        # what the reader itself salvages from the damaged files
        surviving = []
        for name in ("page_header_garbage", "truncated_mid_page"):
            try:
                with FileReader(
                    str(tmp_path / f"b-{name}.parquet"), columns=["id"],
                    on_error="skip",
                ) as r:
                    surviving.extend(
                        np.asarray(c[("id",)].values)
                        for c in (
                            r._read_row_group(g, None, pack=False)
                            for g in range(r.num_row_groups)
                        )
                        if c
                    )
            except ParquetFileError:
                pass  # unreadable footer: the file contributes nothing
        ds = ParquetDataset(
            str(tmp_path / "*.parquet"), batch_size=97, columns=["id"],
            on_error="skip", nullable="zero", remainder="keep",
        )
        got = np.concatenate([np.asarray(b[("id",)]) for b in ds])
        clean = pq.read_table(
            tmp_path / "a-clean.parquet"
        ).column("id").to_numpy()
        want = np.sort(np.concatenate([clean] + surviving))
        assert np.array_equal(np.sort(got), want)

    def test_null_policy_zero_fills_corrupt_chunk(self, tmp_path):
        paths = _write_shards(tmp_path, rows=[600])
        want_y = pq.read_table(paths[0]).column("y").to_numpy()
        meta = FileReader.open_metadata(paths[0])
        cc = meta.row_groups[0].columns[0].meta_data  # column "x"
        with open(paths[0], "r+b") as f:
            f.seek(cc.data_page_offset + 16)
            f.write(b"\xff" * 64)
        ds = ParquetDataset(
            paths, batch_size=100, on_error="null", nullable="zero",
            remainder="keep",
        )
        got = _drain(iter(ds))
        # no rows lost: the corrupt x-chunk delivers as zeros, row-aligned
        # with the intact y column of the same group
        assert sum(b[("x",)].shape[0] for b in got) == 600
        x = np.concatenate([b[("x",)] for b in got])
        y = np.concatenate([b[("y",)] for b in got])
        assert np.all(x[:ROW_GROUP] == 0)
        assert np.array_equal(y, want_y)

    def test_raise_policy_propagates(self, tmp_path):
        paths = _write_shards(tmp_path, rows=[500])
        bad = tmp_path / "zz-bad.parquet"
        shutil.copy(paths[0], bad)
        meta = FileReader.open_metadata(bad)
        cc = meta.row_groups[0].columns[0].meta_data
        with open(bad, "r+b") as f:
            f.seek(cc.data_page_offset + 16)
            f.write(b"\xff" * 64)
        from parquet_tpu.core.reader import PARQUET_ERRORS

        ds = ParquetDataset(str(tmp_path / "*.parquet"), batch_size=100)
        with pytest.raises(PARQUET_ERRORS):
            _drain(iter(ds))


class TestPrefetch:
    def test_two_iterators_two_threads_watchdog(self, pattern):
        """Tier-1 loader stress: concurrent iterators over one dataset's
        bounded pool must neither deadlock nor cross their streams."""
        xs, _ = _source_rows(pattern)

        def run():
            ds = ParquetDataset(
                pattern, batch_size=128, prefetch=2, remainder="keep"
            )
            out = [None, None]
            errs = []

            def worker(slot):
                try:
                    out[slot] = np.concatenate(
                        [np.asarray(b[("x",)]) for b in ds]
                    )
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)

            ts = [
                threading.Thread(target=worker, args=(i,)) for i in range(2)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(WATCHDOG_SECONDS)
            assert not errs, errs
            return out

        out = with_watchdog(run)
        for got in out:
            assert got is not None and np.array_equal(got, xs)

    def test_close_mid_stream_cancels(self, pattern):
        ds = ParquetDataset(pattern, batch_size=100, prefetch=3)
        it = iter(ds)
        next(it)
        it.close()
        with pytest.raises(StopIteration):
            next(it)
        ds.close()  # idempotent, queued work cancelled
        ds.close()

    def test_wait_metrics_and_gauge(self, pattern):
        s0 = metrics.snapshot()
        ds = ParquetDataset(pattern, batch_size=512, prefetch=2)
        n = len(_drain(iter(ds)))
        d = metrics.delta(s0)
        assert d.get("dataset_batches_total") == n
        assert d.get("dataset_rows_total") == n * 512
        assert d.get("dataset_wait_seconds_count", 0) > 0
        # the gauge exists, settles to 0 after the drain, and is a gauge in
        # the exposition. It counts the PROCESS's units in flight, and an
        # iterator abandoned by an earlier test gives its share back only
        # when it is collected: collect first (the gauge read 2 here once in
        # three whole runs under six loaded workers; not reproduced alone)
        import gc

        gc.collect()
        assert metrics.get("dataset_prefetch_depth") == 0
        assert (
            "# TYPE parquet_tpu_dataset_prefetch_depth gauge"
            in metrics.render_prometheus()
        )


class TestTraceSpans:
    def test_dataset_spans_recorded(self, pattern):
        from parquet_tpu.utils.trace import decode_trace

        with decode_trace() as t:
            ds = ParquetDataset(pattern, batch_size=512, prefetch=2)
            _drain(iter(ds))
        names = {e[0] for e in t._events}
        assert "dataset.unit" in names
        assert "dataset.wait" in t.stages


class TestDevice:
    def test_device_batches_match_host(self, pattern):
        import jax

        host = _drain(
            iter(ParquetDataset(pattern, batch_size=256, num_epochs=1))
        )
        dev_ds = ParquetDataset(
            pattern, batch_size=256, num_epochs=1, device=jax.devices()[0]
        )
        dev = list(dev_ds)
        assert all(
            isinstance(b[("x",)], jax.Array) for b in dev
        )
        _batches_equal(host, _drain(iter(dev)))

    def test_device_put_pipelined_defers_source_error(self):
        """A source failure surfaces at the stream position where it
        happened: batches already staged/uploaded deliver first, then the
        error — never dropped rows, never an early misattributed raise."""
        from parquet_tpu.kernels.pipeline import device_put_pipelined

        def src():
            yield {"a": np.arange(4)}
            yield {"a": np.arange(4, 8)}
            raise RuntimeError("boom")

        got = []
        with pytest.raises(RuntimeError, match="boom"):
            for b in device_put_pipelined(src(), depth=3):
                got.append(np.asarray(b["a"]))
        assert len(got) == 2
        assert np.array_equal(got[1], np.arange(4, 8))

    def test_sharded_batches(self, pattern):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("data",))
        ds = ParquetDataset(
            pattern, batch_size=256, num_epochs=1,
            device=NamedSharding(mesh, P("data")),
        )
        b = next(iter(ds))
        assert b[("x",)].sharding.spec == P("data")


class TestReaderSatellites:
    def test_open_metadata_matches_full_open(self, pattern):
        p = sorted(glob.glob(pattern))[0]
        meta = FileReader.open_metadata(p)
        with FileReader(p) as r:
            assert meta.num_rows == r.metadata.num_rows
            # reusing the parsed footer skips the re-parse entirely
            with FileReader(p, metadata=meta) as r2:
                assert r2.num_rows == r.num_rows

    def test_open_many_and_idempotent_close(self, pattern):
        files = sorted(glob.glob(pattern))
        readers = FileReader.open_many(files)
        assert [r.num_rows for r in readers] == [
            pq.read_table(p).num_rows for p in files
        ]
        for r in readers:
            r.close()
            r.close()  # idempotent under open/close churn
        # all-or-nothing: one bad path closes the rest and raises
        with pytest.raises(FileNotFoundError):
            FileReader.open_many(files + [files[0] + ".nope"])
