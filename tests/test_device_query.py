"""Device-resident query execution: differential parity vs the host engines.

The read-side twin of tests/test_device_encode.py's write matrix, closing
the HBM loop end to end:

  * core/filter_device.device_dnf_mask (through
    FileReader.read_row_group_device(filters=) and the
    iter_device_batches(filter_rows=True) compaction) must produce masks
    and batches BYTE-IDENTICAL to the host vec engine across the same
    type zoo test_filter_vec pins — ints, unsigned bit-pattern views,
    floats with NaN, decimals, strings/binary, bools, nulls everywhere,
    LIST `contains` — with every decline typed and counted into the host
    fallback, never divergent output;
  * serve/query_device.device_unit_partial (through
    ServeConfig(device=True) -> execute_query) must render query bodies
    identical to run_local_query's pyarrow-pinned host path, including
    the shapes OUTSIDE the device envelope (float sums, group_by,
    decimal domains) falling back typed-and-counted per unit;
  * FileWriter.write_device_column must produce files byte-identical to
    write_column across encodings x codecs x data-page versions.

Everything runs on CPU jax (conftest forces the platform); identity — not
speed — is the contract this suite pins.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

# x64 flips on at device_ops import: pull it in before ANY jnp array is
# built, or int64 test data silently truncates to int32
import parquet_tpu.kernels.device_ops  # noqa: E402,F401

from parquet_tpu.core.filter import normalize_dnf
from parquet_tpu.core.filter_vec import VecFilterError, dnf_mask
from parquet_tpu.core.reader import FileReader
from parquet_tpu.core.writer import FileWriter
from parquet_tpu.sink import MemorySink
from parquet_tpu.schema.dsl import parse_schema
from parquet_tpu.utils import metrics
from tests.test_filter_vec import ZOO_FILTERS, zoo  # noqa: F401

jnp = jax.numpy


# -- resident masks vs the host vec engine -------------------------------------


class TestDeviceMaskParity:
    @pytest.mark.parametrize(
        "filt", ZOO_FILTERS, ids=[str(f) for f in ZOO_FILTERS]
    )
    def test_mask_parity_type_zoo(self, zoo, filt):
        """Per row group: the device mask (engine ladder included) equals
        the host vec mask bit for bit; where even the host vec engine
        declines, the device path must raise the SAME typed error."""
        with FileReader(zoo) as r:
            nd = normalize_dnf(r.schema, filt)
            for i in range(r.num_row_groups):
                n = int(r.row_group(i).num_rows or 0)
                chunks = r._read_row_group(i, None, pack=False)
                try:
                    host = dnf_mask(chunks, nd, n)
                except VecFilterError:
                    with pytest.raises(VecFilterError):
                        r.read_row_group_device(i, filters=filt)
                    return
                _cols, mask = r.read_row_group_device(i, filters=filt)
                np.testing.assert_array_equal(np.asarray(mask), host)

    def test_device_engine_engages_and_counts(self, zoo):
        snap = metrics.snapshot()
        with FileReader(zoo) as r:
            _cols, mask = r.read_row_group_device(0, filters=[("i32", ">", 100)])
            assert int(jnp.sum(mask)) > 0
        d = metrics.delta(snap)
        assert d.get('events_total{event="device_filter_engaged"}', 0) > 0
        assert not d.get('events_total{event="device_filter_declined"}', 0)

    def test_plain_bytearray_declines_to_host_identically(self, tmp_path):
        """PLAIN (non-dictionary) byte arrays have no resident ordering:
        the device engine declines, counted, and the host vec mask is
        uploaded instead — same bits either way."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        vals = [f"row{i:04d}" for i in range(500)]
        p = str(tmp_path / "plainba.parquet")
        pq.write_table(
            pa.table({"s": pa.array(vals)}), p, use_dictionary=False
        )
        filt = [("s", ">=", "row0250")]
        snap = metrics.snapshot()
        with FileReader(p) as r:
            nd = normalize_dnf(r.schema, filt)
            chunks = r._read_row_group(0, None, pack=False)
            host = dnf_mask(chunks, nd, 500)
            _cols, mask = r.read_row_group_device(0, filters=filt)
        np.testing.assert_array_equal(np.asarray(mask), host)
        d = metrics.delta(snap)
        assert d.get('events_total{event="device_filter_declined"}', 0) > 0

    def test_filter_columns_delivered_beyond_projection(self, zoo):
        """read_row_group_device(filters=) extends the read set to the
        filter leaves and does NOT compact: the caller applies the mask
        (mask_take_device) and drops filter-only columns itself."""
        with FileReader(zoo) as r:
            cols, mask = r.read_row_group_device(
                0, ["i64"], filters=[("i32", "<", 100)]
            )
            assert ("i64",) in cols and ("i32",) in cols
            n = int(r.row_group(0).num_rows)
            assert mask.shape == (n,)
            assert cols[("i64",)].num_values == n  # not compacted


# -- filter_rows=True batch compaction vs host rows ----------------------------


def _numeric_corpus(tmp_path, groups=4, rows=1500):
    schema = parse_schema(
        """
        message m {
          required int64 id;
          required int32 tag (UINT_32);
          required double v;
          optional int64 maybe;
        }
        """
    )
    rng = np.random.default_rng(31)
    p = str(tmp_path / "corpus.parquet")
    with FileWriter(p, schema, codec="snappy", row_group_size=1 << 30) as w:
        for g in range(groups):
            base = g * rows
            w.write_column("id", np.arange(base, base + rows, dtype=np.int64))
            w.write_column(
                "tag",
                rng.integers(0, 1 << 32, rows, dtype=np.uint64)
                .astype(np.uint32)
                .view(np.int32),
            )
            v = rng.standard_normal(rows)
            v[::97] = np.nan
            w.write_column("v", v)
            dl = (rng.random(rows) < 0.85).astype(np.uint16)
            w.write_column(
                "maybe",
                np.flatnonzero(dl).astype(np.int64),
                def_levels=dl,
            )
            w.flush_row_group()
    return p


BATCH_FILTERS = [
    [("id", ">=", 1000), ("id", "<", 5000)],
    [("tag", ">=", 1 << 31)],
    [("v", ">", 0.5)],  # NaNs fail
    [("maybe", "not_null"), ("v", "<", 0.0)],
    [("maybe", "is_null")],
    [[("id", "<", 700)], [("tag", "<", 1 << 20)]],  # OR of conjunctions
    [("id", "in", [3, 4000, 5999, 123456])],
]


class TestFilterRowsBatches:
    @pytest.mark.parametrize("filt", BATCH_FILTERS, ids=str)
    def test_batches_match_host_filtered_rows(self, tmp_path, filt):
        p = _numeric_corpus(tmp_path)
        with FileReader(p) as r:
            got_id, got_v = [], []
            for b in r.iter_device_batches(
                512,
                columns=["id", "v"],
                drop_remainder=False,
                filters=filt,
                filter_rows=True,
            ):
                got_id.append(np.asarray(b[("id",)]))
                got_v.append(np.asarray(b[("v",)]))
            rows = list(r.iter_rows(filters=filt))
        got_id = np.concatenate(got_id) if got_id else np.empty(0, np.int64)
        got_v = np.concatenate(got_v) if got_v else np.empty(0)
        np.testing.assert_array_equal(
            got_id, np.array([x["id"] for x in rows], dtype=np.int64)
        )
        # floats compare as bit patterns: NaN payloads must survive
        np.testing.assert_array_equal(
            got_v.view(np.uint64),
            np.array([x["v"] for x in rows]).view(np.uint64),
        )

    def test_filter_rows_requires_filters(self, tmp_path):
        p = _numeric_corpus(tmp_path, groups=1, rows=64)
        with FileReader(p) as r:
            with pytest.raises(ValueError, match="filter_rows"):
                next(r.iter_device_batches(8, filter_rows=True))

    def test_default_stays_group_granularity(self, tmp_path):
        """filter_rows defaults OFF: filters= alone prunes row GROUPS and
        surviving groups stream whole (pinned separately in
        test_tpu_backend.test_device_batches_filter_pushdown)."""
        p = _numeric_corpus(tmp_path, groups=2, rows=1000)
        with FileReader(p) as r:
            n = sum(
                int(b[("id",)].shape[0])
                for b in r.iter_device_batches(
                    250, columns=["id"], filters=[("id", "<", 10)]
                )
            )
        assert n == 1000  # whole first group, rows NOT individually masked


# -- device partial aggregation through the serve executor ---------------------


def _agg_corpus(tmp_path):
    schema = parse_schema(
        """
        message m {
          required int64 id;
          required int32 u (UINT_32);
          optional int64 maybe;
          required double score;
          required int32 dec (DECIMAL(9, 2));
          required binary name (UTF8);
        }
        """
    )
    rng = np.random.default_rng(41)
    p = str(tmp_path / "agg.parquet")
    rows, groups = 1200, 3
    with FileWriter(p, schema, codec="snappy", row_group_size=1 << 30) as w:
        for g in range(groups):
            n = rows
            w.write_column(
                "id", rng.integers(-(10**12), 10**12, n).astype(np.int64)
            )
            w.write_column(
                "u",
                rng.integers(0, 1 << 32, n, dtype=np.uint64)
                .astype(np.uint32)
                .view(np.int32),
            )
            dl = (rng.random(n) < 0.8).astype(np.uint16)
            w.write_column(
                "maybe",
                rng.integers(0, 1000, int(dl.sum())).astype(np.int64),
                def_levels=dl,
            )
            w.write_column("score", rng.standard_normal(n))
            w.write_column("dec", rng.integers(-5000, 5000, n).astype(np.int32))
            w.write_column(
                "name", [["x", "y", "zz"][i % 3] for i in range(n)]
            )
            w.flush_row_group()
    return p


AGG_BODIES = [
    # inside the device envelope: global integer count/sum/min/max
    {"aggregates": ["count"]},
    {
        "aggregates": [
            "count",
            {"op": "sum", "column": "id"},
            {"op": "min", "column": "id"},
            {"op": "max", "column": "id"},
        ]
    },
    {"aggregates": [{"op": "sum", "column": "u"}, {"op": "max", "column": "u"}]},
    {"aggregates": [{"op": "count", "column": "maybe"},
                    {"op": "sum", "column": "maybe"}]},
    {
        "aggregates": ["count", {"op": "sum", "column": "id"}],
        "filters": [["id", ">", 0]],
    },
    {
        "aggregates": [{"op": "min", "column": "maybe"}],
        "filters": [["name", "==", "zz"]],
    },
    {
        "aggregates": ["count", {"op": "sum", "column": "id"}],
        "filters": [["maybe", "not_in", [1, 2]]],  # arrow null convention
    },
    {
        "aggregates": [{"op": "max", "column": "id"}],
        "filters": [["id", "<", -(10**13)]],  # zero matches -> null
    },
    # OUTSIDE the envelope: typed per-unit fallback to the host path
    {"aggregates": [{"op": "sum", "column": "score"}]},  # float domain
    {"aggregates": [{"op": "sum", "column": "dec"}]},  # decimal domain
    # grouped: the device's since PR 39 where the key chunk is a dictionary
    # (tests/test_query_group.py), the host's hash groupby otherwise
    {"aggregates": ["count"], "group_by": ["name"]},
]


@pytest.fixture(scope="module")
def agg_setup(tmp_path_factory):
    from parquet_tpu.serve.server import ScanService, ServeConfig

    tmp = tmp_path_factory.mktemp("device_agg")
    path = _agg_corpus(tmp)
    svc = ScanService(ServeConfig(root=str(tmp), device=True))
    return path, svc


class TestDeviceAggregates:
    def _body(self, path, body):
        from parquet_tpu.serve.protocol import parse_query_request

        return parse_query_request(
            json.dumps({"paths": [path], **body}).encode()
        )

    @pytest.mark.parametrize("body", AGG_BODIES, ids=lambda b: json.dumps(b))
    def test_device_query_matches_host(self, agg_setup, body):
        from parquet_tpu.serve.aggregate import (
            render_query_body,
            run_local_query,
        )

        path, svc = agg_setup
        q = self._body(path, body)
        host = render_query_body(run_local_query(q.paths, q))
        ticket, got = svc.query(q, "test")
        ticket.release()
        assert render_query_body(got) == host

    def test_units_counted_by_engine(self, agg_setup):
        path, svc = agg_setup
        snap = metrics.snapshot()
        for body in (
            {"aggregates": [{"op": "sum", "column": "id"}]},  # device
            {"aggregates": [{"op": "sum", "column": "score"}]},  # fallback: a float sum
        ):
            ticket, _ = svc.query(self._body(path, body), "test")
            ticket.release()
        d = metrics.delta(snap)
        assert d.get('query_device_units_total{engine="device"}', 0) > 0
        assert d.get('query_device_units_total{engine="host_fallback"}', 0) > 0

    def test_host_config_never_routes_device(self, agg_setup, tmp_path):
        from parquet_tpu.serve.server import ScanService, ServeConfig

        import os

        path, _svc = agg_setup
        host_svc = ScanService(ServeConfig(root=os.path.dirname(path)))
        snap = metrics.snapshot()
        ticket, _ = host_svc.query(
            self._body(path, {"aggregates": ["count"]}), "test"
        )
        ticket.release()
        d = metrics.delta(snap)
        assert not d.get('query_device_units_total{engine="device"}', 0)


# -- the device write path: byte identity across the encode matrix -------------


def _write_both(codec, dpv, with_crc=False, rows=900):
    """(host_bytes, device_bytes) for a 4-column file covering the PLAIN,
    RLE_DICTIONARY, DELTA_BINARY_PACKED and byte-array device routes."""
    schema = parse_schema(
        """
        message w {
          required int64 hi;
          required int64 lo;
          required int64 seq;
          required binary s (UTF8);
        }
        """
    )
    rng = np.random.default_rng(47)
    hi = rng.integers(-(2**60), 2**60, rows).astype(np.int64)  # PLAIN
    lo = rng.integers(0, 50, rows).astype(np.int64)  # dictionary
    seq = np.cumsum(rng.integers(0, 7, rows)).astype(np.int64)  # DELTA
    strs = [f"s{i % 37}" for i in range(rows)]
    data = np.frombuffer("".join(strs).encode(), dtype=np.uint8)
    offsets = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum([len(s) for s in strs], out=offsets[1:])

    def write(device):
        sink = MemorySink()
        w = FileWriter(
            sink,
            schema,
            codec=codec,
            data_page_version=dpv,
            with_crc=with_crc,
            column_encodings={"seq": "DELTA_BINARY_PACKED"},
        )
        for _ in range(2):
            if device:
                w.write_device_column("hi", jnp.asarray(hi))
                w.write_device_column("lo", jnp.asarray(lo))
                w.write_device_column("seq", jnp.asarray(seq))
                w.write_device_column(
                    "s", (jnp.asarray(data), jnp.asarray(offsets))
                )
            else:
                w.write_column("hi", hi)
                w.write_column("lo", lo)
                w.write_column("seq", seq)
                w.write_column("s", strs)
            w.flush_row_group()
        w.close()
        return sink.getvalue()

    return write(False), write(True)


class TestDeviceWriteMatrix:
    @pytest.mark.parametrize(
        "codec,dpv", [("snappy", 2), ("uncompressed", 1)], ids=str
    )
    def test_byte_identical_fast(self, codec, dpv):
        snap = metrics.snapshot()
        host, dev = _write_both(codec, dpv)
        assert host == dev
        d = metrics.delta(snap)
        assert d.get('events_total{event="device_write_engaged"}', 0) > 0

    @pytest.mark.slow
    @pytest.mark.parametrize("with_crc", [False, True], ids=["nocrc", "crc"])
    @pytest.mark.parametrize("dpv", [1, 2])
    @pytest.mark.parametrize("codec", ["uncompressed", "snappy", "gzip"])
    def test_byte_identical_full_matrix(self, codec, dpv, with_crc):
        host, dev = _write_both(codec, dpv, with_crc=with_crc)
        assert host == dev

    def test_byte_stream_split_falls_back_identically(self):
        schema = parse_schema("message w { required double x; }")
        rng = np.random.default_rng(3)
        x = rng.standard_normal(400)

        def write(device):
            sink = MemorySink()
            w = FileWriter(
                sink, schema, column_encodings={"x": "BYTE_STREAM_SPLIT"}
            )
            if device:
                w.write_device_column("x", jnp.asarray(x))
            else:
                w.write_column("x", x)
            w.close()
            return sink.getvalue()

        snap = metrics.snapshot()
        host, dev = write(False), write(True)
        assert host == dev
        d = metrics.delta(snap)
        assert d.get('events_total{event="device_write_declined"}', 0) > 0


# -- dataset filter_rows -------------------------------------------------------


class TestDatasetFilterRows:
    def test_rows_filtered_and_filter_columns_dropped(self, tmp_path):
        from parquet_tpu.data.dataset import ParquetDataset

        p = _numeric_corpus(tmp_path, groups=3, rows=1000)
        filt = [("id", ">=", 500), ("id", "<", 2500), ("tag", ">=", 1 << 31)]
        ds = ParquetDataset(
            p,
            batch_size=128,
            columns=["id", "v"],
            filters=filt,
            filter_rows=True,
            remainder="keep",
            prefetch=0,
        )
        got_id, got_v = [], []
        for b in ds:
            assert set(b) == {("id",), ("v",)}  # tag read but not delivered
            got_id.append(np.asarray(b[("id",)]))
            got_v.append(np.asarray(b[("v",)]))
        got_id = np.concatenate(got_id)
        got_v = np.concatenate(got_v)
        with FileReader(p) as r:
            rows = list(r.iter_rows(filters=filt))
        np.testing.assert_array_equal(
            got_id, np.array([x["id"] for x in rows], dtype=np.int64)
        )
        np.testing.assert_array_equal(
            got_v.view(np.uint64),
            np.array([x["v"] for x in rows]).view(np.uint64),
        )

    def test_filter_rows_requires_filters(self, tmp_path):
        from parquet_tpu.data.dataset import ParquetDataset

        with pytest.raises(ValueError, match="filter_rows"):
            ParquetDataset(
                str(tmp_path / "x.parquet"), batch_size=8, filter_rows=True
            )

    def test_resume_reproduces_filtered_tail(self, tmp_path):
        from parquet_tpu.data.dataset import ParquetDataset

        p = _numeric_corpus(tmp_path, groups=3, rows=1000)
        filt = [("id", "<", 2200)]

        def make():
            return ParquetDataset(
                p,
                batch_size=100,
                columns=["id"],
                filters=filt,
                filter_rows=True,
                remainder="keep",
                prefetch=0,
            )

        it = iter(make())
        for _ in range(4):
            next(it)
        state = it.state_dict()
        rest = [np.asarray(b[("id",)]) for b in it]
        it2 = iter(make())
        it2.load_state_dict(state)
        rest2 = [np.asarray(b[("id",)]) for b in it2]
        assert len(rest) == len(rest2)
        for a, b in zip(rest, rest2):
            np.testing.assert_array_equal(a, b)


# -- the extended slow sweep ---------------------------------------------------


@pytest.mark.slow
class TestExtendedSweep:
    def test_mask_parity_random_predicates(self, zoo):
        """Randomized DNF shapes over the zoo, device vs host per group —
        the long tail the enumerated list can't reach."""
        rng = np.random.default_rng(77)
        ops = ["==", "!=", "<", "<=", ">", ">="]
        cols = [
            ("i32", lambda: int(rng.integers(-10, 810))),
            ("i64", lambda: int(rng.integers(-500, 500))),
            ("u32", lambda: (1 << 31) + int(rng.integers(0, 800))),
            ("f", lambda: float(rng.standard_normal())),
            ("s", lambda: f"v{int(rng.integers(0, 25))}"),
        ]
        with FileReader(zoo) as r:
            for _ in range(60):
                conj = []
                for _ in range(int(rng.integers(1, 4))):
                    name, gen = cols[int(rng.integers(0, len(cols)))]
                    conj.append((name, ops[int(rng.integers(0, len(ops)))], gen()))
                filt = [conj]
                nd = normalize_dnf(r.schema, filt)
                for i in range(r.num_row_groups):
                    n = int(r.row_group(i).num_rows or 0)
                    chunks = r._read_row_group(i, None, pack=False)
                    try:
                        host = dnf_mask(chunks, nd, n)
                    except VecFilterError:
                        continue
                    _c, mask = r.read_row_group_device(i, filters=filt)
                    np.testing.assert_array_equal(
                        np.asarray(mask), host, err_msg=str(filt)
                    )

    def test_filtered_rows_match_iter_rows_full_zoo(self, zoo):
        """Every zoo filter the host vec engine accepts, compacted on
        device (numeric projection) vs the row oracle."""
        with FileReader(zoo) as r:
            for filt in ZOO_FILTERS:
                try:
                    rows = list(r.iter_rows(filters=filt))
                except Exception:
                    continue
                try:
                    got = [
                        np.asarray(b[("i32",)])
                        for b in r.iter_device_batches(
                            128,
                            columns=["i32"],
                            drop_remainder=False,
                            filters=filt,
                            filter_rows=True,
                        )
                    ]
                except VecFilterError:
                    continue
                flat = (
                    np.concatenate(got) if got else np.empty(0, np.int32)
                )
                np.testing.assert_array_equal(
                    flat,
                    np.array([x["i32"] for x in rows], dtype=np.int32),
                    err_msg=str(filt),
                )
