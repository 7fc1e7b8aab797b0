"""The hybrid frame: what pack_hybrid_upload ships in place of a dictionary
chunk's RLE/bit-packed index stream (kernels/device_ops.py), through the one
freeze (kernels/pipeline.py _freeze_hybrid_from_tables).

Real hybrid wire (ops/rle_hybrid.py) laid out as the staged walk's tables,
frozen once with the native writer (ptq_hybrid_frame) and once with its NumPy
reference, byte for byte, over every width; the upload's shape as a function
of (shipped width, n_pad) alone; and pyarrow files end to end on the CPU —
through read_row_groups_device and iter_device_batches(lists="pack") — equal
to pyarrow's read with no page decoded on the host and the frame's three
counters saying what was framed.
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import parquet_tpu.kernels.device_ops as dops  # x64 on, before any jnp array
import jax.numpy as jnp

from parquet_tpu import FileReader
from parquet_tpu.core.chunk import ChunkWindow, chunk_byte_range
from parquet_tpu.kernels import pipeline
from parquet_tpu.ops.rle_hybrid import _emit_bitpacked, _emit_uvarint, decode_hybrid, prescan_hybrid
from parquet_tpu.utils.native import get_native
from parquet_tpu.utils.trace import decode_trace


def _shipped(width: int) -> int:
    """The rule, written out again: at most two set bits."""
    return next(w for w in range(width, 33) if bin(w).count("1") <= 2)


def _page(kind: str, width: int, n: int, rng) -> bytes:
    """One page of hybrid wire holding at least `n` values: RLE runs only,
    bit-packed runs only (the last group overshoots `n`), or both in turn."""
    top = 1 << width
    out = bytearray()
    at = 0
    while at < n:
        rle = kind == "rle" or (kind == "mixed" and rng.random() < 0.5)
        if rle:
            count = int(rng.integers(1, 700))
            _emit_uvarint(out, count << 1)
            out += int(rng.integers(0, top)).to_bytes((width + 7) // 8, "little")
        else:
            count = 8 * int(rng.integers(1, 90))
            _emit_bitpacked(out, rng.integers(0, top, count, dtype=np.uint64), width)
        at += count
    return bytes(out)


def _tables(pages, width):
    """The staged walk's (page rows, run tables) of `pages` = [(wire, values wanted)]."""
    pending = [("dict", k, prescan_hybrid(wire, n, width), width, n, None) for k, (wire, n) in enumerate(pages)]
    return pipeline._hybrid_tables_of(pending)


def _freeze_both_ways(pages, width, monkeypatch):
    """(native records, NumPy reference records, the numpy decode) of the same pages."""
    lib = get_native()
    if lib is None or not lib.has_hybrid_frame:
        pytest.skip("native hybrid frame not built")
    rows, tables = _tables(pages, width)
    native = pipeline._freeze_hybrid_from_tables(rows, tables)
    with monkeypatch.context() as m:
        m.setattr(lib, "has_hybrid_frame", False)
        reference = pipeline._freeze_hybrid_from_tables(rows, tables)
    return native, reference, np.concatenate([decode_hybrid(wire, n, width) for wire, n in pages])


class TestHybridFrame:
    @pytest.mark.parametrize("kind", ["rle", "bit-packed", "mixed"])
    @pytest.mark.parametrize("width", range(1, 33))
    def test_native_frame_equals_numpy_reference(self, width, kind, monkeypatch):
        rng = np.random.default_rng(width * 3 + len(kind))
        # four pages, each clamped inside its last run; 5,003 values: past one
        # wrap of every plane of the 8,192-slot frame but the 32-bit one
        pages = [(_page(kind, width, n, rng), n) for n in (1500, 3, 2500, 1000)]
        (native,), (reference,), expected = _freeze_both_ways(pages, width, monkeypatch)
        assert native.buf.tobytes() == reference.buf.tobytes()
        assert native[1:] == reference[1:] == (_shipped(width), 8192, 5003)
        assert native.buf.shape == (8192 * _shipped(width) // 32,)
        got = np.asarray(dops.expand_hybrid_device(jnp.asarray(native.buf), native.width, native.n_pad))
        np.testing.assert_array_equal(got[: native.total], expected)
        assert not got[native.total :].any()

    def test_width_0_is_zeros_and_no_upload(self, monkeypatch):
        # a dictionary of one entry: pyarrow writes its pages at width 0
        pages = [(b"\x10", 8), (b"\x03", 5)]  # an RLE run of 8; 5 of a bit-packed group: headers only
        (native,), (reference,), expected = _freeze_both_ways(pages, 0, monkeypatch)
        assert native.buf.shape == reference.buf.shape == (0,) and native[1:] == (0, 1024, 13)
        got = np.asarray(dops.expand_hybrid_device(jnp.asarray(native.buf), 0, native.n_pad))
        assert got.shape == (1024,) and not got.any() and not expected.any()

    @pytest.mark.parametrize("width", [3, 9, 16])
    def test_upload_shape_follows_width_and_pad_only(self, width):
        """Two streams with other run counts, wire sizes and page counts: one
        shape, because the frame's length is a function of (shipped width,
        n_pad); the counters say what differed."""
        rng = np.random.default_rng(width)
        shapes, wires, runs = set(), set(), set()
        for kind, sizes in (("rle", [3000]), ("bit-packed", [700, 1, 1299, 900]), ("mixed", [2049])):
            rows, tables = _tables([(_page(kind, width, n, rng), n) for n in sizes], width)
            with decode_trace() as t:
                (f,) = pipeline._freeze_hybrid_from_tables(rows, tables)
            shapes.add((f.width, f.n_pad, f.buf.shape, f.buf.dtype))
            wires.add(t.counters()["hybrid_wire_bytes"])
            runs.add(len(tables["h_counts"]))
            assert t.counters()["hybrid_values_framed"] == sum(sizes) == f.total
            assert t.counters()["hybrid_frame_bytes"] == 4096 * width // 8 == f.buf.nbytes
            assert t.stages["prepare.hybrid_frame"].bytes == f.buf.nbytes
        assert shapes == {(width, 4096, (4096 * width // 32,), np.dtype(np.uint32))}
        assert len(wires) == 3 and len(runs) == 3

    def test_wire_bytes_count_groups_headers_and_rle_values(self):
        """hybrid_wire_bytes is the stream's size on the wire, to the byte,
        where no page is clamped inside a group: the bit-packed groups, a
        varint header a run, an RLE run's value."""
        width = 12
        rng = np.random.default_rng(1)
        wire = bytearray()
        _emit_uvarint(wire, 300 << 1)  # a two-byte header
        wire += (77).to_bytes(2, "little")
        _emit_bitpacked(wire, rng.integers(0, 1 << width, 8 * 70, dtype=np.uint64), width)  # 70 groups: two bytes
        _emit_uvarint(wire, 9 << 1)
        wire += (5).to_bytes(2, "little")
        _emit_bitpacked(wire, rng.integers(0, 1 << width, 8, dtype=np.uint64), width)
        rows, tables = _tables([(bytes(wire), 300 + 560 + 9 + 8)], width)
        with decode_trace() as t:
            pipeline._freeze_hybrid_from_tables(rows, tables)
        assert t.counters()["hybrid_wire_bytes"] == len(wire) == (2 + 2) + (2 + 840) + (1 + 2) + (1 + 12)


# -- pyarrow files, end to end on the CPU -----------------------------------------

ROWS, GROUP = 60_000, 20_000


def _table():
    """Dictionary-encoded int32 (long RLE runs), optional int64, float32,
    strings, and an int64 whose dictionary grows under the writer (pages at
    two widths, 5 and 9 bits, in the first group: re-packed to one)."""
    rng = np.random.default_rng(38)
    runs = np.repeat(rng.integers(0, 90, ROWS // 500), 500).astype(np.int32)
    for at in range(0, ROWS, 3000):  # and bit-packed stretches between
        runs[at : at + 700] = rng.integers(0, 90, 700)
    grow = np.concatenate([rng.integers(0, 20, GROUP // 2), rng.integers(0, 400, ROWS - GROUP // 2)]).astype(np.int64)
    return pa.table({
        "runs": pa.array(runs),
        "opt": pa.array(rng.integers(-(1 << 40), 1 << 40, 3000)[rng.integers(0, 3000, ROWS)], mask=rng.random(ROWS) < 0.2),
        "f32": pa.array(rng.standard_normal(700).astype(np.float32)[rng.integers(0, 700, ROWS)]),
        "s": pa.array([f"zone-{i:03d}" for i in rng.integers(0, 265, ROWS)]),
        "grow": pa.array(grow),
    })


@pytest.fixture(scope="module")
def dict_file(tmp_path_factory):
    t = _table()
    path = str(tmp_path_factory.mktemp("hybrid") / "dict.parquet")
    pq.write_table(t, path, row_group_size=GROUP, data_page_size=8 << 10, use_dictionary=True)
    meta = pq.ParquetFile(path).metadata
    assert all("RLE_DICTIONARY" in meta.row_group(0).column(c).encodings for c in range(t.num_columns))
    return t, path


def _frozen_of(path):
    """Every chunk's FrozenHybrid records, prepared and not dispatched."""
    out = {}
    with FileReader(path) as r:
        for g in range(r.num_row_groups):
            for p, cc, column in r._selected_chunks(g):
                offset, total = chunk_byte_range(cc)
                plan = pipeline.prepare_chunk_plan(ChunkWindow(r._fetch_chunk(offset, total), offset), cc, column)
                out[(g, ".".join(p))] = plan.frozen_hybrid
    return out


def test_pyarrow_dictionary_file_through_the_device_reader(dict_file):
    t, path = dict_file
    frozen = _frozen_of(path)
    assert all(len(f) == 1 for f in frozen.values())
    # the widths on the link: 90 int32 values ship at 7 -> 8 bits, 3,000 at
    # 12, 700 at 10, 265 strings at 9, and the growing dictionary's chunk at
    # one width though its pages were written at two
    assert {c: {f[0].width for (_g, n), f in frozen.items() if n == c} for c in t.column_names} == {
        "runs": {8}, "opt": {12}, "f32": {10}, "s": {9}, "grow": {9},
    }
    with decode_trace() as tr:
        with FileReader(path) as r:
            groups = r.read_row_groups_device()
    assert len(groups) == ROWS // GROUP
    for name in t.column_names:
        want = t.column(name).combine_chunks()
        cols = [g[(name,)] for g in groups]
        assert sum(c.num_values for c in cols) == ROWS
        if name == "s":
            got = []
            for c in cols:
                d = c.dictionary
                entries = [bytes(d.data[a:b]).decode() for a, b in zip(d.offsets[:-1], d.offsets[1:])]
                got += [entries[i] for i in np.asarray(c.indices)]
            assert got == want.to_pylist()
            continue
        got = np.concatenate([np.asarray(c.values) for c in cols])
        dense = want.drop_null().to_numpy()
        assert got.dtype == dense.dtype and got.tobytes() == dense.tobytes(), name
        if name == "opt":
            levels = np.concatenate([np.asarray(c.def_levels) for c in cols])
            np.testing.assert_array_equal(levels == 1, np.asarray(want.is_valid()))
    counters = tr.counters()
    assert "host_decoded_pages" not in counters
    assert counters["hybrid_pages_repacked"] > 0  # the growing dictionary's narrower pages
    assert counters["hybrid_values_framed"] == sum(f[0].total for f in frozen.values())
    assert counters["hybrid_values_framed"] == 5 * ROWS - t.column("opt").null_count
    assert counters["hybrid_frame_bytes"] == sum(f[0].n_pad * f[0].width // 8 for f in frozen.values())
    # a group's 20,000 slots pad to 32,768; the optional column's ~16,000 non-null ones to 16,384
    assert counters["hybrid_frame_bytes"] == 3 * (32_768 * (8 + 10 + 9 + 9) + 16_384 * 12) // 8
    assert tr.stages["prepare.hybrid_frame"].bytes == counters["hybrid_frame_bytes"]
    assert tr.stages["prepare.hybrid_frame"].calls == len(frozen)
    # long RLE runs ship as their indices: the one column whose frame is larger than its wire
    assert 0 < counters["hybrid_wire_bytes"] < counters["hybrid_frame_bytes"]


@pytest.mark.parametrize("element", [pa.int32(), pa.int64()], ids=["int32", "int64"])
def test_pyarrow_dictionary_lists_through_the_packer(tmp_path, element):
    """Dictionary-encoded token ids with long stretches of one id (RLE runs
    between the bit-packed ones), null and empty documents, small pages:
    iter_device_batches(lists="pack") equals the reference's pack of
    pyarrow's read, and every id was framed, none decoded on the host."""
    from test_pack_sequences import same_as_reference, write

    rng = np.random.default_rng(7)
    groups = []
    for g in range(3):
        docs = [rng.integers(0, 3000, int(k)).tolist() for k in rng.integers(1, 200, 150)]
        for i in range(0, len(docs), 9):
            docs[i] = [int(rng.integers(0, 3000))] * int(rng.integers(50, 400))  # one id, over and over
        docs[5], docs[40] = None, []
        groups.append(docs)
    path = write(tmp_path / f"t{element}.parquet", groups, element, data_page_size=4 << 10, use_dictionary=True)
    tokens = sum(len(d) for docs in groups for d in docs if d)
    with decode_trace() as tr:
        same_as_reference(path, 4, 256)
    counters = tr.counters()
    assert "host_decoded_pages" not in counters
    assert counters["hybrid_values_framed"] == tokens
    # one chunk a group, 12 bits a slot, each group's ids inside one bucket
    assert counters["hybrid_frame_bytes"] == sum(
        dops._bucket(sum(len(d) for d in docs if d)) * 12 // 8 for docs in groups
    )
