"""Sequence packing: FileReader.iter_device_batches(lists="pack", seq_len=...)
against the plain reference, bit for bit.

The reference is the benchmark's own file, benchmark/lib/reference_packed.py
(numpy + pyarrow; it imports neither the program nor jax), loaded by path:
there is no second copy. Everything here is small and runs on the CPU; what
the chip adds — that the programs compile there and that the same comparison
holds at the cell's size — is benchmark/run.py --workload tok-8k.packed.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import parquet_tpu.kernels.device_ops as dops  # x64 on, before any jnp array
import jax

from parquet_tpu import FileReader, PackedBatch
from parquet_tpu.meta.file_meta import ParquetFileError
from parquet_tpu.utils import metrics
from parquet_tpu.utils.trace import decode_trace

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location(
    "reference_packed", ROOT / "benchmark" / "lib" / "reference_packed.py")
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

LEAF = "input_ids.list.element"
ENCODINGS = {
    "dictionary": dict(use_dictionary=True),
    "plain": dict(use_dictionary=False, column_encoding={LEAF: "PLAIN"}),
    "delta": dict(use_dictionary=False, column_encoding={LEAF: "DELTA_BINARY_PACKED"}),
}


def documents(seed: int, n: int, mean: float = 60.0, vocab: int = 5000, nulls: float = 0.03) -> list:
    """`n` documents of heavy-tailed lengths; a few of them null, a few empty."""
    rng = np.random.default_rng(seed)
    lengths = np.minimum(rng.lognormal(np.log(mean), 1.0, n).astype(int), 40 * int(mean))
    docs = [rng.integers(0, vocab, k).tolist() for k in lengths]
    for i in np.flatnonzero(rng.random(n) < nulls):
        docs[i] = None if i % 2 else []
    return docs


def write(path, groups: list, element=pa.int32(), **options) -> str:
    """One row group per entry of `groups` (each a list of documents)."""
    schema = pa.schema([("input_ids", pa.list_(element))])
    with pq.ParquetWriter(str(path), schema, **options) as w:
        for docs in groups:
            w.write_table(pa.table({"input_ids": pa.array(docs, type=pa.list_(element))}, schema=schema))
    return str(path)


def read_packed(path: str, batch: int, seq_len: int, **kw) -> list:
    with FileReader(path) as r:
        return list(r.iter_device_batches(batch, columns=["input_ids"], lists="pack", seq_len=seq_len,
                                          drop_remainder=False, **kw))


def same_as_reference(path: str, batch: int, seq_len: int, got: list | None = None, drop_remainder: bool = False):
    """Every batch against the reference's pack of pyarrow's read of the same
    file: count, shapes, dtype, residency, values."""
    want = reference.pack(pq.read_table(path)["input_ids"], seq_len)
    n_seq = want[0].shape[0]
    if got is None:
        got = read_packed(path, batch, seq_len)
    n_batches = n_seq // batch if drop_remainder else -(-n_seq // batch)
    assert len(got) == n_batches, (len(got), n_seq, batch)
    for k, b in enumerate(got):
        assert isinstance(b, PackedBatch)
        rows = min(batch, n_seq - k * batch)
        for name, a, w in zip(PackedBatch._fields, b, want):
            assert isinstance(a, jax.Array) and a.dtype == np.int32 and a.shape == (rows, seq_len), (k, name, a)
            assert np.array_equal(np.asarray(a), w[k * batch : k * batch + rows]), f"batch {k}: {name} differs"
    return want


# -- every way the ids may be written ------------------------------------------


@pytest.mark.parametrize("element", [pa.int32(), pa.int64()], ids=["int32", "int64"])
@pytest.mark.parametrize("compression", ["snappy", "none"])
@pytest.mark.parametrize("page", ["1.0", "2.0"], ids=["v1", "v2"])
@pytest.mark.parametrize("encoding", sorted(ENCODINGS))
def test_every_writing_of_the_ids(tmp_path, encoding, page, compression, element):
    groups = [documents(11 + k, n) for k, n in enumerate((150, 90, 210))]
    path = write(tmp_path / "t.parquet", groups, element, compression=compression,
                 data_page_version=page, data_page_size=4 << 10, **ENCODINGS[encoding])
    meta = pq.ParquetFile(path).metadata.row_group(0).column(0)
    assert ("DELTA_BINARY_PACKED" in meta.encodings) == (encoding == "delta")
    assert any("DICTIONARY" in e for e in meta.encodings) == (encoding == "dictionary")
    before = metrics.snapshot().get('events_total{event="host_decoded_pages"}', 0)
    same_as_reference(path, 4, 128)
    assert metrics.snapshot().get('events_total{event="host_decoded_pages"}', 0) == before


@pytest.mark.parametrize("batch", [1, 4, 64])
@pytest.mark.parametrize("seq_len", [16, 128, 8192])
def test_every_batch_shape(tmp_path, seq_len, batch):
    mean = {16: 20, 128: 60, 8192: 900}[seq_len]
    groups = [documents(21 + k, n, mean=mean) for k, n in enumerate((120, 75, 160))]
    path = write(tmp_path / "t.parquet", groups)
    same_as_reference(path, batch, seq_len)


# -- where documents and sequences meet ----------------------------------------


def _doc(n, start=1):
    return list(range(start, start + n))


LAYOUTS = {
    # row groups whose token totals are all different
    "groups_all_different": [documents(31, 40), documents(32, 160), documents(33, 7), documents(34, 95)],
    # one document longer than three sequences (seq_len 32), between short ones
    "document_over_three_sequences": [[_doc(5), _doc(117, 100), _doc(9)], [_doc(40)]],
    # a document that ends exactly on a sequence's last slot: the next one opens the next sequence
    "document_ends_on_last_slot": [[_doc(20), _doc(12, 50), _doc(7, 90)], [_doc(25), _doc(64, 7)]],
    # a document that starts on a sequence's last slot
    "document_starts_on_last_slot": [[_doc(31), _doc(10, 70)]],
    "empty_and_null_first": [[None, [], _doc(10)], [_doc(50)]],
    "empty_and_null_last": [[_doc(10), _doc(30)], [_doc(5), [], None]],
    "empty_and_null_adjacent": [[_doc(3), None, None, [], [], None, _doc(40), [], _doc(2)]],
    "a_group_of_nothing_but_nulls": [[_doc(12)], [None, [], None], [_doc(70)]],
    "one_document": [[_doc(45)]],
    "one_document_of_one_token": [[[7]]],
    "shorter_than_one_sequence": [[_doc(3), _doc(4), _doc(5)]],
    "exactly_one_batch": [[_doc(32 * 4)]],
    "exactly_one_sequence": [[_doc(10), _doc(22)]],
    "nothing_but_nulls": [[None, [], None]],
    "tokens_that_are_zero_and_negative": [[[0, 0, -1, -(2**31), 2**31 - 1], [0], [0, 0]]],
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_where_documents_and_sequences_meet(tmp_path, layout):
    path = write(tmp_path / "t.parquet", LAYOUTS[layout])
    tokens, segment_ids, _ = same_as_reference(path, 4, 32)
    if layout == "document_over_three_sequences":
        # the long document (slots 5..121) fills sequences 1 and 2 whole: one piece each
        assert (segment_ids[1:3] == 1).all() and segment_ids[3, 25:27].tolist() == [1, 2]
    if layout == "nothing_but_nulls":
        assert tokens.shape == (0, 32)


@pytest.mark.parametrize("layout", ["groups_all_different", "exactly_one_batch", "shorter_than_one_sequence"])
def test_drop_remainder_drops_the_short_batch_only(tmp_path, layout):
    path = write(tmp_path / "t.parquet", LAYOUTS[layout])
    with FileReader(path) as r:
        got = list(r.iter_device_batches(4, columns=["input_ids"], lists="pack", seq_len=32))
    same_as_reference(path, 4, 32, got, drop_remainder=True)


def test_int64_ids_arrive_as_their_low_32_bits(tmp_path):
    path = write(tmp_path / "t.parquet", [[[1, 2**32 + 5, -3], [2**40]]], pa.int64())
    (b,) = read_packed(path, 2, 8)
    assert np.asarray(b.tokens)[0, :4].tolist() == [1, 5, -3, 0]
    same_as_reference(path, 2, 8)


def test_the_staged_walk_packs_the_same(tmp_path, staged_walk):
    path = write(tmp_path / "t.parquet", [documents(41, 120), documents(42, 60)])
    with staged_walk():
        same_as_reference(path, 4, 64)


def test_under_a_memory_ceiling(tmp_path):
    path = write(tmp_path / "t.parquet", [documents(43, 120), documents(44, 60)])
    with FileReader(path, max_memory=64 << 20) as r:
        got = list(r.iter_device_batches(4, columns=["input_ids"], lists="pack", seq_len=64, drop_remainder=False))
    same_as_reference(path, 4, 64, got)


def test_batches_lay_out_over_a_mesh(tmp_path):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    path = write(tmp_path / "t.parquet", [documents(45, 300)])
    sharding = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("data",)), P("data"))
    got = read_packed(path, 8, 64, sharding=sharding)
    assert got[0].tokens.sharding.is_equivalent_to(sharding, 2)
    same_as_reference(path, 8, 64, got)


# -- the refusals ----------------------------------------------------------------


def test_a_null_element_is_refused(tmp_path):
    path = write(tmp_path / "t.parquet", [[_doc(5), [1, None, 3], _doc(4)]])
    with FileReader(path) as r:
        with pytest.raises(ParquetFileError, match="null elements inside lists"):
            list(r.iter_device_batches(2, columns=["input_ids"], lists="pack", seq_len=8))


def test_two_list_levels_are_refused_at_the_call(tmp_path):
    t = pa.table({"input_ids": pa.array([[[1, 2], [3]], [[4]]], type=pa.list_(pa.list_(pa.int32())))})
    pq.write_table(t, tmp_path / "t.parquet")
    with FileReader(str(tmp_path / "t.parquet")) as r:
        with pytest.raises(ParquetFileError, match="2 repetition levels"):
            r.iter_device_batches(2, columns=["input_ids"], lists="pack", seq_len=8)  # EAGER


def test_other_elements_than_integers_are_refused(tmp_path):
    t = pa.table({"x": pa.array([[1.5], [2.5]], type=pa.list_(pa.float32())), "flat": pa.array([1, 2])})
    pq.write_table(t, tmp_path / "t.parquet")
    with FileReader(str(tmp_path / "t.parquet")) as r:
        with pytest.raises(ParquetFileError, match="FLOAT elements"):
            r.iter_device_batches(2, columns=["x"], lists="pack", seq_len=8)
        with pytest.raises(ParquetFileError, match="0 repetition levels"):
            r.iter_device_batches(2, columns=["flat"], lists="pack", seq_len=8)
        with pytest.raises(ValueError, match="ONE leaf; 2 are selected"):
            r.iter_device_batches(2, lists="pack", seq_len=8)


@pytest.mark.parametrize("kw,match", [
    (dict(lists="pack"), "seq_len goes with"),
    (dict(lists="pad", max_list_len=4, seq_len=8), "seq_len goes with"),
    (dict(lists="pack", seq_len=0), "positive seq_len"),
    (dict(lists="pack", seq_len=8, nullable="mask"), "takes no nullable"),
    (dict(lists="pack", seq_len=8, max_list_len=4), "takes no max_list_len"),
    (dict(lists="pack", seq_len=8, filters=[("input_ids", "==", 1)]), "takes no filters"),
    (dict(lists="pack", seq_len=8, doubles="bits"), "takes no doubles"),
    (dict(lists="packed", seq_len=8), 'must be "error", "pad" or "pack"'),
])
def test_arguments_that_do_not_go_together(tmp_path, kw, match):
    path = write(tmp_path / "t.parquet", [[_doc(5)]])
    with FileReader(path) as r:
        with pytest.raises(ValueError, match=match):
            r.iter_device_batches(2, columns=["input_ids"], **kw)


# -- what is compiled, and what is counted -----------------------------------------


class Compiles:
    """One event per program jax asks the backend to compile."""

    def __init__(self):
        from jax import monitoring

        self.names = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, seconds, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.names.append(kw.get("fun_name", "?"))


def test_a_second_file_of_other_lengths_compiles_nothing(tmp_path):
    """No compiled shape follows a row group's element, document or
    dictionary-entry count: a file whose groups all differ from the first
    file's (within the same power-of-two buckets) runs on the first file's
    programs, the short last batch included."""
    first = write(tmp_path / "a.parquet", [documents(51, 300), documents(52, 280), documents(53, 330)])
    second = write(tmp_path / "b.parquet", [documents(54, 320), documents(55, 270), documents(56, 290),
                                           documents(57, 310)])
    totals = lambda p: [pq.ParquetFile(p).metadata.row_group(g).column(0).num_values for g in range(3)]  # noqa: E731
    assert len(set(totals(first) + totals(second))) == 6
    seen = Compiles()
    got = read_packed(first, 7, 256)
    assert {"jit(pack_append_device)", "jit(pack_emit_device)"} <= set(seen.names)
    mark = len(seen.names)
    again = read_packed(second, 7, 256)
    assert seen.names[mark:] == []
    # both end in a short batch, of another length each, and neither length has a program
    assert (got[-1].tokens.shape[0], again[-1].tokens.shape[0]) == (5, 6)
    same_as_reference(first, 7, 256, got)
    same_as_reference(second, 7, 256, again)


def _group_of(seed: int, tokens: int, vocab: int = 60000) -> list:
    """Documents of 500 ids (the last one shorter) that sum to `tokens`."""
    ids = np.random.default_rng(seed).integers(0, vocab, tokens)
    return [ids[lo:lo + 500].tolist() for lo in range(0, tokens, 500)]


def test_counts_on_both_sides_of_a_bucket_compile_nothing(tmp_path):
    """Within one bucket of values, a group's run count and payload length are
    data too: pyarrow writes bit-packed runs of 504 indices, so groups of
    58,000 and of 65,536 tokens hold 116 and 131 runs and 29,001 and 32,769
    payload words — on both sides of a 128-run and of a 2^15-word bucket, as the
    token corpus's groups of up to 2^20 tokens lie on both sides of 2,048 and
    2^19. The hybrid frame ships neither: an upload's length is a function of
    (shipped width, n_pad), for the exact delivery as for the padded one, so
    both groups run on one set of programs."""
    from parquet_tpu.core.chunk import ChunkWindow, chunk_byte_range
    from parquet_tpu.kernels.pipeline import prepare_chunk_plan
    from parquet_tpu.utils.trace import decode_trace

    first = write(tmp_path / "a.parquet", [_group_of(1, 1 << 16), _group_of(4, 1 << 16)])
    second = write(tmp_path / "b.parquet", [_group_of(2, 58000), _group_of(3, 65000)])

    def uploads(path, **kw):
        out = []
        with FileReader(path) as r:
            for g in range(r.num_row_groups):
                for _p, cc, leaf in r._selected_chunks(g, ["input_ids"]):
                    offset, total = chunk_byte_range(cc)
                    (f,) = prepare_chunk_plan(ChunkWindow(r._fetch_chunk(offset, total), offset), cc, leaf,
                                              **kw).frozen_hybrid
                    out.append((f.width, f.n_pad, len(f.buf)))
        return out

    with decode_trace() as tr:
        exact = uploads(first) + uploads(second)
    assert set(exact) == {(16, 1 << 16, (1 << 16) * 16 // 32)}, "one index width, one bucket of values, one length"
    # what the frames replaced does straddle: the wire's bytes are another number a group
    assert tr.counters()["hybrid_frame_bytes"] == 4 * (1 << 16) * 16 // 8
    assert tr.counters()["hybrid_values_framed"] == 2 * (1 << 16) + 58000 + 65000
    assert tr.counters()["hybrid_wire_bytes"] < tr.counters()["hybrid_frame_bytes"]
    assert set(uploads(first, list_lengths=True) + uploads(second, list_lengths=True)) == set(exact)
    seen = Compiles()
    got = read_packed(first, 2, 8192)
    mark = len(seen.names)
    # the recorder does see this read's programs (the expansion's own, one a
    # (width, n_pad), may have been compiled by an earlier test of the process)
    assert "jit(pack_append_device)" in seen.names
    again = read_packed(second, 2, 8192)
    assert seen.names[mark:] == []
    same_as_reference(first, 2, 8192, got)
    same_as_reference(second, 2, 8192, again)


def test_the_exact_fallback_is_counted(tmp_path, monkeypatch):
    """A chunk whose pages do not fit one device batch is delivered exactly
    and padded after: right, but a program a count, so an event says that it
    happened (the packed cell pins it at 0); a chunk of one batch leaves it."""
    import parquet_tpu.kernels.pipeline as pipeline

    name = 'events_total{event="padded_delivery_exact_chunks"}'
    path = write(tmp_path / "t.parquet", [documents(71, 200), documents(72, 240)], data_page_size=2 << 10)
    before = metrics.snapshot().get(name, 0)
    same_as_reference(path, 4, 128)
    assert metrics.snapshot().get(name, 0) == before
    monkeypatch.setattr(pipeline, "_BATCH_BITS_CAP", 40_000)
    same_as_reference(path, 4, 128)
    assert metrics.snapshot().get(name, 0) == before + 2


def test_counters_and_stages(tmp_path):
    docs = [_doc(20), None, _doc(30, 40), [], _doc(7, 90)]  # 57 tokens: 4 sequences of 16, 7 slots of padding
    path = write(tmp_path / "t.parquet", [docs[:3], docs[3:]])
    name = 'events_total{event="%s"}'
    keys = ("packed_tokens", "packed_sequences", "packed_documents", "packed_documents_cut",
            "packed_padding_tokens", "list_structure_upload_bytes")
    before = metrics.snapshot()
    with decode_trace() as tr:
        read_packed(path, 2, 16)
    after = metrics.snapshot()
    rise = {k: after.get(name % k, 0) - before.get(name % k, 0) for k in keys}
    # the documents of 20 and 30 tokens each cross a sequence's end (slots 0-19, 20-49); the last (50-56) does not
    assert rise == {"packed_tokens": 57, "packed_sequences": 4, "packed_documents": 5, "packed_documents_cut": 2,
                    "packed_padding_tokens": 7, "list_structure_upload_bytes": 2 * 4 * 4096}
    assert {k: tr.stages[k].calls for k in keys} == rise
    assert tr.stages["deliver.pack"].calls >= 3 and tr.stages["deliver.pack"].seconds <= tr.stages["deliver"].seconds
    assert tr.stages["prepare.levels.lengths"].calls == 2


def test_lengths_are_derived_once_for_pad_and_pack():
    from parquet_tpu.ops.levels import LevelError, list_lengths

    #           doc 0      null  empty  doc 3
    rep = np.array([0, 1, 1, 0, 0, 0, 1], dtype=np.uint16)
    dfl = np.array([3, 3, 3, 0, 1, 3, 3], dtype=np.uint16)
    lengths, elements = list_lengths(rep, dfl, 3, True)
    assert lengths.tolist() == [3, 0, 0, 2] and lengths.dtype == np.int32 and elements == 5
    assert list_lengths(rep, None, 0, False)[0].tolist() == [3, 1, 1, 2]
    assert list_lengths(rep[:0], dfl[:0], 3, True) == (pytest.approx(np.zeros(0)), 0)
    with pytest.raises(LevelError, match="null elements"):
        list_lengths(rep, np.array([3, 2, 3, 0, 1, 3, 3], dtype=np.uint16), 3, True)
