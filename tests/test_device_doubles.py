"""DOUBLE on the device path under `doubles=` ("bits", "float32"), and the one
shipping width of a dictionary chunk's index stream.

A CPU holds float64 exactly, so a CPU run cannot see what a TPU would get
wrong by value; what it can guard is the FORM of the programs: the narrowing
kernel equals numpy on the bit patterns where narrowing goes wrong first, and
no program that runs under doubles= holds a float64 value at all
(TestNoFloat64 walks their jaxprs). The chip's own proof is chip_smoke.py's
doubles leg."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import parquet_tpu.kernels.device_ops as dops  # x64 on, before any jnp array
import jax
import jax.numpy as jnp

from parquet_tpu import FileReader
from parquet_tpu.kernels import pipeline
from parquet_tpu.kernels.pipeline import DeviceDoubleError, prepare_chunk_plan
from parquet_tpu.testing.doubles import adversarial_doubles, same_double_form
from parquet_tpu.utils.trace import decode_trace

FORMS = ("bits", "float32")
ROWS = 60_000
GROUP = 24_000


def _table(seed: int = 5):
    """money: few distinct values (dictionary); wild: every family of
    adversarial bit patterns (NaN, inf, overflow, subnormals); key: rows."""
    rng = np.random.default_rng(seed)
    money = np.round(rng.gamma(2.0, 9.0, ROWS) * np.linspace(1, 60, ROWS), 2)
    wild = np.resize(np.concatenate(list(adversarial_doubles(seed, 2048).values())), ROWS)
    rng.shuffle(wild)
    return pa.table({
        "money": pa.array(money, mask=rng.random(ROWS) < 0.05),
        "wild": pa.array(wild.view(np.float64), mask=rng.random(ROWS) < 0.03),
        "key": pa.array(np.arange(ROWS, dtype=np.int64)),
    })


# how each chunk shape is written; "mixed": the dictionary outgrows its page
# limit mid-chunk and the writer falls back to PLAIN (pyarrow's behaviour at
# 1 MiB in real files)
ENCODINGS = {
    "dictionary": dict(use_dictionary=["money", "wild"], data_page_size=16 << 10),
    "plain": dict(use_dictionary=False, column_encoding={"money": "PLAIN", "wild": "PLAIN", "key": "PLAIN"}),
    "byte_stream_split": dict(use_dictionary=False, column_encoding={
        "money": "BYTE_STREAM_SPLIT", "wild": "BYTE_STREAM_SPLIT", "key": "PLAIN"}),
    "mixed": dict(use_dictionary=["money", "wild"], dictionary_pagesize_limit=24 << 10, data_page_size=8 << 10),
}


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("doubles")
    t = _table()
    paths = {}
    for name, kw in ENCODINGS.items():
        paths[name] = str(d / f"{name}.parquet")
        pq.write_table(t, paths[name], row_group_size=GROUP, compression="snappy", **kw)
    return t, paths


class TestNarrowKernel:
    @pytest.mark.parametrize("family", sorted(adversarial_doubles()))
    def test_equals_numpy_astype(self, family):
        bits = adversarial_doubles(11)[family]
        got = np.asarray(dops.double_narrow_device(jnp.asarray(bits)))
        assert got.dtype == np.uint32
        assert same_double_form(got.view(np.float32), bits.view(np.float64), "float32")

    def test_nan_stays_quiet_nan_with_sign(self):
        bits = np.array([0x7FF0000000000001, 0xFFF0000000000001], dtype=np.uint64)
        got = np.asarray(dops.double_narrow_device(jnp.asarray(bits)))
        assert np.isnan(got.view(np.float32)).all() and (got >> 31).tolist() == [0, 1]

    def test_traces_under_its_scope(self):
        hlo = dops.double_narrow_device.lower(jnp.zeros(8, jnp.uint64)).as_text(debug_info=True)
        assert "pqt.double_narrow" in hlo


class TestDelivery:
    @pytest.mark.parametrize("form", FORMS)
    @pytest.mark.parametrize("encoding", sorted(ENCODINGS))
    def test_read_row_groups_device(self, files, encoding, form):
        table, paths = files
        with decode_trace() as tr:
            with FileReader(paths[encoding]) as r:
                groups = r.read_row_groups_device(doubles=form)
        off = 0
        for g in groups:
            n = g[("key",)].num_values
            for c in ("money", "wild"):
                dc, col = g[(c,)], table[c].slice(off, n).combine_chunks()
                assert dc.double_form == form
                assert np.array_equal(np.asarray(dc.def_levels) == 1, col.is_valid().to_numpy(zero_copy_only=False))
                assert same_double_form(dc.values, col.drop_null().to_numpy(), form), (c, encoding)
            assert g[("key",)].double_form is None and g[("key",)].values.dtype == jnp.int64
            off += n
        assert off == ROWS
        chunks = 2 * len(groups)
        assert tr.stages[f"device_double_chunks_{form}"].calls == chunks
        if form == "float32" and encoding in ("plain", "byte_stream_split", "mixed"):
            assert tr.stages["double_pages_narrowed_device"].calls > 0
        if form == "float32" and encoding in ("dictionary", "mixed"):
            assert tr.stages["double_dict_narrowed_host"].calls > 0
        if encoding == "mixed":  # dictionary and PLAIN pages in one chunk, merged on the device
            assert "host_decoded_pages" not in tr.stages

    @pytest.mark.parametrize("form", FORMS)
    @pytest.mark.parametrize("encoding", sorted(ENCODINGS))
    def test_iter_device_batches(self, files, encoding, form):
        table, paths = files
        batch = 8192
        with FileReader(paths[encoding]) as r:
            it = r.iter_device_batches(batch, columns=["money", "wild", "key"], nullable="mask", doubles=form)
            got = [next(it) for _ in range(4)]  # crosses a row-group boundary
            it.close()
        for k, b in enumerate(got):
            for c in ("money", "wild"):
                col = table[c].slice(k * batch, batch).combine_chunks()
                valid = col.is_valid().to_numpy(zero_copy_only=False)
                assert np.array_equal(np.asarray(b[(c,)].mask), valid)
                values = np.asarray(b[(c,)].values)
                assert values.dtype == (np.uint64 if form == "bits" else np.float32)
                assert same_double_form(values[valid], col.drop_null().to_numpy(), form)
                assert not values[~valid].view(np.uint8).any()  # nulls zero-filled on the device

    def test_unknown_form_is_refused(self, files):
        _, paths = files
        with FileReader(paths["plain"]) as r:
            with pytest.raises(ValueError, match="doubles"):
                r.read_row_groups_device(doubles="float16")
            with pytest.raises(ValueError, match="doubles"):
                r.iter_device_batches(1024, doubles="f32")

    def test_filter_on_a_delivered_form_takes_the_host_engine(self, files):
        table, paths = files
        with decode_trace() as tr:
            with FileReader(paths["dictionary"]) as r:
                cols, mask = r.read_row_group_device(0, ["money"], filters=[("money", ">", 100.0)], doubles="float32")
        want = table["money"].slice(0, GROUP).to_numpy(zero_copy_only=False) > 100.0  # null -> nan -> False
        assert np.array_equal(np.asarray(mask), want)
        assert tr.stages["device_filter_declined"].calls == 1 and cols[("money",)].double_form == "float32"


class TestDefaultStillRefuses:
    def test_typed_error_names_the_forms(self, files, monkeypatch):
        _, paths = files
        monkeypatch.setattr(pipeline, "_platform_holds_f64", lambda platform: False)
        with FileReader(paths["dictionary"]) as r:
            with pytest.raises(DeviceDoubleError) as e:
                r.read_row_groups_device(columns=["money"])
            assert 'doubles="bits"' in str(e.value) and 'doubles="float32"' in str(e.value)
            # asked for by form, the same platform is served
            (g, *_) = r.read_row_groups_device(columns=["money"], doubles="bits")
            assert g[("money",)].values.dtype == jnp.uint64

    def test_default_on_a_platform_that_holds_f64_is_unchanged(self, files):
        table, paths = files
        with FileReader(paths["mixed"]) as r:
            (g, *_) = r.read_row_groups_device(columns=["money"])
        dc = g[("money",)]
        assert dc.double_form is None and dc.values.dtype == jnp.float64
        want = table["money"].slice(0, GROUP).drop_null().to_numpy()
        assert np.array_equal(np.asarray(dc.values).view(np.uint64), want.view(np.uint64))


def _avals(jaxpr):
    """Every abstract value in a jaxpr, sub-jaxprs (pjit, cond, scan) included."""
    for v in (*jaxpr.invars, *jaxpr.constvars, *jaxpr.outvars):
        yield v.aval
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            yield v.aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


class TestNoFloat64:
    """Every program jax lowers while a file is read under doubles= — the
    jitted kernels and the eager one-primitive programs alike (uploads'
    bitcasts, slices, concatenations, astype) — is caught at the lowering
    seam and its jaxpr walked: one float64 value anywhere fails."""

    @pytest.mark.parametrize("form", FORMS)
    def test_programs_hold_no_float64(self, files, form, monkeypatch):
        from jax._src.interpreters import mlir

        _, paths = files
        seen = []
        real = mlir.lower_jaxpr_to_module

        def spy(module_name, jaxpr, *a, **kw):
            seen.append((module_name, jaxpr))
            return real(module_name, jaxpr, *a, **kw)

        monkeypatch.setattr(mlir, "lower_jaxpr_to_module", spy)
        jax.clear_caches()  # a program compiled by an earlier test would not be lowered again
        try:
            for encoding in sorted(ENCODINGS):
                with FileReader(paths[encoding]) as r:
                    groups = r.read_row_groups_device(columns=["money", "wild"], doubles=form)
                    jax.block_until_ready([dc.values for g in groups for dc in g.values()])
                    it = r.iter_device_batches(4096, columns=["money", "wild"], nullable="mask", doubles=form)
                    next(it)
                    it.close()
        finally:
            jax.clear_caches()
        names = {n for n, _ in seen}
        # the seam is jax-internal: if it moves, fail loudly rather than pass on nothing
        assert {"jit(expand_hybrid_device)", "jit(dict_gather_device)"} <= names, names
        assert ("jit(double_narrow_device)" in names) == (form == "float32")
        bad = sorted({(n, str(a)) for n, j in seen for a in _avals(j.jaxpr)
                      if getattr(a, "dtype", None) == jnp.float64})
        assert not bad, f"float64 values in programs run under doubles={form!r}: {bad}"

    def test_packing_programs_hold_no_float_at_all(self, tmp_path, monkeypatch):
        """Sequence packing (lists="pack") is integer arithmetic only: the
        same seam, walked for any floating dtype, over a dictionary and a
        PLAIN writing of the ids."""
        from jax._src.interpreters import mlir

        rng = np.random.default_rng(3)
        docs = pa.array([rng.integers(0, 900, k).tolist() for k in rng.integers(0, 300, 400)],
                        type=pa.list_(pa.int32()))
        seen = []
        real = mlir.lower_jaxpr_to_module

        def spy(module_name, jaxpr, *a, **kw):
            seen.append((module_name, jaxpr))
            return real(module_name, jaxpr, *a, **kw)

        monkeypatch.setattr(mlir, "lower_jaxpr_to_module", spy)
        jax.clear_caches()
        try:
            for name, use_dictionary in (("d.parquet", True), ("p.parquet", False)):
                pq.write_table(pa.table({"input_ids": docs}), tmp_path / name, use_dictionary=use_dictionary,
                               row_group_size=150)
                with FileReader(str(tmp_path / name)) as r:
                    jax.block_until_ready(list(r.iter_device_batches(
                        4, columns=["input_ids"], lists="pack", seq_len=128, drop_remainder=False)))
        finally:
            jax.clear_caches()
        names = {n for n, _ in seen}
        assert {"jit(pack_append_device)", "jit(pack_emit_device)", "jit(pack_carry_device)",
                "jit(expand_hybrid_device)", "jit(dict_gather_device)"} <= names, names
        # the dense dictionary lookup contracts a 0/1 one-hot with byte planes
        # on the MXU: bfloat16 operands and a float32 accumulator that hold
        # small integers exactly (tests/test_dict_lookup.py), nothing wider
        exact = {"jit(dict_gather_device)": (jnp.bfloat16, jnp.float32)}
        bad = sorted({(n, str(a)) for n, j in seen for a in _avals(j.jaxpr)
                      if jnp.issubdtype(getattr(a, "dtype", jnp.int32), jnp.floating)
                      and a.dtype not in exact.get(n, ())})
        assert not bad, f"floating values in the packing programs: {bad}"

    def test_query_programs_hold_no_float_at_all(self, tmp_path, monkeypatch):
        """The device query lane answers TPC-H Q6 in integers only: every
        program lowered while a unit decodes DATE and DECIMAL columns, masks
        them (pqt.query_mask: compares on values, a dictionary's verdict
        lifted through its indices) and reduces the decimal product
        (pqt.expr_agg) is walked for any floating dtype."""
        import importlib.util
        import json
        import sys
        from pathlib import Path

        from jax._src.interpreters import mlir

        from parquet_tpu.serve.protocol import parse_query_request
        from parquet_tpu.serve.query_device import device_unit_partial

        bench = Path(__file__).resolve().parents[1] / "benchmark"
        sys.path.insert(0, str(bench / "lib"))  # the kind imports reference_tpch as the corpus's workers do
        spec = importlib.util.spec_from_file_location("bench_corpora_tpch_lineitem", bench / "corpora" / "tpch_lineitem.py")
        kind = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(kind)
        corpus = json.loads((bench / "configs" / "tpch-sf10-lineitem.json").read_text())["corpus"]
        kind.write_file(kind.rehearsal(corpus, 2048)[0], 7, 0, str(tmp_path), [])
        path = str(tmp_path / kind.file_name(0))
        q = parse_query_request(json.dumps({
            "paths": [path],
            "filters": [["l_shipdate", ">=", "1994-01-01"], ["l_shipdate", "<", "1995-01-01"], ["l_discount", ">=", "0.05"],
                        ["l_discount", "<=", "0.07"], ["l_quantity", "<", "24"], ["l_returnflag", "!=", "N"]],
            "aggregates": ["count", "sum(l_extendedprice*l_discount)", ["max", "l_extendedprice"], ["min", "l_shipdate"]],
        }).encode())
        seen = []
        real = mlir.lower_jaxpr_to_module

        def spy(module_name, jaxpr, *a, **kw):
            seen.append((module_name, jaxpr))
            return real(module_name, jaxpr, *a, **kw)

        monkeypatch.setattr(mlir, "lower_jaxpr_to_module", spy)
        jax.clear_caches()
        try:
            with FileReader(path) as r:
                (groups, _), scanned, matched = device_unit_partial(r, 0, q, q.filters)
        finally:
            jax.clear_caches()
        assert scanned == 2048 and 0 < matched == groups[()][0]
        names = {n for n, _ in seen}
        assert {"jit(expr_agg_device)", "jit(predicate_mask_device)", "jit(dict_verdict_device)", "jit(masked_agg_device)",
                "jit(merge_mixed_numeric_device)", "jit(expand_hybrid_device)", "jit(dict_gather_device)"} <= names, names
        bad = sorted({(n, str(a)) for n, j in seen for a in _avals(j.jaxpr)
                      if jnp.issubdtype(getattr(a, "dtype", jnp.int32), jnp.floating)})
        assert not bad, f"floating values in the query lane's programs: {bad}"

    def test_the_default_path_would_be_caught(self, files, monkeypatch):
        """The spy sees the float64 bitcast of the default delivery: the
        guard above is not vacuous."""
        from jax._src.interpreters import mlir

        _, paths = files
        seen = []
        real = mlir.lower_jaxpr_to_module

        def spy(module_name, jaxpr, *a, **kw):
            seen.append(jaxpr)
            return real(module_name, jaxpr, *a, **kw)

        monkeypatch.setattr(mlir, "lower_jaxpr_to_module", spy)
        jax.clear_caches()
        try:
            with FileReader(paths["plain"]) as r:
                r.read_row_groups_device(columns=["money"])
        finally:
            jax.clear_caches()
        assert any(getattr(a, "dtype", None) == jnp.float64 for j in seen for a in _avals(j.jaxpr))


class TestOneShippingWidth:
    """A writer packs each page at the width of the dictionary so far; the
    chunk ships at one width, narrower pages re-packed at freeze time."""

    @pytest.mark.parametrize("w_from,w_to", [(1, 2), (1, 32), (3, 5), (7, 8), (8, 9), (11, 12), (13, 14), (31, 32)])
    def test_repack_pages_native_equals_numpy(self, w_from, w_to, monkeypatch):
        """One page widened and one already at the width, as the native
        walk's tables: the library's re-pack, then ops/bitpack.py's."""
        from parquet_tpu.ops.bitpack import pack_bits, unpack_bits
        from parquet_tpu.ops.rle_hybrid import RunTable
        from parquet_tpu.utils.native import get_native

        rng = np.random.default_rng(w_from * 33 + w_to)
        vals = rng.integers(0, 1 << w_from, 8 * 257, dtype=np.uint64)
        kept = np.frombuffer(pack_bits(rng.integers(0, 1 << w_to, 80, dtype=np.uint64), w_to), dtype=np.uint8)
        src = np.frombuffer(pack_bits(vals, w_from), dtype=np.uint8)

        def page(k, packed, n, width):  # one bit-packed run, as the staged walk stages it
            table = RunTable(np.zeros(1, bool), np.array([n]), np.zeros(1, np.uint64), np.zeros(1, np.int64),
                             packed.tobytes(), 0)
            return ("dict", k, table, width, n, None)

        rows, res = pipeline._hybrid_tables_of([page(0, src, len(vals), w_from), page(1, kept, 80, w_to)])
        pages, is_rle, byteoff, native = pipeline._repack_pages_to_width(rows, res, w_to)
        wide = 257 * w_to
        assert np.array_equal(unpack_bits(native[:wide], len(vals), w_to, dtype=np.uint64), vals)
        assert np.array_equal(native[wide:], kept) and byteoff.tolist() == [0, wide] and not is_rle.any()
        assert [(P[pipeline._PC_PACKS], P[pipeline._PC_PACKE], P[pipeline._PC_EXTRA]) for P in pages] == [
            (0, wide, w_to), (wide, wide + len(kept), w_to)]
        monkeypatch.setattr(get_native(), "has_repack_pages", False)
        assert np.array_equal(pipeline._repack_pages_to_width(rows, res, w_to)[3], native)

    @pytest.mark.parametrize("n_dict,page_width,want", [
        (1, 1, 1), (2, 1, 1), (3, 2, 2), (6, 3, 3), (7, 3, 3), (265, 9, 9), (2026, 11, 12), (2062, 12, 12),
        (1821, 11, 11), (1822, 11, 12), (8888, 14, 14), (10526, 14, 14), (0, 0, 0), (5, 0, 3), (1 << 31, 32, 32),
    ])
    def test_index_width(self, n_dict, page_width, want):
        assert pipeline._index_width(page_width, n_dict) == want

    def _two_width_pages(self):
        from parquet_tpu.ops.rle_hybrid import encode_hybrid, prescan_hybrid

        rng = np.random.default_rng(8)
        pages = []
        for width, n in ((3, 4001), (3, 977), (5, 4001), (0, 64), (5, 1500)):
            idx = rng.integers(0, max(1 << width, 1), n).astype(np.uint64)
            if n > 200:
                idx[100:180] = idx[100]  # an RLE run among the bit-packed ones
            stream = encode_hybrid(idx, width)
            pages.append((width, n, idx, prescan_hybrid(stream, n, width)))
        return pages

    def test_two_widths_freeze_to_one_program_equal_to_the_two_program_result(self):
        pages = self._two_width_pages()
        pending = [("dict", k, table, width, n, None) for k, (width, n, _idx, table) in enumerate(pages)]

        def freeze(part):  # no dictionary size given: the width is the widest page's
            return pipeline._freeze_hybrid_from_tables(*pipeline._hybrid_tables_of(part))

        # as before PR 28: one upload, one program, per run of equal widths
        parts = [[pending[0], pending[1]], [pending[2]], [pending[3]], [pending[4]]]
        old = jnp.concatenate([pipeline._dispatch_hybrid(f) for part in parts for f in freeze(part)])
        with decode_trace() as tr:
            (frozen,) = freeze(pending)
        assert isinstance(frozen, dops.FrozenHybrid) and frozen.width == 5
        assert tr.stages["hybrid_pages_repacked"].calls == 3
        new = pipeline._dispatch_hybrid(frozen)
        assert len(parts) == 4 and np.array_equal(np.asarray(new), np.asarray(old))
        assert np.array_equal(np.asarray(new), np.concatenate([p[2] for p in pages]).astype(np.uint32))

    @pytest.mark.parametrize("fused", ["1", "1-numpy", "0"])
    def test_growing_dictionary_chunk_ships_at_one_width(self, tmp_path, monkeypatch, fused, staged_walk):
        """Both walks (the native walk's tables, the staged walk's prescans
        laid out as such) into the one freeze, on a real file whose pages
        widen 2 -> 10 bits; the native walk also with the NumPy fallback of
        the re-pack."""
        from contextlib import nullcontext

        if fused == "1-numpy":
            from parquet_tpu.utils.native import get_native

            monkeypatch.setattr(get_native(), "has_repack_pages", False)
        rng = np.random.default_rng(4)
        v = np.concatenate([rng.integers(0, 4, 30_000), rng.integers(0, 700, 50_000)]).astype(np.int64)
        path = str(tmp_path / "grow.parquet")
        pq.write_table(pa.table({"x": pa.array(v)}), path, data_page_size=4096, row_group_size=len(v))
        with (staged_walk if fused == "0" else nullcontext)(), decode_trace() as tr:
            with FileReader(path) as r:
                cc, col = r.row_group(0).columns[0], r.schema.column(("x",))
                plan = prepare_chunk_plan(r._f, cc, col)
                assert len(plan.frozen_hybrid) == 1 and plan.frozen_hybrid[0].width == 10
                (g,) = r.read_row_groups_device()
        assert ("prepare_fused_engaged" in tr.stages) == (fused != "0")
        assert np.array_equal(np.asarray(g[("x",)].values), v)
        assert tr.stages["hybrid_pages_repacked"].calls > 0
        assert tr.stages["prepare.repack_width"].seconds > 0 and tr.stages["prepare.repack_width"].bytes > 0
        assert "host_decoded_pages" not in tr.stages

    def test_chunks_just_under_and_over_a_power_of_two_share_a_shape(self, tmp_path):
        """2,030 and 2,060 dictionary entries: 11 and 12 bits as written, one
        compiled shape as shipped (TLC tip_amount, PERF.md section 6)."""
        shapes = set()
        for k, n_dict in enumerate((2030, 2060)):
            rng = np.random.default_rng(k)
            v = np.concatenate([np.arange(n_dict), rng.integers(0, n_dict, 40_000)]).astype(np.float64) / 4
            path = str(tmp_path / f"d{n_dict}.parquet")
            pq.write_table(pa.table({"x": pa.array(v)}), path, row_group_size=len(v))
            with FileReader(path) as r:
                cc, col = r.row_group(0).columns[0], r.schema.column(("x",))
                plan = prepare_chunk_plan(r._f, cc, col, doubles="float32")
                (f,) = plan.frozen_hybrid
                shapes.add((f.width, f.n_pad, f.run_pad, len(f.buf), plan.dict_upload.dtype, len(plan.dict_upload)))
                (g,) = r.read_row_groups_device(doubles="float32")
            assert same_double_form(g[("x",)].values, v, "float32")
        assert len(shapes) == 1 and next(iter(shapes))[0] == 12
