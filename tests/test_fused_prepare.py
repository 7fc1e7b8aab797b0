"""The fused GIL-free native chunk prepare (ptq_chunk_prepare via
_native_ext.chunk_prepare / ctypes).

Four contracts pinned here:
  * byte-identical ChunkData between the fused walk and the staged per-page
    Python walk (conftest.py's staged_walk: the native walk declines) across
    the encoding x codec x page version x nullable/nested matrix, with
    read_chunk as a third oracle;
  * one freeze: over the same matrix, and over chunks whose index pages were
    written at three widths, the staged walk's frozen upload records equal
    the native walk's field for field and byte for byte;
  * observability: prepare_fused_engaged / prepare_fused_declined trace
    counters say which path a chunk took, and the fused walk's internal
    stage split lands in prepare.* stages;
  * thread-safety + GIL release: concurrent prepares from >= 4 threads are
    correct, and on a multi-core host the walk delivers more than one
    effective core of throughput.
"""

from __future__ import annotations

import concurrent.futures as cf
import os
import time
from contextlib import nullcontext

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from parquet_tpu.core.arrays import ByteArrayData
from parquet_tpu.core.chunk import ChunkWindow, chunk_byte_range, read_chunk
from parquet_tpu.core.reader import FileReader
from parquet_tpu.kernels import pipeline
from parquet_tpu.kernels.pipeline import plan_chunk_tpu, prepare_chunk_plan
from parquet_tpu.utils.native import get_native
from parquet_tpu.utils.trace import decode_trace

_lib = get_native()
requires_native = pytest.mark.skipif(
    _lib is None or not _lib.has_chunk_prepare,
    reason="native chunk_prepare not built",
)


# -- the differential matrix ---------------------------------------------------

ROWS = 20_000
_MATRIX_KINDS = [
    "plain_i64", "plain_f32", "dict_str", "delta_i64", "bss_f32", "nullable_i64", "nested_list",
]


def _column(kind):
    """(arrow array, write kwargs) for one matrix shape."""
    rng = np.random.default_rng(11)
    if kind == "plain_i64":
        return pa.array(rng.integers(-(1 << 40), 1 << 40, ROWS), pa.int64()), {
            "use_dictionary": False,
            "column_encoding": {"v": "PLAIN"},
        }
    if kind == "plain_f32":
        return pa.array(rng.random(ROWS).astype(np.float32)), {
            "use_dictionary": False,
            "column_encoding": {"v": "PLAIN"},
        }
    if kind == "dict_str":
        return pa.array([f"val_{i % 97}" for i in range(ROWS)]), {
            "use_dictionary": ["v"],
        }
    if kind == "delta_i64":
        return pa.array(np.cumsum(rng.integers(0, 50, ROWS)).astype(np.int64)), {
            "use_dictionary": False,
            "column_encoding": {"v": "DELTA_BINARY_PACKED"},
        }
    if kind == "bss_f32":
        return pa.array(rng.random(ROWS).astype(np.float32)), {
            "use_dictionary": False,
            "column_encoding": {"v": "BYTE_STREAM_SPLIT"},
        }
    if kind == "nullable_i64":
        mask = rng.random(ROWS) < 0.25
        return pa.array(
            rng.integers(0, 1 << 30, ROWS), pa.int64(), mask=mask
        ), {"use_dictionary": False, "column_encoding": {"v": "PLAIN"}}
    if kind == "nested_list":
        lengths = rng.integers(0, 5, ROWS // 4)
        vals = rng.integers(0, 1 << 20, int(lengths.sum())).astype(np.int32)
        offs = np.zeros(len(lengths) + 1, dtype=np.int64)
        np.cumsum(lengths, out=offs[1:])
        rows = [
            None if i % 7 == 0 else vals[offs[i] : offs[i + 1]].tolist()
            for i in range(len(lengths))
        ]
        return pa.array(rows, pa.list_(pa.int32())), {"use_dictionary": False}
    raise AssertionError(kind)


def _build(tmp_path, kind, codec, version):
    arr, kw = _column(kind)
    p = str(tmp_path / f"{kind}_{codec}_{version.replace('.', '')}.parquet")
    pq.write_table(
        pa.table({"v": arr}),
        p,
        compression=codec,
        data_page_version=version,  # pyarrow spells them "1.0"/"2.0"
        row_group_size=ROWS // 3,  # several pages/chunks per file
        **kw,
    )
    return p


def _chunk_windows(r):
    """(window, chunk, column) of every chunk of an open FileReader."""
    for i in range(r.num_row_groups):
        for _p, cc, col in r._selected_chunks(i):
            off, total = chunk_byte_range(cc)
            yield ChunkWindow(r._pread(off, total), off), cc, col


def _prepare_chunks(path, walk=nullcontext):
    """Every chunk's ChunkData via the device-plan pipeline: fused, or staged
    under walk=staged_walk."""
    with walk(), decode_trace() as tr:
        with FileReader(path) as r:
            out = [plan_chunk_tpu(*c).finalize() for c in _chunk_windows(r)]
    return out, tr


def _host_chunks(path):
    with FileReader(path) as r:
        return [read_chunk(*c) for c in _chunk_windows(r)]


def _assert_chunkdata_equal(a, b, ctx):
    assert a.num_values == b.num_values, ctx
    va, vb = a.values, b.values
    if isinstance(va, ByteArrayData) or isinstance(vb, ByteArrayData):
        assert isinstance(va, ByteArrayData) and isinstance(vb, ByteArrayData), ctx
        assert np.array_equal(va.offsets, vb.offsets), ctx
        assert bytes(va.data) == bytes(vb.data), ctx
    else:
        na, nb = np.asarray(va), np.asarray(vb)
        assert na.dtype == nb.dtype, (ctx, na.dtype, nb.dtype)
        assert np.array_equal(
            na.view((np.uint8, na.dtype.itemsize)) if na.itemsize > 1 else na,
            nb.view((np.uint8, nb.dtype.itemsize)) if nb.itemsize > 1 else nb,
        ), ctx
    for attr in ("def_levels", "rep_levels"):
        la, lb = getattr(a, attr), getattr(b, attr)
        assert (la is None) == (lb is None), (ctx, attr)
        if la is not None:
            assert np.array_equal(np.asarray(la), np.asarray(lb)), (ctx, attr)


@requires_native
@pytest.mark.parametrize("codec", ["none", "snappy", "gzip"])
@pytest.mark.parametrize("version", ["1.0", "2.0"])
@pytest.mark.parametrize("kind", _MATRIX_KINDS)
def test_fused_matches_staged_and_host(tmp_path, kind, codec, version, staged_walk):
    path = _build(tmp_path, kind, codec, version)
    fused, tr_fused = _prepare_chunks(path)
    staged, tr_staged = _prepare_chunks(path, staged_walk)
    host = _host_chunks(path)
    ctx = (kind, codec, version)
    assert len(fused) == len(staged) == len(host), ctx
    for a, b, c in zip(fused, staged, host):
        _assert_chunkdata_equal(a, b, ctx)
        _assert_chunkdata_equal(a, c, ctx)
    # the fused run must actually have taken the fused path for every chunk
    engaged = tr_fused.stages.get("prepare_fused_engaged")
    assert engaged is not None and engaged.calls == len(fused), ctx
    assert "prepare_fused_declined" not in tr_fused.stages, ctx
    # the staged run must not have touched the fused walk
    assert "prepare_fused_engaged" not in tr_staged.stages, ctx


# -- one freeze: the staged walk's upload records are the native walk's --------

def _frozen_records(path, walk=nullcontext, **kw):
    """[(frozen_hybrid, frozen_delta)] of every chunk, prepared and not dispatched."""
    with walk(), FileReader(path) as r:
        plans = [prepare_chunk_plan(*c, **kw) for c in _chunk_windows(r)]
    return [(plan.frozen_hybrid, plan.frozen_delta) for plan in plans]


def _assert_records_equal(native, staged, ctx):
    assert len(native) == len(staged), ctx
    for a, b in zip(native, staged):
        assert type(a) is type(b) and a._fields == b._fields, ctx
        for name in a._fields:
            x, y = getattr(a, name), getattr(b, name)
            if isinstance(x, np.ndarray):
                assert x.dtype == y.dtype and x.shape == y.shape, (ctx, name)
                assert x.tobytes() == y.tobytes(), (ctx, name)
            else:
                assert x == y, (ctx, name)


@requires_native
@pytest.mark.parametrize("codec", ["none", "snappy", "gzip"])
@pytest.mark.parametrize("version", ["1.0", "2.0"])
@pytest.mark.parametrize("kind", _MATRIX_KINDS)
def test_staged_freeze_equals_native_freeze(tmp_path, kind, codec, version, staged_walk):
    path = _build(tmp_path, kind, codec, version)
    native = _frozen_records(path)
    staged = _frozen_records(path, staged_walk)
    assert len(native) == len(staged) >= 1
    for (nh, nd), (sh, sd) in zip(native, staged):
        _assert_records_equal(nh, sh, (kind, codec, version, "hybrid"))
        _assert_records_equal(nd, sd, (kind, codec, version, "delta"))
        # the two kinds that freeze an upload do
        assert len(nh) == (kind == "dict_str") and len(nd) == (kind == "delta_i64")


def _three_width_table():
    """Dictionary columns whose pages widen 2 -> 10 -> 13 bits as the
    dictionary grows, with a stretch of one value (RLE runs) in every width's
    pages — an RLE run's bit offset, which no kernel reads, is where the two
    walks differed before they shared a freeze — plain, nullable and DOUBLE;
    and two delta columns."""
    rng = np.random.default_rng(4)
    v = np.concatenate(
        [rng.integers(0, 4, 30_000), rng.integers(0, 700, 50_000), rng.integers(0, 5000, 80_000)]
    ).astype(np.int64)
    for a in (1_000, 22_000, 41_000, 75_000, 90_000, 150_000):
        v[a : a + 3_000] = v[a]
    mask = rng.random(len(v)) < 0.1
    ts = np.cumsum(rng.integers(0, 5000, len(v))).astype(np.int64)
    return pa.table({
        "x": pa.array(v),
        "xn": pa.array(v, mask=mask),
        "xd": pa.array(v.astype(np.float64) / 4),
        "ts": pa.array(ts, mask=mask),
        "t32": pa.array((ts % (1 << 30)).astype(np.int32)),
    })


def _write_three_widths(tmp_path, version):
    t = _three_width_table()
    path = str(tmp_path / "widths.parquet")
    pq.write_table(
        t, path, data_page_size=4096, row_group_size=t.num_rows, data_page_version=version,
        use_dictionary=["x", "xn", "xd"],
        column_encoding={"ts": "DELTA_BINARY_PACKED", "t32": "DELTA_BINARY_PACKED"},
    )
    return t, path


@requires_native
@pytest.mark.parametrize("cap", [None, 1 << 19], ids=["one-upload", "uploads-split-at-2^19-bits"])
@pytest.mark.parametrize("version", ["1.0", "2.0"])
def test_staged_freeze_equals_native_freeze_three_widths(tmp_path, version, cap, staged_walk, monkeypatch):
    t, path = _write_three_widths(tmp_path, version)
    if cap is not None:
        monkeypatch.setattr(pipeline, "_BATCH_BITS_CAP", cap)
    with decode_trace() as tr_native:
        native = _frozen_records(path, doubles="float32")
    with decode_trace() as tr_staged:
        staged = _frozen_records(path, staged_walk, doubles="float32")
    for name, (nh, nd), (sh, sd) in zip(t.column_names, native, staged):
        _assert_records_equal(nh, sh, (name, "hybrid"))
        _assert_records_equal(nd, sd, (name, "delta"))
        uploads = len(nh) + len(nd)
        assert (uploads == 1) if cap is None else (uploads > 1), (name, uploads)
        assert all(f.width == 16 for f in nh), name  # the chunk's 13 bits ship as 16
    # both walks widened the same pages, through the same re-pack
    repacked = tr_native.stages["hybrid_pages_repacked"].calls
    assert repacked > 0 and tr_staged.stages["hybrid_pages_repacked"].calls == repacked
    assert "host_decoded_pages" not in tr_native.stages and "host_decoded_pages" not in tr_staged.stages


# -- what the hybrid frame holds: the host decode's indices, slot for slot -------

def _unframe(f):
    """The slots of a FrozenHybrid in order, in numpy: word j of a plane of
    b-bit parts holds slots j, j + L, j + 2L, ... (device_ops.pack_hybrid_upload)."""
    a = 1 << (f.width.bit_length() - 1) if f.width else 0
    out = np.zeros(f.n_pad, dtype=np.uint32)
    at = 0
    for bits, shift in ((a, 0), (f.width - a, a)):
        if bits:
            plane = f.buf[at : at + f.n_pad * bits // 32]
            at += len(plane)
            k = (np.arange(32 // bits, dtype=np.uint32) * np.uint32(bits))[:, None]
            out |= ((plane[None, :] >> k) & np.uint32((1 << bits) - 1)).reshape(-1) << np.uint32(shift)
    assert at == len(f.buf) and not out[f.total :].any()
    return out[: f.total]


def _assert_frames_hold_the_host_indices(path, walk, ctx, columns=None, **kw):
    """Every dictionary chunk's frames, end to end, are the indices the host
    path decodes from the same wire (read_chunk keep_dict_indices=True):
    re-packed pages, RLE runs, clamped last groups, nulls and uploads split
    under the bit cap included. No dictionary value takes part."""
    frames = 0
    with walk(), FileReader(path) as r:
        for window, cc, col in _chunk_windows(r):
            if columns is not None and col.path_str not in columns:
                continue
            plan = prepare_chunk_plan(window, cc, col, **kw)
            host = read_chunk(window, cc, col, keep_dict_indices=True)
            assert plan.frozen_hybrid and host.indices is not None, (ctx, col.path_str)
            got = np.concatenate([_unframe(f) for f in plan.frozen_hybrid])
            assert np.array_equal(got, np.asarray(host.indices).astype(np.uint32)), (ctx, col.path_str)
            frames += len(plan.frozen_hybrid)
    assert frames, ctx
    return frames


@requires_native
@pytest.mark.parametrize("walk", ["native", "staged"])
@pytest.mark.parametrize("codec", ["none", "snappy", "gzip"])
@pytest.mark.parametrize("version", ["1.0", "2.0"])
def test_frames_hold_the_host_indices(tmp_path, version, codec, walk, staged_walk):
    path = _build(tmp_path, "dict_str", codec, version)
    _assert_frames_hold_the_host_indices(
        path, staged_walk if walk == "staged" else nullcontext, (version, codec, walk)
    )


@requires_native
@pytest.mark.parametrize("walk", ["native", "staged"])
@pytest.mark.parametrize("cap", [None, 1 << 19], ids=["one-upload", "uploads-split-at-2^19-bits"])
@pytest.mark.parametrize("version", ["1.0", "2.0"])
def test_frames_hold_the_host_indices_three_widths(
    tmp_path, version, cap, walk, staged_walk, monkeypatch
):
    """Pages re-packed from 2 and 10 bits to the chunk's 13 (shipped as 16),
    RLE runs between the bit-packed ones, nulls, a DOUBLE dictionary, uploads
    split under the bit cap (a later group's offsets count from its own first
    byte, and its frame from its own first slot)."""
    _t, path = _write_three_widths(tmp_path, version)
    if cap is not None:
        monkeypatch.setattr(pipeline, "_BATCH_BITS_CAP", cap)
    frames = _assert_frames_hold_the_host_indices(
        path, staged_walk if walk == "staged" else nullcontext, (version, cap, walk),
        columns=("x", "xn", "xd"), doubles="float32",
    )
    assert (frames == 3) if cap is None else (frames > 3)


@requires_native
def test_fused_stage_breakdown_collected(tmp_path):
    """Under an active trace the walk reports its internal stage split."""
    path = _build(tmp_path, "dict_str", "snappy", "2.0")
    _, tr = _prepare_chunks(path)
    assert tr.stages["prepare.decompress"].seconds > 0
    # dict-index pages prescan their run headers inside the walk
    assert "prepare.prescan" in tr.stages


@requires_native
def test_fused_crc_validation_stays_engaged(tmp_path):
    """validate_crc no longer forfeits the fused walk: stored CRCs verify
    INSIDE the native prepare, so clean chunks stay on the fast path (the
    counters say so), and the decode matches the staged walk exactly."""
    import pyarrow.parquet as _pq

    arr, kw = _column("plain_i64")
    path = str(tmp_path / "crc.parquet")
    _pq.write_table(
        pa.table({"v": arr}), path, compression="snappy",
        write_page_checksum=True, row_group_size=ROWS // 3, **kw,
    )
    with decode_trace() as tr:
        with FileReader(path) as r:
            plans = [
                prepare_chunk_plan(*c, validate_crc=True).dispatch_device().finalize()
                for c in _chunk_windows(r)
            ]
    engaged = tr.stages.get("prepare_fused_engaged")
    assert engaged is not None and engaged.calls == len(plans)
    assert "prepare_fused_declined" not in tr.stages
    assert "prepare.crc" in tr.stages
    host = _host_chunks(path)
    for a, b in zip(plans, host):
        _assert_chunkdata_equal(a, b, "crc-validated fused")


@requires_native
def test_fused_prepare_reader_end_to_end(tmp_path):
    """read_row_group through the device backend equals the host backend with
    the fused walk engaged (the whole-reader differential)."""
    path = _build(tmp_path, "dict_str", "snappy", "1.0")
    with decode_trace() as tr:
        with FileReader(path, backend="tpu_roundtrip") as r:
            dev = [r.read_row_group(i) for i in range(r.num_row_groups)]
    assert tr.stages["prepare_fused_engaged"].calls > 0
    with FileReader(path, backend="host") as r:
        host = [r.read_row_group(i) for i in range(r.num_row_groups)]
    for rg_d, rg_h in zip(dev, host):
        assert rg_d.keys() == rg_h.keys()
        for p in rg_d:
            _assert_chunkdata_equal(rg_d[p], rg_h[p], p)


# -- multi-thread stress (the released-GIL contract) ---------------------------


def _stress_work(tmp_path, n_groups=12):
    rng = np.random.default_rng(3)
    rows = 240_000
    t = pa.table(
        {
            "a": pa.array(rng.integers(0, 1 << 40, rows), pa.int64()),
            "s": pa.array([f"k{i % 211}" for i in range(rows)]),
        }
    )
    p = str(tmp_path / "stress.parquet")
    pq.write_table(
        t,
        p,
        compression="snappy",
        use_dictionary=["s"],
        column_encoding={"a": "PLAIN"},
        row_group_size=rows // n_groups,
    )
    work = []
    with FileReader(p) as r:
        for i in range(r.num_row_groups):
            for _p, cc, col in r._selected_chunks(i):
                off, total = chunk_byte_range(cc)
                work.append((r._pread(off, total), off, cc, col))
    return work


def _prep_item(item):
    buf, off, cc, col = item
    return prepare_chunk_plan(ChunkWindow(buf, off), cc, col)


@requires_native
def test_multithreaded_fused_prepare_correct(tmp_path):
    """>= 4 threads hammering the fused walk concurrently produce exactly the
    serial results (thread-local scratch, no shared mutable state)."""
    work = _stress_work(tmp_path)
    serial = [_prep_item(it).dispatch_device().finalize() for it in work]
    with cf.ThreadPoolExecutor(max_workers=4) as pool:
        for _round in range(3):
            plans = list(pool.map(_prep_item, work))
            for plan, want, it in zip(plans, serial, work):
                got = plan.dispatch_device().finalize()
                _assert_chunkdata_equal(got, want, it[2].meta_data.path_in_schema)


@requires_native
@pytest.mark.skipif((os.cpu_count() or 1) < 2, reason="single-core host")
def test_multithreaded_fused_prepare_scales(tmp_path):
    """The fused walk holds no GIL while crunching: on a multi-core host,
    4 prepare threads must beat 1 (best-of-7 each, > 1 effective core).

    Chunks are sized so the GIL-free C walk dominates each prepare — tiny
    chunks measure executor overhead and the GIL-held plan assembly instead
    (Amdahl), which is not the contract under test."""
    rng = np.random.default_rng(5)
    rows = 1_000_000
    t = pa.table({"v": pa.array(rng.integers(0, 1000, rows).astype(np.int64))})
    p = str(tmp_path / "scale.parquet")
    pq.write_table(
        t, p, compression="snappy", use_dictionary=False,
        column_encoding={"v": "PLAIN"}, row_group_size=rows // 8,
    )
    work = []
    with FileReader(p) as r:
        for i in range(r.num_row_groups):
            for _pp, cc, col in r._selected_chunks(i):
                off, total = chunk_byte_range(cc)
                work.append((r._pread(off, total), off, cc, col))
    for it in work:
        _prep_item(it)  # warm native buffers + page cache

    def serial():
        for it in work:
            _prep_item(it)

    with cf.ThreadPoolExecutor(max_workers=4) as pool:

        def threaded():
            list(pool.map(_prep_item, work))

        threaded()  # per-thread scratch warmup
        # A held GIL serializes the C walks, so threaded can NEVER beat
        # serial; a shared/loaded CI host merely makes any single sample
        # noisy. Retrying distinguishes the two: real parallelism wins some
        # attempt, a serialized walk wins none. (8 attempts: on cgroup
        # cpu-shares-throttled 2-vCPU boxes the quiet windows where threads
        # can actually run side by side are minutes apart — observed 2-of-4
        # spurious failures at 3 attempts with the walk fully GIL-free.)
        ts = tp = None
        for _attempt in range(8):
            ts = min(_walltime(serial) for _ in range(7))
            tp = min(_walltime(threaded) for _ in range(7))
            if tp < ts:
                break
    if tp >= ts and not _host_can_thread():
        # the PREMISE failed, not the contract: this host (throttled
        # shared vCPUs) cannot run even two known-GIL-free zlib threads
        # side by side right now, so no walk could demonstrate scaling
        pytest.skip("host cannot run 2 GIL-free C threads concurrently")
    assert tp < ts, f"no scaling: serial {ts * 1e3:.1f}ms threaded {tp * 1e3:.1f}ms"


def _host_can_thread() -> bool:
    """Calibration: can THIS host, RIGHT NOW, run two threads of plain C
    work (zlib.compress — drops the GIL unconditionally) faster than the
    same work serially? Distinguishes 'the fused walk holds the GIL' (a
    real bug, fails everywhere) from 'this CI box has no second core to
    give' (cgroup shares / SMT-sibling vCPUs / noisy neighbors)."""
    import threading
    import zlib

    data = bytes(range(256)) * 8192  # ~2 MiB, big enough to dwarf overhead

    def crunch():
        for _ in range(4):
            zlib.compress(data, 6)

    crunch()
    best_serial = min(_walltime(lambda: (crunch(), crunch())) for _ in range(3))

    def pair():
        threads = [threading.Thread(target=crunch) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    best_pair = min(_walltime(pair) for _ in range(3))
    return best_pair < best_serial * 0.85


def _walltime(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
