"""Benchmark: decoded columns delivered into TPU HBM — device decode vs host.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "rows/s", "vs_baseline": N}

The metric is the TPU-native delivery point (BASELINE.json north star, SURVEY
§7.1): a TPU framework's decode ends with typed column arrays resident in
device memory, ready for jitted compute — not host arrays. Two ways to get
there, on a NYC-taxi-like file (int64 id PLAIN, dict-encoded vendor string,
DELTA_BINARY_PACKED int64 timestamp; snappy; the north-star column mix):

  baseline   host-path decode (vectorized NumPy) + upload of the decoded
             columns to the device — what a JAX user does with any host
             parquet library.
  ours       FileReader.read_row_group_device(): encoded value streams are
             prescanned on host, shipped to the device *encoded* (dict
             indices at index width, packed deltas — several times smaller
             than the decoded output) and decoded by the batched XLA kernels
             in HBM. Decoded values never cross the host<->device link.

Both deliveries are verified logically identical (byte-level for numerics,
string-level for dictionary columns) before any timing run. The classic
decode-to-host rows/s comparison is also measured and logged to stderr.

vs_baseline: the Go reference cannot run in this image (no Go toolchain;
BASELINE.md notes the reference publishes no numbers), so the baseline is the
host-decode-plus-upload path above — the stand-in for "pure host decode" in
the north star, measured at the same delivery point.

Env knobs: PQT_BENCH_ROWS (default 2_000_000), PQT_BENCH_REPEATS (default 3),
PQT_BENCH_MATRIX=0 to skip the BASELINE.md 5-config matrix (on by default),
PQT_MATRIX_ROWS (default 1_000_000) rows per matrix config,
PQT_DATASET_ROWS / PQT_DATASET_FILES (default 2_000_000 over 8 files) and
PQT_DATASET_STEP_MS (default 2) for the `--dataset` loader benchmark,
PQT_BENCH_DATASET=0 to skip it in a full run. PQT_IO_ROWS (default 400_000)
and PQT_IO_LAT_MS (default 0.3) shape the `--io` io-layer sweep;
PQT_BENCH_IO=0 skips it in a full run.

`--assembly` benchmarks record assembly: the vectorized level-scan engine
(core/assembly_vec, the iter_rows default) vs the scalar cursor walk
(PQT_VEC_ASSEMBLY=0) vs pyarrow to_pylist, on flat / 1-level (the
matrix cfg5 LIST<int32> shape) / 2-level nested tables. Vec and scalar
assemble the SAME pre-decoded chunks and the vec rows are asserted
identical to the scalar rows before timing. PQT_ASSEMBLY_ROWS (default
300_000) sizes the tables; PQT_BENCH_ASSEMBLY=0 skips it in a full run.
The result rides the --json artifact under "assembly".

`--io` benchmarks the io layer (parquet_tpu.io) against a latency-injected
FlakySource (every read pays a simulated range-GET latency plus a transient
EIO rate absorbed by the retry ladder): a coalesce-gap sweep (0 / 64 KiB /
1 MiB) over a gappy 4-of-8-column projection, then a readahead-depth sweep
(0/2/4 row groups prefetched into a shared block cache on the pqt-io pool).
The result rides the --json artifact under "io".

`--io-remote` benchmarks the REMOTE io stack (io.remote + io.tiercache +
io.autotune) over real loopback HTTP: testing.httpstub serves the fixture
at injected RTT 0/5/25 ms and a 4-of-8 projection scans through HttpSource
with fixed local knobs vs coalesce_gap="auto" (the latency-aware tuner),
plus a tiered RAM->disk cache whose warm re-scan is asserted to read ZERO
source bytes before timing. PQT_IO_REMOTE_ROWS (default 200_000) and
PQT_IO_REMOTE_REPEATS (default 3) size it; PQT_BENCH_IO_REMOTE=0 skips it
in a full run. The result rides the --json artifact under "io_remote".

`--io-write` benchmarks the remote WRITE path (io.remote_sink) over real
loopback HTTP: an IO_WRITE_MB payload streams through HttpSink's multipart
protocol into a writable testing.httpstub at injected RTT 0/5/25 ms,
sweeping the part size (2/4/8 MiB), with every committed object asserted
byte-identical to the payload before its time counts. PQT_IO_WRITE_MB
(default 32) and PQT_IO_WRITE_REPEATS (default 3) size it;
PQT_BENCH_IO_WRITE=0 skips it in a full run. The result rides the --json
artifact under "io_write".

`--write` benchmarks the write path: FileWriter vs pyarrow (snappy headline)
plus the pqt-encode PARALLELISM sweep — pool 1/4/8 x 8/16 row groups on a
GZIP log-ingest table (PQT_WRITE_ROWS rows, default 400K), every parallel
output asserted byte-identical to the serial file before timing. The result
rides the --json artifact under "write" (also as the matrix "write" config).

`--dataset` benchmarks the streaming loader (parquet_tpu.data) end to end
over a multi-file glob: rows/s through ParquetDataset at a sweep of prefetch
depths against a device-bound consumer (host blocked PQT_DATASET_STEP_MS per
batch, the shape of block_until_ready on an accelerator step), with the
wait-time share (consumer starvation) per depth — the overlap-is-real check
is depth>=2 beating depth 0, and `loader_rows_s` records the step-free pure
decode+rebatch rate. Host-only (jax forced to CPU); the result rides the
--json artifact under "dataset".

`--serve` benchmarks the scan/query daemon (parquet_tpu.serve) over real
HTTP against an in-process `ScanServer` on an ephemeral port: requests/s
and p50/p99 request latency at client concurrency 1/4/16 (each request a
full jsonl shard scan, round-robin over a PQT_SERVE_FILES-file corpus of
PQT_SERVE_ROWS total rows, PQT_SERVE_REQUESTS per level) against a WARM
daemon, plus the cold-vs-warm /v1/plan latency ratio the footer/block
caches buy. PQT_BENCH_SERVE=0 skips it in a full run; the result rides
the --json artifact under "serve".

`--serve-mesh` benchmarks the sharded-serve router (parquet_tpu.serve.mesh)
over REAL subprocess replica daemons: routed req/s at replica counts 1 and
4 under fixed client concurrency (the `mesh.rps_1r`/`mesh.rps_4r` trend
pins — read the scaling ratio against the fingerprint's nproc), every
routed response checked byte-identical against a direct replica answer,
plus a chaos leg that SIGKILLs one replica mid-hammer and pins typed
retries only (no torn streams, no untyped errors).
PQT_SERVE_MESH_REQUESTS / PQT_SERVE_MESH_CONC size it;
PQT_BENCH_SERVE_MESH=0 skips it in a full run; the result rides the
--json artifact under "mesh".

`--chaos` benchmarks graceful degradation under the scripted fault schedule
(testing/chaos.py: latency spike -> error burst -> blackout -> recovery,
driven through every source the process opens): the SLO-controlled dataset
pipeline vs the same pipeline uncontrolled (per-phase p50/p99 consumer
waits; the pin is p99 within the SLO in the steady spike phase WITH the
controller and over it WITHOUT), hedged-read win rate, the breakered vs
un-breakered time-to-error on a blacked-out source (pin: < 10%), and the
serve daemon under brownout (statuses, sheds, typed-responses-only pin).
PQT_CHAOS_ROWS / PQT_CHAOS_FILES / PQT_CHAOS_PHASE_S size it;
PQT_CHAOS_SMOKE=1 is the make-check-sized smoke; PQT_BENCH_CHAOS=0 skips
it in a full run. The result rides the --json artifact under "chaos".

`--ingest` benchmarks the data-lake write loop (parquet_tpu.lake):
sustained append rows/s into a sort-keyed table with every batch flushed
(each flush a real sort+encode+manifest generation), then the compaction
payoff — a sort-key point probe's pruned-unit ratio and filtered-scan
wall before vs after one compaction folds the overlapping ingest files
into clustered row groups. Tracked pins: ingest.append_rows_s,
ingest.pruned_ratio_gain, ingest.scan_speedup. PQT_INGEST_ROWS /
PQT_INGEST_BATCH size it; PQT_BENCH_INGEST=0 skips it in a full run.
The result rides the --json artifact under "ingest".

`--json out.json` (or PQT_BENCH_JSON=out.json) additionally writes the
final structured result — headline + per-stage prepare breakdown + matrix —
to a file, so the BENCH_* trajectory artifacts are produced by the harness
itself instead of by hand. Works in phase mode too
(`bench.py --phase prepare --json out.json` writes that phase's object).

`--compare old.json new.json [--threshold 0.10]` diffs two --json artifacts
section by section: every tracked metric (throughputs like rows_s/req_s and
the headline `value` are higher-better; latencies/walls like *_ms, p50_ms,
`t` are lower-better) prints a new/old ratio, and the run exits non-zero
when any tracked metric REGRESSES beyond the threshold (default 10%) — the
`make bench-compare OLD=... NEW=...` gate future PRs hold the BENCH_r0x
trajectory against. Untracked leaves (counts, depths, config echoes) are
reported as changed/unchanged but never gate; two artifacts with NO
tracked metric in common also exit non-zero (a gate that compared
nothing must not read as green). With ONE path, the old side defaults to
the LATEST round recorded in BENCH_history.jsonl — `bench.py --compare
/tmp/now.json` is the whole regression check.

`--record artifact.json [--label rNN] [--history PATH]` appends the
artifact to the persistent trend store BENCH_history.jsonl together with
its provenance (git rev, a fingerprint of the PQT_* config env, python/
platform, timestamp) — the per-PR trajectory record the BENCH_r0x files
used to be by hand. `--trend [--history PATH] [--section S]` renders
every tracked metric's value across the recorded rounds with the
last-vs-first ratio, newest round on the right; it also validates the
store's schema (a malformed entry exits non-zero), which is what the
`make check` trend smoke asserts.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import numpy as np

ROWS = int(os.environ.get("PQT_BENCH_ROWS", 2_000_000))
REPEATS = int(os.environ.get("PQT_BENCH_REPEATS", 5))
CACHE = Path(f"/tmp/pqt_bench_{ROWS}.parquet")

# `--json PATH` / PQT_BENCH_JSON: where to write the final structured result
_JSON_OUT = os.environ.get("PQT_BENCH_JSON")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _write_artifact(obj) -> None:
    """Write the structured result to the --json/PQT_BENCH_JSON path (no-op
    when unset). The artifact carries the config fingerprint of the env
    the benchmark ACTUALLY ran under, so a later `--record` from a
    different shell cannot stamp the wrong provenance (string leaves:
    invisible to the --compare gate)."""
    if _JSON_OUT:
        digest, basis = _config_fingerprint()
        obj = {**obj, "bench_config": {"fingerprint": digest, "basis": basis}}
        try:
            Path(_JSON_OUT).write_text(json.dumps(obj, indent=1) + "\n")
        except OSError as e:  # pragma: no cover
            log(f"bench: could not write {_JSON_OUT}: {e}")


def _emit(obj) -> None:
    """Print the result line (the machine-readable contract) and, when
    --json/PQT_BENCH_JSON is set, write the same object to that file."""
    if _DEVICE is not None:
        obj = {**obj, "device": _DEVICE}
    print(json.dumps(obj))
    _write_artifact(obj)


def build_file() -> Path:
    if CACHE.exists():
        return CACHE
    import pyarrow as pa
    import pyarrow.parquet as pq

    log(f"bench: generating {ROWS:,}-row taxi-like file at {CACHE}")
    rng = np.random.default_rng(42)
    vendors = np.array([f"vendor_{i:03d}" for i in range(200)])
    t = pa.table(
        {
            "trip_id": pa.array(np.arange(ROWS, dtype=np.int64)),
            "vendor": pa.array(vendors[rng.integers(0, len(vendors), ROWS)]),
            "ts": pa.array(
                (1_600_000_000_000_000 + np.cumsum(rng.integers(0, 1000, ROWS))).astype(
                    np.int64
                )
            ),
        }
    )
    pq.write_table(
        t,
        CACHE,
        compression="snappy",
        row_group_size=1 << 20,
        use_dictionary=["vendor"],
        column_encoding={"trip_id": "PLAIN", "ts": "DELTA_BINARY_PACKED"},
    )
    log(f"bench: file size {CACHE.stat().st_size / 1e6:.1f} MB")
    return CACHE


# -- the two delivery paths ----------------------------------------------------


def deliver_baseline(path):
    """Host decode, then upload decoded columns — block until resident."""
    import jax
    import jax.numpy as jnp

    from parquet_tpu.core.arrays import ByteArrayData
    from parquet_tpu.core.reader import FileReader

    out = []
    with FileReader(path, backend="host") as r:
        for i in range(r.num_row_groups):
            for p, chunk in r.read_row_group(i).items():
                v = chunk.values
                if isinstance(v, ByteArrayData):
                    out.append(
                        (
                            p,
                            jnp.asarray(np.frombuffer(v.data, dtype=np.uint8)),
                            jnp.asarray(v.offsets),
                        )
                    )
                else:
                    arr = np.asarray(v)
                    if arr.dtype.kind == "f":
                        u = np.uint32 if arr.itemsize == 4 else np.uint64
                        out.append((p, jnp.asarray(arr.view(u))))
                    else:
                        out.append((p, jnp.asarray(arr)))
    jax.block_until_ready([a for item in out for a in item[1:]])
    return out


def deliver_device(path):
    """Encoded upload + device decode — block until resident."""
    import jax

    from parquet_tpu.core.reader import FileReader

    out = []
    arrays = []
    with FileReader(path) as r:
        for rg in r.read_row_groups_device():
            for p, dc in rg.items():
                out.append((p, dc))
                for a in (dc.values, dc.indices, dc.data, dc.offsets, dc.dict_data, dc.dict_offsets):
                    if a is not None:
                        arrays.append(a)
    jax.block_until_ready(arrays)
    return out


def deliver_pyarrow(path):
    """External-implementation baseline: pyarrow (Arrow C++) decodes, then
    the decoded Arrow buffers upload to the device — the strongest host
    decoder a JAX user could reach for today, at the same delivery point."""
    import jax
    import jax.numpy as jnp
    import pyarrow.parquet as pq_mod

    t = pq_mod.read_table(path)
    arrays = []
    for name in t.column_names:
        col = t.column(name).combine_chunks()
        for chunk in col.chunks if hasattr(col, "chunks") else [col]:
            for buf in chunk.buffers():
                if buf is not None and buf.size:
                    arrays.append(jnp.asarray(np.frombuffer(buf, dtype=np.uint8)))
    jax.block_until_ready(arrays)
    return arrays


def verify_deliveries(path) -> None:
    """Both paths must deliver the same logical columns."""
    from parquet_tpu.core.arrays import ByteArrayData
    from parquet_tpu.core.reader import FileReader

    with FileReader(path, backend="host") as r:
        host = [r.read_row_group(i) for i in range(r.num_row_groups)]
    with FileReader(path) as r:
        dev = [r.read_row_group_device(i) for i in range(r.num_row_groups)]
    for rg_h, rg_d in zip(host, dev):
        assert rg_h.keys() == rg_d.keys()
        for p in rg_h:
            h, d = rg_h[p], rg_d[p]
            if d.indices is not None:
                got = d.dictionary.take(np.asarray(d.indices).astype(np.int64))
                assert isinstance(h.values, ByteArrayData)
                assert np.array_equal(got.offsets, h.values.offsets), p
                assert got.data == h.values.data, p
            elif d.offsets is not None:
                assert isinstance(h.values, ByteArrayData)
                assert np.array_equal(np.asarray(d.offsets), h.values.offsets), p
                assert bytes(np.asarray(d.data)) == h.values.data, p
            else:
                got = np.asarray(d.values)
                want = np.asarray(h.values)
                assert got.dtype == want.dtype, (p, got.dtype, want.dtype)
                assert np.array_equal(
                    got.view((np.uint8, got.dtype.itemsize)),
                    want.view((np.uint8, want.dtype.itemsize)),
                ), p
    log("bench: deliveries logically identical (host+upload vs device decode) ✓")


def decode_all_host(path):
    from parquet_tpu.core.reader import FileReader

    with FileReader(path, backend="host") as r:
        return [r.read_row_group(i) for i in range(r.num_row_groups)]


def decode_all_tpu_to_host(path):
    """Explicit device decode + fetch-back (backend="tpu" itself auto-routes
    host-bound reads to the host path; the roundtrip backend is the parity
    oracle and the honest measure of fetch-back cost)."""
    from parquet_tpu.core.reader import FileReader

    with FileReader(path, backend="tpu_roundtrip") as r:
        return [r.read_row_group(i) for i in range(r.num_row_groups)]


# -- the BASELINE.md 5-config matrix ------------------------------------------
#
# Per-config rows/s + bytes/s (encoded and decoded) + byte-equality, per the
# first-milestone deliverable table in BASELINE.md. Each config runs in its
# own subprocess (same isolation rationale as the phases below) and orders
# device timing BEFORE any device->host fetch so the verification fetch can't
# poison the measured transfer path.

MATRIX_ROWS = int(os.environ.get("PQT_MATRIX_ROWS", 1_000_000))


def _matrix_table(cfg: int, rows: int):
    import pyarrow as pa

    rng = np.random.default_rng(cfg)
    if cfg == 1:  # PLAIN int64, flat, uncompressed, DataPage V1
        return pa.table({"v": pa.array(rng.integers(0, 1 << 60, rows), pa.int64())})
    if cfg == 2:  # hybrid (dict-index) int32, SNAPPY, DataPage V2
        return pa.table({"v": pa.array(rng.integers(0, 1000, rows).astype(np.int32))})
    if cfg == 3:  # dict STRING, 100K-key dictionary
        keys = np.array([f"key_{i:06d}" for i in range(100_000)])
        return pa.table({"v": pa.array(keys[rng.integers(0, len(keys), rows)])})
    if cfg == 4:  # DELTA_BINARY_PACKED int64 timestamps, GZIP
        ts = 1_600_000_000_000_000 + np.cumsum(rng.integers(0, 1000, rows))
        return pa.table({"v": pa.array(ts.astype(np.int64))})
    if cfg == 5:  # nested LIST<int32> via the floor-equivalent reader
        lengths = rng.integers(0, 5, rows)
        flat = rng.integers(0, 1 << 30, int(lengths.sum())).astype(np.int32)
        offsets = np.zeros(rows + 1, dtype=np.int64)
        np.cumsum(lengths, out=offsets[1:])
        return pa.table(
            {"v": pa.ListArray.from_arrays(pa.array(offsets, pa.int32()), pa.array(flat))}
        )
    raise ValueError(cfg)


def _matrix_write_opts(cfg: int) -> dict:
    if cfg == 1:
        return dict(compression="none", column_encoding={"v": "PLAIN"}, use_dictionary=False, data_page_version="1.0")
    if cfg == 2:
        return dict(compression="snappy", use_dictionary=["v"], data_page_version="2.0")
    if cfg == 3:
        # raise pyarrow's 1MB dictionary-page ceiling: the config SPEC is a
        # dictionary-encoded column with 100K keys (~1.1MB of values), and
        # the default limit silently spills half the pages to PLAIN
        return dict(compression="snappy", use_dictionary=["v"], data_page_version="1.0",
                    dictionary_pagesize_limit=16 << 20)
    if cfg == 4:
        return dict(compression="gzip", column_encoding={"v": "DELTA_BINARY_PACKED"}, use_dictionary=False, data_page_version="1.0")
    return dict(compression="snappy", data_page_version="1.0")


def _matrix_file(cfg: int) -> Path:
    import hashlib

    import pyarrow.parquet as pq

    # cache key includes the write options so editing a config invalidates
    # the cached fixture instead of silently benchmarking the stale file
    tag = hashlib.sha1(repr(sorted(_matrix_write_opts(cfg).items())).encode()).hexdigest()[:10]
    path = Path(f"/tmp/pqt_matrix_{cfg}_{MATRIX_ROWS}_{tag}.parquet")
    if not path.exists():
        pq.write_table(
            _matrix_table(cfg, MATRIX_ROWS), path, row_group_size=1 << 20, **_matrix_write_opts(cfg)
        )
    return path


def _decoded_bytes(chunks_list) -> int:
    from parquet_tpu.core.arrays import ByteArrayData

    total = 0
    for chunks in chunks_list:
        for c in chunks.values():
            v = c.values
            if isinstance(v, ByteArrayData):
                total += len(v.data) + v.offsets.nbytes
            else:
                total += np.asarray(v).nbytes
    return total


def _phase_matrix(cfg: int) -> None:
    """One matrix config: device + baseline timings, then byte-equality.

    Timing reuses the headline delivery functions (deliver_device /
    deliver_baseline) so the matrix and headline measure the identical
    delivery point."""
    from parquet_tpu.core.reader import FileReader

    path = _matrix_file(cfg)
    rows = MATRIX_ROWS

    deliver_device(path)  # warm (compile cache + connection)
    s_dev = timed_stats(lambda: deliver_device(path), REPEATS, f"cfg{cfg} device", rows=rows)
    s_base = timed_stats(
        lambda: deliver_baseline(path), REPEATS, f"cfg{cfg} baseline", rows=rows
    )
    s_pa = timed_stats(
        lambda: deliver_pyarrow(path), REPEATS, f"cfg{cfg} pyarrow", rows=rows
    )
    t_dev, t_base, t_pa = s_dev["t"], s_base["t"], s_pa["t"]
    t_rows = None
    t_arrow = None
    if cfg == 5:
        # the floor-equivalent read: nested LIST assembly on host over the
        # decoded leaf (BASELINE.md config 5's mixed host/TPU shape)
        def assembled():
            with FileReader(path) as r:
                return sum(1 for _ in r.iter_rows())

        t_rows = timed(assembled, REPEATS, f"cfg{cfg} assembled-rows", rows=rows)

        # the columnar nested lane (vectorized Dremel-levels -> Arrow): the
        # product path for bulk nested reads; dict-row materialization above
        # is bounded by CPython object allocation (~200ns/row just for the
        # row dicts), this one is not
        def columnar():
            with FileReader(path) as r:
                return r.to_arrow().num_rows

        t_arrow = timed(columnar, REPEATS, f"cfg{cfg} to-arrow", rows=rows)

    # verification LAST (fetches poison the transfer path)
    with FileReader(path, backend="host") as r:
        host = [r.read_row_group(i) for i in range(r.num_row_groups)]
    with FileReader(path, backend="tpu_roundtrip") as r:
        rt = [r.read_row_group(i) for i in range(r.num_row_groups)]
    try:
        _verify_host_paths(host, rt)
        equal = True
    except AssertionError as e:
        log(f"bench: cfg{cfg} parity FAILED: {e}")
        equal = False
    enc = path.stat().st_size
    dec = _decoded_bytes(host)
    out = {
        "config": cfg,
        "rows_s_device": round(rows / t_dev, 1),
        "rows_s_baseline": round(rows / t_base, 1),
        "rows_s_pyarrow": round(rows / t_pa, 1),
        "vs_baseline": round(t_base / t_dev, 3),
        "vs_pyarrow": round(t_pa / t_dev, 3),
        "encoded_MB_s": round(enc / t_dev / 1e6, 1),
        "decoded_MB_s": round(dec / t_dev / 1e6, 1),
        "byte_equal": bool(equal),
        # medians over REPEATS samples; every sample recorded so the prose
        # can be audited against the artifact
        "stat": "median",
        "samples_device_s": s_dev["samples"],
        "samples_baseline_s": s_base["samples"],
        "samples_pyarrow_s": s_pa["samples"],
    }
    if t_rows is not None:
        out["rows_s_assembled"] = round(rows / t_rows, 1)
    if t_arrow is not None:
        out["rows_s_to_arrow"] = round(rows / t_arrow, 1)
    _emit(out)


WRITE_ROWS = int(os.environ.get("PQT_WRITE_ROWS", 400_000))


def _phase_write() -> None:
    """Write-path benchmark (matrix config "write"; `bench.py --write`).

    Part 1 (headline): rows/s writing the headline-like 3-column table
    (dict-int64 + dict-string + delta-ts) with our FileWriter vs
    pyarrow.write_table, both SNAPPY. Output is verified by reading it back
    with pyarrow (cross-implementation) before timing.

    Part 2 (parallelism sweep): the pqt-encode pipeline vs the serial
    writer on a log-ingest-shaped table (PQT_WRITE_ROWS rows: random int64
    id, ~90-byte log-line strings, delta timestamps, random doubles; GZIP,
    no dictionary — the archival-ingest shape where encode+compress
    dominate and the encode work is native/GIL-free). Sweeps pool size
    1/4/8 x row-group count 8/16; every parallel output is asserted
    BYTE-IDENTICAL to the serial file before any timing run. The result
    rides the --json artifact's "write" section."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu.core.writer import FileWriter
    from parquet_tpu.schema.dsl import parse_schema

    rows = MATRIX_ROWS
    rng = np.random.default_rng(99)
    ints = rng.integers(0, 1000, rows).astype(np.int64)
    keys = np.array([f"key_{i:05d}" for i in range(5000)])
    strs = keys[rng.integers(0, len(keys), rows)]
    ts = (1_600_000_000_000_000 + np.cumsum(rng.integers(0, 1000, rows))).astype(
        np.int64
    )
    table = pa.table({"i": pa.array(ints), "s": pa.array(strs), "ts": pa.array(ts)})
    schema = parse_schema(
        "message m { required int64 i; required binary s (UTF8); "
        "required int64 ts (TIMESTAMP_MICROS); }"
    )
    strs_l = strs.tolist()

    def ours():
        with FileWriter(
            "/tmp/pqt_bench_write_ours.parquet",
            schema,
            codec="snappy",
            column_encodings={"ts": "DELTA_BINARY_PACKED"},
        ) as w:
            w.write_column("i", ints)
            w.write_column("s", strs_l)
            w.write_column("ts", ts)

    def ours_arrow():
        # same input class as pyarrow gets (arrow arrays, zero-copy ingest)
        with FileWriter(
            "/tmp/pqt_bench_write_ours_arrow.parquet",
            schema,
            codec="snappy",
            column_encodings={"ts": "DELTA_BINARY_PACKED"},
        ) as w:
            w.write_column("i", table.column("i"))
            w.write_column("s", table.column("s"))
            w.write_column("ts", table.column("ts"))

    # correctness FIRST: pyarrow must read our output back identically
    ours()
    ours_arrow()
    for f in (
        "/tmp/pqt_bench_write_ours.parquet",
        "/tmp/pqt_bench_write_ours_arrow.parquet",
    ):
        got = pq.read_table(f)
        assert got.column("i").to_pylist() == ints.tolist()
        assert got.column("s").to_pylist() == strs_l
        assert got.column("ts").cast(pa.int64()).to_pylist() == ts.tolist()
    log("bench: write output verified by pyarrow readback ✓")

    s_ours = timed_stats(ours, REPEATS, "write ours", rows=rows)
    s_ours_arrow = timed_stats(ours_arrow, REPEATS, "write ours(arrow-in)", rows=rows)
    s_pa = timed_stats(
        lambda: pq.write_table(
            table, "/tmp/pqt_bench_write_pa.parquet", compression="snappy"
        ),
        REPEATS,
        "write pyarrow",
        rows=rows,
    )
    t_ours, t_ours_arrow, t_pa = s_ours["t"], s_ours_arrow["t"], s_pa["t"]
    out = {
        "config": "write",
        "rows_s_ours": round(rows / t_ours, 1),
        "rows_s_ours_arrow_in": round(rows / t_ours_arrow, 1),
        "rows_s_pyarrow": round(rows / t_pa, 1),
        "vs_pyarrow": round(t_pa / t_ours, 3),
        "vs_pyarrow_arrow_in": round(t_pa / t_ours_arrow, 3),
        "written_MB": round(
            Path("/tmp/pqt_bench_write_ours.parquet").stat().st_size / 1e6, 1
        ),
        "readback_ok": True,
        "stat": "median",
        "samples_ours_s": s_ours["samples"],
        "samples_ours_arrow_in_s": s_ours_arrow["samples"],
        "samples_pyarrow_s": s_pa["samples"],
    }

    # -- part 2: the pqt-encode parallelism sweep ------------------------------
    wrows = WRITE_ROWS
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 1 << 60, wrows).astype(np.int64)
    hexes = rng.integers(0, 1 << 40, wrows)
    logs = pa.array(
        [
            f"2026-08-03T12:00:00Z level=info svc=ingest "
            f"shard-{int(h) % 64:02d} req={int(h):012x} status=200"
            for h in hexes
        ]
    )
    wts = (
        1_600_000_000_000_000 + np.cumsum(rng.integers(0, 1000, wrows))
    ).astype(np.int64)
    wx = rng.random(wrows)
    wschema = parse_schema(
        "message m { required int64 id; required binary s (UTF8); "
        "required int64 ts (TIMESTAMP_MICROS); required double x; }"
    )

    def write_ingest(path, parallel, n_groups):
        with FileWriter(
            path,
            wschema,
            codec="gzip",
            column_encodings={"ts": "DELTA_BINARY_PACKED"},
            use_dictionary=False,
            parallel=parallel,
        ) as w:
            per = wrows // n_groups
            for g in range(n_groups):
                a = g * per
                b = wrows if g == n_groups - 1 else (g + 1) * per
                w.write_column("id", ids[a:b])
                w.write_column("s", logs.slice(a, b - a))
                w.write_column("ts", wts[a:b])
                w.write_column("x", wx[a:b])
                w.flush_row_group()

    # PAIRED sampling: every repeat times the serial writer and then each
    # pool config back to back, and the reported speedup is the MEDIAN OF
    # PAIRED RATIOS. On a shared box the load drift between runs dwarfs the
    # config effect (observed serial spread ~1.3x across minutes); pairing
    # puts both sides of each ratio in the same load window, the same
    # rationale that picked medians over best-of (VERDICT r3).
    pools = (1, 4, 8)
    sweep = {}
    best_speedup = 0.0
    for n_groups in (8, 16):
        ser_path = f"/tmp/pqt_write_serial_{n_groups}.parquet"
        write_ingest(ser_path, False, n_groups)  # warm + the identity oracle
        ser_bytes = Path(ser_path).read_bytes()
        for pool in pools:  # warm each pool config + the identity check
            par_path = f"/tmp/pqt_write_pool{pool}_{n_groups}.parquet"
            write_ingest(par_path, pool, n_groups)
            if Path(par_path).read_bytes() != ser_bytes:
                # a divergence is a correctness bug, not a data point:
                # timing divergent configs would launder it into the artifact
                raise SystemExit(
                    f"bench: write pool={pool} g={n_groups} output is NOT "
                    "byte-identical to the serial writer"
                )
        ser_samples = []
        par_samples = {p: [] for p in pools}
        ratios = {p: [] for p in pools}
        for rep in range(REPEATS):
            t0 = time.perf_counter()
            write_ingest(ser_path, False, n_groups)
            t_s = time.perf_counter() - t0
            ser_samples.append(round(t_s, 5))
            for pool in pools:
                par_path = f"/tmp/pqt_write_pool{pool}_{n_groups}.parquet"
                t0 = time.perf_counter()
                write_ingest(par_path, pool, n_groups)
                t_p = time.perf_counter() - t0
                par_samples[pool].append(round(t_p, 5))
                ratios[pool].append(t_s / t_p)
            log(
                f"bench:   write g={n_groups} rep {rep + 1}/{REPEATS}: "
                f"serial {t_s:.3f}s, " + ", ".join(
                    f"pool{p} {par_samples[p][-1]:.3f}s "
                    f"({ratios[p][-1]:.2f}x)" for p in pools
                )
            )
        med_ser = sorted(ser_samples)[len(ser_samples) // 2]
        entry = {
            "serial_rows_s": round(wrows / med_ser, 1),
            "serial_samples_s": ser_samples,
        }
        for pool in pools:
            med_par = sorted(par_samples[pool])[len(par_samples[pool]) // 2]
            r = sorted(ratios[pool])[len(ratios[pool]) // 2]
            entry[f"pool_{pool}"] = {
                "rows_s": round(wrows / med_par, 1),
                "speedup": round(r, 3),  # median of PAIRED ratios
                "samples_s": par_samples[pool],
            }
            if pool >= 4 and n_groups >= 8:
                best_speedup = max(best_speedup, round(r, 3))
        sweep[f"groups_{n_groups}"] = entry
    out["parallel_rows"] = wrows
    out["parallel_codec"] = "gzip"
    out["parallel_sweep"] = sweep
    # every config was asserted byte-identical above (divergence exits)
    out["parallel_byte_identical"] = True
    # the acceptance pin: best (pool >= 4, >= 8 groups) config vs serial
    out["parallel_speedup"] = best_speedup
    log(
        f"bench: write parallel sweep: best pool>=4 speedup "
        f"{best_speedup:.2f}x vs serial (all configs byte-identical)"
    )
    _emit(out)


def run_matrix() -> list:
    results = []
    for cfg in (1, 2, 3, 4, 5):
        _matrix_file(cfg)  # build outside the timed subprocess
        r = _run_phase(f"matrix{cfg}")
        log(f"bench: matrix config {cfg}: {json.dumps(r)}")
        results.append(r)
    r = _run_phase("write")
    if r is not None:
        log(f"bench: matrix config write: {json.dumps(r)}")
        results.append(r)
    else:
        log("bench: write config FAILED")
    return results


def timed(fn, repeats: int, label: str, rows: int | None = None) -> float:
    """Median-of-repeats wall time (all samples logged; see timed_stats)."""
    return timed_stats(fn, repeats, label, rows)["t"]


def timed_stats(fn, repeats: int, label: str, rows: int | None = None) -> dict:
    """Run fn `repeats` times; report the MEDIAN with min/max and every
    sample. Medians, not best-of: host-side run-to-run drift is the
    dominant noise here, and a best-of headline overstates what a user
    sees (VERDICT r3: single-run entries can't support prose claims)."""
    rows = ROWS if rows is None else rows
    samples = []
    for i in range(repeats):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        log(f"bench:   {label} run {i + 1}/{repeats}: {dt:.3f}s ({rows / dt / 1e6:.2f} M rows/s)")
        samples.append(dt)
    s = sorted(samples)
    med = s[len(s) // 2] if len(s) % 2 else 0.5 * (s[len(s) // 2 - 1] + s[len(s) // 2])
    return {
        "t": med,
        "t_min": s[0],
        "t_max": s[-1],
        "samples": [round(x, 5) for x in samples],
    }


# -- phase isolation -----------------------------------------------------------
#
# Every measurement phase runs in its OWN subprocess, one after the other,
# and this parent never imports jax: a TPU belongs to one process at a time,
# so a parent that had probed the device would hold it and every child that
# needs it would fail or hang. The first device phase (verify) is the probe.
# All children share one persistent XLA compile cache (kernels/device_ops.py),
# so only the first pays for a program. Whether per-phase processes stay is
# ROADMAP 1.1's call.

# Phases of a full run that deliver to, or fetch from, the device. A child
# running one refuses a non-TPU default device (unless JAX_PLATFORMS=cpu
# asks for the CPU by name) and a missing native library; the parent fails
# the run when one exits non-zero — a device number is never quietly
# replaced by a host one. (`--device`, outside the full run, requires the
# device itself.)
_DEVICE_PHASES = frozenset(
    {"verify", "tpu_host", "baseline", "device", "pyarrow"}
    | {f"matrix{c}" for c in (1, 2, 3, 4, 5)}
)
_DEVICE = None  # device facts of this (child) process, stamped on results


def _require_device() -> dict:
    """Device facts for a device phase; raises when the device path would
    not be the one measured (no TPU, or no native walk/GIL-free binding)."""
    global _DEVICE
    from parquet_tpu.kernels.device_ops import require_chip
    from parquet_tpu.utils.native import require_native

    require_native()
    _DEVICE = require_chip()
    log(f"bench: device {_DEVICE}")
    return _DEVICE


def _phase_verify(path) -> None:
    verify_deliveries(path)
    host = decode_all_host(path)
    tpu = decode_all_tpu_to_host(path)
    _verify_host_paths(host, tpu)
    _emit({"ok": True})


def _phase_prepare() -> None:
    """Host-prepare microbench (`make bench-prepare`): the serial prepare wall
    named in BASELINE.md, split per stage by the fused native walk's internal
    clocks (decompress / levels / prescan / copy), plus thread scaling of the
    GIL-free path. Host-only — runs with or without an accelerator."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # host-only: needs no device
    path = build_file()
    import concurrent.futures as cf

    from parquet_tpu.core.chunk import ChunkWindow, chunk_byte_range
    from parquet_tpu.core.reader import FileReader
    from parquet_tpu.kernels.pipeline import prepare_chunk_plan
    from parquet_tpu.utils.trace import decode_trace

    with FileReader(path) as r:
        rows = int(r.metadata.num_rows or 0)
        work = []
        for i in range(r.num_row_groups):
            for _p, cc, column in r._selected_chunks(i):
                off, total = chunk_byte_range(cc)
                work.append((r._pread(off, total), off, cc, column))

    def prep_one(item):
        buf, off, cc, column = item
        return prepare_chunk_plan(ChunkWindow(buf, off), cc, column)

    def prep_all():
        for it in work:
            prep_one(it)

    prep_all()  # warm: lazy imports, native load, per-thread buffer pools
    with decode_trace() as tr:
        t0 = time.perf_counter()
        prep_all()
        serial_probe = time.perf_counter() - t0
    stages = {
        name: round(s.seconds * 1e3, 3)
        for name, s in sorted(tr.stages.items())
        if name.startswith("prepare.")
    }
    engaged = tr.stages.get("prepare_fused_engaged")
    declined = tr.stages.get("prepare_fused_declined")
    serial = timed_stats(prep_all, REPEATS, "prepare-serial", rows)["t"]

    # thread scaling: the same chunk list split over N workers; the fused
    # walk holds no lock and no GIL, so wall should shrink ~linearly until
    # memory bandwidth saturates
    scaling = {}
    ncpu = os.cpu_count() or 1
    for nthreads in sorted({2, 4, min(8, ncpu), ncpu}):
        if nthreads < 2 or nthreads > ncpu:
            continue
        with cf.ThreadPoolExecutor(max_workers=nthreads) as pool:
            list(pool.map(prep_one, work))  # per-thread warmup (scratch pools)
            t0 = time.perf_counter()
            list(pool.map(prep_one, work))
            wall = time.perf_counter() - t0
        scaling[str(nthreads)] = {
            "t": round(wall, 5),
            "effective_cores": round(serial / wall, 2),
        }
    from parquet_tpu.utils.native import require_native

    # the fused walk under the extension binding (explicit
    # Py_BEGIN_ALLOW_THREADS) is what this phase measures; the ctypes
    # fallback or the per-page Python walk would be a different program
    require_native()
    out = {
        "rows": rows,
        "gil_free_binding": True,
        "prepare_serial_s": round(serial, 5),
        "prepare_serial_probe_s": round(serial_probe, 5),
        "prepare_ms_per_1m_rows": round(serial / max(rows, 1) * 1e6 * 1e3, 3),
        "rows_s_prepare": round(rows / serial, 1),
        "stage_ms": stages,
        "fused_engaged": engaged.calls if engaged else 0,
        "fused_declined": declined.calls if declined else 0,
        "thread_scaling": scaling,
    }
    log(f"bench: prepare breakdown {out}")
    _emit(out)


# -- the record-assembly benchmark (--assembly / phase "assembly") -------------

ASSEMBLY_ROWS = int(os.environ.get("PQT_ASSEMBLY_ROWS", 300_000))


def _assembly_tables(rows: int) -> dict:
    """flat / 1-level / 2-level tables for the assembly-engine sweep. The
    1-level config reproduces the matrix cfg5 shape (LIST<int32>, avg 2
    elements, empties) PLUS a null mask over ~1/16 of the rows, so the
    pre-timing vec==scalar identity assert also covers the null-list
    (slices-mask) path cfg5 itself never exercises."""
    import pyarrow as pa

    rng = np.random.default_rng(5)
    flat = pa.table(
        {
            "i": pa.array(rng.integers(0, 1 << 50, rows), pa.int64()),
            "f": pa.array(rng.standard_normal(rows)),
            "s": pa.array(
                [None if k % 11 == 0 else f"v{k % 97}" for k in range(rows)]
            ),
        }
    )
    lengths = rng.integers(0, 5, rows)
    null_rows = rng.integers(0, 16, rows) == 0
    lengths[null_rows] = 0
    flat_vals = rng.integers(0, 1 << 30, int(lengths.sum())).astype(np.int32)
    offsets = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    lst = pa.table(
        {
            "v": pa.ListArray.from_arrays(
                pa.array(offsets, pa.int32()),
                pa.array(flat_vals),
                mask=pa.array(null_rows),
            )
        }
    )
    ll = pa.table(
        {
            "ll": pa.array(
                [
                    None
                    if i % 13 == 0
                    else [list(range(j % 3)) for j in range(i % 4)]
                    for i in range(rows)
                ],
                pa.list_(pa.list_(pa.int64())),
            )
        }
    )
    return {"flat": flat, "list1": lst, "list2": ll}


def _phase_assembly() -> None:
    """Record-assembly engine sweep: the vectorized level-scan engine
    (core/assembly_vec) vs the scalar cursor walk vs pyarrow to_pylist, on
    flat / 1-level / 2-level tables. Vec and scalar assemble from the SAME
    pre-decoded chunks (pure engine time, gc paused like the production
    reader's windows); pyarrow's to_pylist includes its own decode — it is
    the external "rows in Python" comparator, not an engine isolate. Vec
    output is asserted identical to scalar BEFORE any timing. The result
    rides the --json artifact under "assembly"."""
    import gc

    import pyarrow.parquet as pq

    from parquet_tpu.core.assembly import RecordAssembler
    from parquet_tpu.core.assembly_vec import assemble_rows
    from parquet_tpu.core.reader import FileReader

    rows = ASSEMBLY_ROWS
    scalar_repeats = max(1, REPEATS - 2)
    out = {"config": "assembly", "rows": rows, "tables": {}}
    for name, table in _assembly_tables(rows).items():
        path = Path(f"/tmp/pqt_assembly_{name}_{rows}.parquet")
        pq.write_table(table, path, row_group_size=1 << 20, compression="snappy")
        with FileReader(str(path)) as r:
            chunks = [r.read_row_group(i) for i in range(r.num_row_groups)]
            schema = r.schema

        def vec_all():
            gc.disable()
            try:
                return [assemble_rows(schema, c, False) for c in chunks]
            finally:
                gc.enable()

        def scalar_all():
            gc.disable()
            try:
                return [
                    list(RecordAssembler(schema, c, raw=False, engine="scalar"))
                    for c in chunks
                ]
            finally:
                gc.enable()

        # identity BEFORE timing: the engines must agree on every row
        v, s = vec_all(), scalar_all()
        assert all(g is not None for g in v), f"{name}: vec engine declined"
        assert v == s, f"{name}: vec rows differ from scalar rows"
        del v, s

        t_vec = timed(vec_all, REPEATS, f"assembly {name} vec", rows=rows)
        t_scl = timed(
            scalar_all, scalar_repeats, f"assembly {name} scalar", rows=rows
        )
        t_pa = timed(
            lambda: pq.read_table(path).to_pylist(),
            REPEATS,
            f"assembly {name} pyarrow",
            rows=rows,
        )
        out["tables"][name] = {
            "rows_s_vec": round(rows / t_vec, 1),
            "rows_s_scalar": round(rows / t_scl, 1),
            "rows_s_pyarrow": round(rows / t_pa, 1),
            "vs_scalar": round(t_scl / t_vec, 2),
            "vs_pyarrow": round(t_pa / t_vec, 2),
            "t_vec": round(t_vec, 4),
            "t_scalar": round(t_scl, 4),
            "t_pyarrow": round(t_pa, 4),
        }
        log(
            f"bench: assembly {name}: vec {rows / t_vec / 1e6:.2f} M rows/s | "
            f"scalar {rows / t_scl / 1e6:.3f} M rows/s | pyarrow "
            f"{rows / t_pa / 1e6:.2f} M rows/s | vec/scalar "
            f"{t_scl / t_vec:.1f}x | vec/pyarrow {t_pa / t_vec:.1f}x"
        )
    # the acceptance pin: >= 10x over the scalar engine on the cfg5-style
    # 1-level nested table
    out["nested_vec_vs_scalar"] = out["tables"]["list1"]["vs_scalar"]
    _emit(out)


# -- the IO-layer benchmark (--io / phase "io") --------------------------------

IO_ROWS = int(os.environ.get("PQT_IO_ROWS", 400_000))
IO_LAT_MS = float(os.environ.get("PQT_IO_LAT_MS", "0.3"))


def _io_file() -> Path:
    """An 8-column fixture for the io sweeps: wide enough that a projected
    read leaves real gaps between selected chunks (what coalescing has to
    decide about) and several row groups so readahead has a pipeline."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = Path(f"/tmp/pqt_io_{IO_ROWS}.parquet")
    if not path.exists():
        rng = np.random.default_rng(11)
        log(f"bench: generating {IO_ROWS:,}-row 8-column io fixture at {path}")
        t = pa.table(
            {
                f"c{k}": pa.array(
                    rng.integers(0, 1 << 40, IO_ROWS).astype(np.int64)
                )
                for k in range(8)
            }
        )
        pq.write_table(
            t, path, compression="snappy", row_group_size=1 << 16,
            use_dictionary=False,
        )
    return path


def _phase_io() -> None:
    """IO-layer sweeps against a latency-injected flaky source.

    Models an object-store read: every source read pays PQT_IO_LAT_MS of
    injected latency (the range-GET shape) plus a small transient-EIO rate
    the retry ladder must absorb. Sweep 1 holds the projection fixed
    (4 of 8 columns — real gaps between selected chunks) and sweeps the
    coalesce gap 0 / 64 KiB / 1 MiB: wall time falls as read calls merge.
    Sweep 2 fixes the gap and sweeps readahead depth 0/2/4 row groups via
    the pqt-io scheduler fetching into a shared block cache ahead of
    decode. Host-only; the result rides the --json artifact as "io"."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from parquet_tpu.core.reader import FileReader
    from parquet_tpu.io import (
        BlockCache,
        LocalFileSource,
        Readahead,
        RetryingSource,
        plan_ranges,
    )
    from parquet_tpu.testing.flaky import FlakySource
    from parquet_tpu.utils import metrics

    path = _io_file()
    cols = [f"c{k}" for k in range(0, 8, 2)]  # 4-of-8 projection: gappy
    lat_s = IO_LAT_MS / 1e3

    def flaky(seed=3):
        return RetryingSource(
            FlakySource(
                LocalFileSource(path), seed=seed, error_rate=0.02,
                latency_s=lat_s,
            ),
            attempts=5,
            base_delay_s=0.001,
            max_delay_s=0.01,
            seed=seed,
        )

    def read_all(gap, cache_bytes=0, readahead_depth=0):
        # a FRESH cache per run: a warm cache across repeats would measure
        # memory hits, not the readahead overlap under test
        cache = BlockCache(cache_bytes) if cache_bytes else None
        src = flaky()
        try:
            with FileReader(
                src, columns=cols, block_cache=cache, coalesce_gap=gap
            ) as r:
                ra = None
                ra_srcs = []
                if readahead_depth and cache is not None:
                    ra = Readahead(cache, gap=gap)
                    paths = {tuple(c.split(".")) for c in cols}
                    spans = [
                        plan_ranges(
                            r.metadata, row_groups=[g], columns=paths
                        )
                        for g in range(r.num_row_groups)
                    ]
                rows = 0
                scheduled = set()
                for g in range(r.num_row_groups):
                    if ra is not None:
                        for j in range(g + 1, min(g + 1 + readahead_depth,
                                                  r.num_row_groups)):
                            if j in scheduled:
                                continue
                            scheduled.add(j)
                            # one PRIVATE source per scheduled fetch: the
                            # seeded fault/latency rngs are not thread-safe,
                            # so sharing `src` with pqt-io workers would make
                            # the schedule racy and the sweep irreproducible
                            s2 = flaky(seed=100 + j)
                            ra_srcs.append(s2)
                            ra.schedule(s2, spans[j])
                    cols_g = r.read_row_group(g)
                    rows += next(iter(cols_g.values())).num_values
                if ra is not None:
                    ra.drain()
                for s2 in ra_srcs:
                    s2.close()
                return rows
        finally:
            src.close()

    out = {
        "config": "io",
        "rows": IO_ROWS,
        "file_mb": round(path.stat().st_size / 1e6, 2),
        "projection": cols,
        "latency_ms_per_read": IO_LAT_MS,
        "stat": "median",
    }
    gap_sweep = {}
    for gap in (0, 64 << 10, 1 << 20):
        s0 = metrics.snapshot()
        t = timed_stats(
            lambda g=gap: read_all(g), REPEATS, f"io gap={gap}", rows=IO_ROWS
        )
        d = metrics.delta(s0)
        gap_sweep[str(gap)] = {
            "t": t["t"],
            "rows_s": round(IO_ROWS / t["t"], 1),
            "read_calls": d.get("io_read_calls_total", 0) // REPEATS,
            "bytes_read": d.get("io_bytes_read_total", 0) // REPEATS,
            "retries": sum(
                v for k, v in d.items() if k.startswith("io_retries_total")
            ),
            "samples_s": t["samples"],
        }
    out["gap_sweep"] = gap_sweep
    ra_sweep = {}
    for depth in (0, 2, 4):
        s0 = metrics.snapshot()
        t = timed_stats(
            lambda d=depth: read_all(64 << 10, cache_bytes=256 << 20,
                                     readahead_depth=d),
            REPEATS, f"io readahead={depth}", rows=IO_ROWS,
        )
        d = metrics.delta(s0)
        hits = d.get("io_cache_hits_total", 0)
        misses = d.get("io_cache_misses_total", 0)
        ra_sweep[str(depth)] = {
            "t": t["t"],
            "rows_s": round(IO_ROWS / t["t"], 1),
            "cache_hit_rate": (
                round(hits / (hits + misses), 4) if hits + misses else None
            ),
            "samples_s": t["samples"],
        }
    out["readahead_sweep"] = ra_sweep
    best_gap = min(gap_sweep, key=lambda k: gap_sweep[k]["t"])
    out["best_gap"] = int(best_gap)
    out["gap_speedup"] = round(
        gap_sweep["0"]["t"] / gap_sweep[best_gap]["t"], 3
    )
    log(
        f"bench: io gap sweep best={best_gap} "
        f"({out['gap_speedup']:.2f}x over gap 0); readahead "
        + ", ".join(
            f"d{k}={v['rows_s'] / 1e6:.2f}M rows/s"
            for k, v in ra_sweep.items()
        )
    )
    _emit(out)


# -- the remote-IO benchmark (--io-remote / phase "io_remote") -----------------

IO_REMOTE_ROWS = int(os.environ.get("PQT_IO_REMOTE_ROWS", 200_000))
IO_REMOTE_RTTS_MS = (0.0, 5.0, 25.0)
IO_REMOTE_REPEATS = int(os.environ.get("PQT_IO_REMOTE_REPEATS", 3))


def _io_remote_file() -> Path:
    """A smaller-row-group variant of the io fixture for the remote sweep:
    ~128 KiB column chunks leave per-group gaps the auto-tuner's
    bandwidth-delay verdict has to decide about at every injected RTT."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = Path(f"/tmp/pqt_io_remote_{IO_REMOTE_ROWS}.parquet")
    if not path.exists():
        rng = np.random.default_rng(13)
        log(
            f"bench: generating {IO_REMOTE_ROWS:,}-row 8-column remote "
            f"fixture at {path}"
        )
        t = pa.table(
            {
                f"c{k}": pa.array(
                    rng.integers(0, 1 << 40, IO_REMOTE_ROWS).astype(np.int64)
                )
                for k in range(8)
            }
        )
        pq.write_table(
            t, path, compression="snappy", row_group_size=1 << 14,
            use_dictionary=False,
        )
    return path


def _phase_io_remote() -> None:
    """Remote-latency profile sweep (`bench.py --io-remote` /
    `make bench-io-remote`).

    Serves the fixture through testing.httpstub (real loopback HTTP,
    range GETs on pooled connections) at injected RTT 0/5/25 ms and scans
    a 4-of-8 projection via io.remote.HttpSource three ways per RTT:

      fixed   the local-profile knobs (64 KiB coalesce gap) — what a
              reader naive about the transport pays
      auto    coalesce_gap="auto": the io.autotune profile observed from
              this sweep's own reads (reset per run) — the acceptance
              pin: auto beats fixed at the 25 ms RTT
      warm    a tiered (RAM->disk) cache filled by one cold auto scan,
              then re-scanned — asserted to read ZERO source bytes (the
              ROADMAP pin) before timing

    Host-only; the result rides the --json artifact as "io_remote"."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from parquet_tpu.core.reader import FileReader
    from parquet_tpu.io import FooterCache, TieredCache, io_tuner
    from parquet_tpu.testing.httpstub import RangeHttpStub
    from parquet_tpu.utils import metrics

    path = _io_remote_file()
    data = path.read_bytes()
    cols = [f"c{k}" for k in range(0, 8, 2)]  # 4-of-8: gappy projection

    def scan(url, gap, fc=None, cache=None) -> int:
        with FileReader(
            url, columns=cols, footer_cache=fc, block_cache=cache,
            coalesce_gap=gap,
        ) as r:
            rows = 0
            for g in range(r.num_row_groups):
                rows += next(iter(r.read_row_group(g).values())).num_values
            assert rows == IO_REMOTE_ROWS
            return rows

    out = {
        "config": "io_remote",
        "rows": IO_REMOTE_ROWS,
        "file_mb": round(len(data) / 1e6, 2),
        "projection": cols,
        "stat": "median",
        "repeats": IO_REMOTE_REPEATS,
    }
    sweep = {}
    for rtt_ms in IO_REMOTE_RTTS_MS:
        with RangeHttpStub(
            files={"c.parquet": data}, latency_s=rtt_ms / 1e3
        ) as stub:
            url = stub.url_for("c.parquet")

            def run(gap):
                # a COLD tuner per SAMPLE (reset inside the timed fn):
                # "auto" must earn its knobs from each scan's own
                # observations, or samples 2..n would measure a
                # pre-trained tuner the comment's "cold" claim belies
                def one_cold_scan():
                    io_tuner().reset()
                    scan(url, gap)

                s0 = metrics.snapshot()
                t = timed_stats(
                    one_cold_scan, IO_REMOTE_REPEATS,
                    f"io-remote rtt={rtt_ms:g}ms gap={gap}",
                    rows=IO_REMOTE_ROWS,
                )
                d = metrics.delta(s0)
                return t, {
                    "t": t["t"],
                    "rows_s": round(IO_REMOTE_ROWS / t["t"], 1),
                    "http_requests": sum(
                        v for k, v in d.items()
                        if k.startswith("io_http_requests_total")
                    ) // IO_REMOTE_REPEATS,
                    "bytes_read": d.get("io_bytes_read_total", 0)
                    // IO_REMOTE_REPEATS,
                }

            _, fixed = run(None)
            _, auto = run("auto")
            # tiered warm: one cold fill, then the warm re-scan (zero
            # source bytes asserted BEFORE timing)
            io_tuner().reset()
            fc = FooterCache()
            with TieredCache(
                ram_bytes=32 << 20, disk_bytes=256 << 20
            ) as cache:
                scan(url, "auto", fc, cache)  # cold fill
                s0 = metrics.snapshot()
                scan(url, "auto", fc, cache)
                d0 = metrics.delta(s0)
                assert d0.get("io_bytes_read_total", 0) == 0, (
                    "warm tiered scan touched the source"
                )
                tw = timed_stats(
                    lambda: scan(url, "auto", fc, cache),
                    IO_REMOTE_REPEATS,
                    f"io-remote rtt={rtt_ms:g}ms warm-tiered",
                    rows=IO_REMOTE_ROWS,
                )
            sweep[f"{rtt_ms:g}"] = {
                "fixed": fixed,
                "auto": auto,
                "auto_speedup": round(fixed["t"] / auto["t"], 3),
                "warm_tiered": {
                    "t": tw["t"],
                    "rows_s": round(IO_REMOTE_ROWS / tw["t"], 1),
                    "zero_source_bytes": True,
                },
            }
    out["rtt_sweep"] = sweep
    hot = sweep[f"{IO_REMOTE_RTTS_MS[-1]:g}"]
    out["auto_speedup_at_max_rtt"] = hot["auto_speedup"]
    out["warm_vs_fixed_at_max_rtt"] = round(
        hot["fixed"]["t"] / hot["warm_tiered"]["t"], 3
    )
    log(
        "bench: io-remote @"
        + ", ".join(
            f"{k}ms auto {v['auto_speedup']:.2f}x fixed"
            f" ({v['fixed']['http_requests']}->{v['auto']['http_requests']}"
            " reqs)"
            for k, v in sweep.items()
        )
        + f"; warm tiered {out['warm_vs_fixed_at_max_rtt']:.1f}x fixed "
        f"at {IO_REMOTE_RTTS_MS[-1]:g}ms (zero source bytes)"
    )
    _emit(out)


# -- the remote-WRITE benchmark (--io-write / phase "io_write") ----------------

IO_WRITE_MB = int(os.environ.get("PQT_IO_WRITE_MB", 32))
IO_WRITE_RTTS_MS = (0.0, 5.0, 25.0)
IO_WRITE_PART_MB = (2, 4, 8)
IO_WRITE_REPEATS = int(os.environ.get("PQT_IO_WRITE_REPEATS", 3))


def _phase_io_write() -> None:
    """Remote write-throughput sweep (`bench.py --io-write` /
    `make bench-io-write`).

    Streams an IO_WRITE_MB payload through io.remote_sink.HttpSink into a
    WRITABLE testing.httpstub (real loopback HTTP, multipart initiate ->
    part PUTs -> complete) at injected RTT 0/5/25 ms, sweeping the
    multipart part size — the knob that trades request count (each part
    pays one RTT) against in-flight memory (part_bytes x max_in_flight).
    Every sample's committed object is asserted BYTE-IDENTICAL to the
    payload before its time counts: a fast write of wrong bytes is not a
    result. Host-only; rides the --json artifact as "io_write"."""
    from parquet_tpu.io.remote_sink import HttpSink
    from parquet_tpu.testing.httpstub import RangeHttpStub
    from parquet_tpu.utils import metrics

    data = (
        np.random.default_rng(23)
        .integers(0, 256, IO_WRITE_MB << 20, dtype=np.uint8)
        .tobytes()
    )
    chunk = 1 << 20  # writer-shaped: row groups arrive in ~MiB runs
    out = {
        "config": "io_write",
        "file_mb": IO_WRITE_MB,
        "stat": "median",
        "repeats": IO_WRITE_REPEATS,
        "part_mb_sweep": list(IO_WRITE_PART_MB),
    }
    sweep = {}
    for rtt_ms in IO_WRITE_RTTS_MS:
        with RangeHttpStub(
            writable=True, latency_s=rtt_ms / 1e3
        ) as stub:
            url = stub.url_for("bench.bin")
            per_part = {}
            for part_mb in IO_WRITE_PART_MB:

                def one_write():
                    with HttpSink(url, part_bytes=part_mb << 20) as s:
                        for i in range(0, len(data), chunk):
                            s.write(data[i : i + chunk])

                s0 = metrics.snapshot()
                t = timed_stats(
                    one_write,
                    IO_WRITE_REPEATS,
                    f"io-write rtt={rtt_ms:g}ms part={part_mb}MiB",
                    rows=IO_WRITE_MB,
                )
                d = metrics.delta(s0)
                assert stub.object_bytes("bench.bin") == data, (
                    "committed object differs from the written payload"
                )
                per_part[f"{part_mb}"] = {
                    "t": t["t"],
                    "mb_s": round(len(data) / 1e6 / t["t"], 1),
                    "put_requests": sum(
                        v
                        for k, v in d.items()
                        if k.startswith("io_put_requests_total")
                    )
                    // IO_WRITE_REPEATS,
                }
            best = max(per_part, key=lambda k: per_part[k]["mb_s"])
            sweep[f"{rtt_ms:g}"] = {
                "parts": per_part,
                "best_part_mb": int(best),
                "mb_s": per_part[best]["mb_s"],
            }
    out["rtt_sweep"] = sweep
    out["mb_s_at_max_rtt"] = sweep[f"{IO_WRITE_RTTS_MS[-1]:g}"]["mb_s"]
    log(
        "bench: io-write @"
        + ", ".join(
            f"{k}ms {v['mb_s']:.0f} MB/s (best part {v['best_part_mb']}MiB)"
            for k, v in sweep.items()
        )
        + "; every committed object verified byte-identical"
    )
    _emit(out)


# -- the scan-service benchmark (--serve / phase "serve") ----------------------

SERVE_ROWS = int(os.environ.get("PQT_SERVE_ROWS", 160_000))
SERVE_FILES = int(os.environ.get("PQT_SERVE_FILES", 8))
SERVE_REQUESTS = int(os.environ.get("PQT_SERVE_REQUESTS", 32))


def _serve_dir(
    rows: int | None = None, files: int | None = None, row_group: int = 1 << 14
) -> Path:
    """A cached multi-file corpus for the daemon: `rows` int64+float64
    rows over `files` files of `row_group`-row groups, so one request
    decodes a few units and concurrent requests spread across files."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rows = SERVE_ROWS if rows is None else rows
    files = SERVE_FILES if files is None else files
    d = Path(f"/tmp/pqt_serve_{rows}_{files}_{row_group}")
    if d.exists():
        return d
    d.mkdir(parents=True)
    rng = np.random.default_rng(17)
    per = rows // files
    log(f"bench: generating {files}x{per:,}-row serve corpus at {d}")
    for i in range(files):
        t = pa.table(
            {
                "id": pa.array(
                    np.arange(i * per, (i + 1) * per, dtype=np.int64)
                ),
                "v": pa.array(rng.standard_normal(per)),
            }
        )
        pq.write_table(
            t, str(d / f"shard-{i:03d}.parquet"),
            compression="snappy", row_group_size=row_group,
        )
    return d


def _phase_serve() -> None:
    """Scan-service benchmark (`bench.py --serve` / `make bench-serve`).

    Drives a real in-process daemon (parquet_tpu.serve, ephemeral port)
    over HTTP, the way clients will: requests/s and p50/p99 request
    latency at client concurrency 1/4/16 against a WARM daemon (each
    request a full jsonl scan of one shard, round-robin across the
    corpus), plus the cold-vs-warm /v1/plan latency ratio — the number
    the footer/block caches exist to move (a warm plan is pure in-memory
    metadata work; a cold one parses every footer). Host-only; the result
    rides the --json artifact as "serve"."""
    import http.client

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import threading

    from parquet_tpu.serve import ScanServer, ServeConfig

    d = _serve_dir()

    def one_request(host, port, body):
        t0 = time.perf_counter()
        conn = http.client.HTTPConnection(host, port, timeout=120)
        try:
            conn.request("POST", "/v1/scan", body=body)
            resp = conn.getresponse()
            payload = resp.read()
            assert resp.status == 200, payload[:200]
            return time.perf_counter() - t0, len(payload)
        finally:
            conn.close()

    def plan_latency(host, port):
        t0 = time.perf_counter()
        conn = http.client.HTTPConnection(host, port, timeout=120)
        try:
            conn.request("GET", "/v1/plan?paths=shard-*.parquet")
            resp = conn.getresponse()
            assert resp.status == 200, resp.read()[:200]
            resp.read()
            return time.perf_counter() - t0
        finally:
            conn.close()

    # cold plan: a FRESH daemon's first /v1/plan parses every footer; one
    # sample per daemon, so take a few daemons and keep the median
    cold = []
    for _ in range(3):
        with ScanServer(ServeConfig(port=0, root=str(d))) as srv:
            srv.start_background()
            cold.append(plan_latency(srv.host, srv.port))
    cold_ms = float(np.median(cold) * 1e3)

    out = {
        "config": "serve",
        "rows_per_file": SERVE_ROWS // SERVE_FILES,
        "files": SERVE_FILES,
        "requests_per_level": SERVE_REQUESTS,
        "stat": "median",
    }
    bodies = [
        json.dumps({"paths": f"shard-{i % SERVE_FILES:03d}.parquet"}).encode()
        for i in range(SERVE_REQUESTS)
    ]
    # caps above the sweep's widest concurrency: this measures throughput,
    # not admission control (tests pin the 429 behavior)
    with ScanServer(
        ServeConfig(
            port=0, root=str(d), cache_mb=256,
            max_inflight=64, tenant_concurrent=64,
        )
    ) as srv:
        srv.start_background()
        host, port = srv.host, srv.port
        warm = [plan_latency(host, port) for _ in range(20)][5:]
        warm_ms = float(np.median(warm) * 1e3)
        # warm the daemon's caches end to end before timing the sweep
        for i in range(SERVE_FILES):
            one_request(host, port, bodies[i])
        sweep = {}
        for conc in (1, 4, 16):
            lat: list = []
            lock = threading.Lock()
            idx = iter(range(SERVE_REQUESTS))

            def worker():
                while True:
                    with lock:
                        i = next(idx, None)
                    if i is None:
                        return
                    t, _n = one_request(host, port, bodies[i])
                    with lock:
                        lat.append(t)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=worker) for _ in range(conc)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            assert len(lat) == SERVE_REQUESTS
            sweep[str(conc)] = {
                "rps": round(SERVE_REQUESTS / wall, 2),
                "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
                "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
                "wall_s": round(wall, 4),
            }
            log(
                f"bench: serve conc={conc}: {sweep[str(conc)]['rps']} req/s, "
                f"p50 {sweep[str(conc)]['p50_ms']} ms, "
                f"p99 {sweep[str(conc)]['p99_ms']} ms"
            )
    out["concurrency_sweep"] = sweep
    # headline latency/throughput at the widest sweep level, hoisted to a
    # stable dotted path (serve.p99_ms / serve.rps) so the trend store and
    # the --compare gate track serve latency regressions like throughput —
    # independent of which concurrency levels the sweep happens to run
    top = sweep[max(sweep, key=int)]
    out["p99_ms"] = top["p99_ms"]
    out["p50_ms"] = top["p50_ms"]
    out["rps"] = top["rps"]
    out["plan_cold_ms"] = round(cold_ms, 3)
    out["plan_warm_ms"] = round(warm_ms, 3)
    out["plan_cold_vs_warm"] = round(cold_ms / warm_ms, 2) if warm_ms else None
    log(
        f"bench: serve plan cold {out['plan_cold_ms']} ms vs warm "
        f"{out['plan_warm_ms']} ms = {out['plan_cold_vs_warm']}x"
    )
    _emit(out)


# -- the mesh-router benchmark (--serve-mesh / phase "serve_mesh") -------------

SERVE_MESH_REQUESTS = int(os.environ.get("PQT_SERVE_MESH_REQUESTS", 32))
SERVE_MESH_CONC = int(os.environ.get("PQT_SERVE_MESH_CONC", 8))


def _phase_serve_mesh() -> None:
    """Mesh-router benchmark (`bench.py --serve-mesh` / `make
    bench-serve-mesh`).

    Spawns REAL replica daemons as subprocesses (each its own process =
    its own GIL, the deployment shape) plus an in-process MeshRouter, and
    measures routed req/s at replica counts 1 and 4 under a fixed client
    concurrency — rps_1r/rps_4r are the trend-store scaling pins (read
    them against the fingerprint's nproc: a 1-core box cannot scale).
    Then the chaos leg: the same hammer with one replica SIGKILLed
    mid-run — every response must be byte-identical or a typed error
    record, never torn; the router's mesh_retries_total counters report
    what the kill actually cost."""
    import http.client
    import re as _re
    import subprocess
    import threading

    # the router and these replicas are host-only daemons (no --device):
    # pinned to the CPU so four children never contend for one chip. Four
    # one-chip device replicas on a four-chip host is ROADMAP 1.6.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from parquet_tpu.serve.mesh import MeshConfig, MeshRouter

    d = _serve_dir()
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def spawn_replica():
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "parquet_tpu.tools.parquet_tool",
                "serve", "--port", "0", "--root", str(d),
                "--cache-mb", "256", "--max-inflight", "64",
                "--tenant-concurrent", "64",
            ],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env,
        )
        for line in proc.stdout:
            m = _re.search(r"listening on (http://\S+)", line)
            if m:
                return proc, m.group(1)
        raise SystemExit("bench: replica daemon never reported its port")

    def one_request(host, port, body):
        t0 = time.perf_counter()
        conn = http.client.HTTPConnection(host, port, timeout=120)
        try:
            conn.request("POST", "/v1/scan", body=body)
            resp = conn.getresponse()
            payload = resp.read()
            return time.perf_counter() - t0, resp.status, payload
        finally:
            conn.close()

    bodies = [
        json.dumps({"paths": f"shard-{i % SERVE_FILES:03d}.parquet"}).encode()
        for i in range(SERVE_MESH_REQUESTS)
    ]

    def hammer(host, port, on_result):
        lock = threading.Lock()
        idx = iter(range(SERVE_MESH_REQUESTS))

        def worker():
            while True:
                with lock:
                    i = next(idx, None)
                if i is None:
                    return
                try:
                    t, status, payload = one_request(host, port, bodies[i])
                except http.client.HTTPException as e:
                    with lock:
                        on_result(i, "torn", repr(e), None)
                    continue
                with lock:
                    on_result(i, "ok" if status == 200 else "error",
                              status, payload)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=worker) for _ in range(SERVE_MESH_CONC)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    procs = []
    out = {
        "config": "serve_mesh",
        "requests_per_level": SERVE_MESH_REQUESTS,
        "concurrency": SERVE_MESH_CONC,
        "stat": "wall-clock req/s",
    }
    try:
        for _ in range(4):
            procs.append(spawn_replica())
        urls = [u for _p, u in procs]
        # reference payloads straight from a replica: the byte-identity
        # oracle every routed response is judged against
        rhost, rport = urls[0].split("//")[1].rsplit(":", 1)
        expect = {}
        for i, body in enumerate(bodies):
            _t, status, payload = one_request(rhost, int(rport), body)
            assert status == 200, payload[:200]
            expect[i] = payload
        for n_replicas in (1, 4):
            router = MeshRouter(
                MeshConfig(
                    port=0, replicas=tuple(urls[:n_replicas]),
                    max_inflight=64, tenant_concurrent=64,
                )
            ).start_background()
            try:
                # warm each file through the routed path before timing
                for i in range(SERVE_FILES):
                    one_request(router.host, router.port, bodies[i])
                lat, bad = [], []

                def on_result(i, kind, detail, payload):
                    if kind != "ok" or payload != expect[i]:
                        bad.append((i, kind, detail))

                wall = hammer(router.host, router.port, on_result)
                assert not bad, f"mesh bench: non-identical responses: {bad[:4]}"
                rps = round(SERVE_MESH_REQUESTS / wall, 2)
                out[f"rps_{n_replicas}r"] = rps
                log(f"bench: serve-mesh {n_replicas} replica(s): {rps} req/s")
            finally:
                router.close()
        out["scaling_ratio"] = (
            round(out["rps_4r"] / out["rps_1r"], 2) if out["rps_1r"] else None
        )
        # chaos leg: SIGKILL one replica mid-hammer; typed retries only
        router = MeshRouter(
            MeshConfig(
                port=0, replicas=tuple(urls),
                max_inflight=64, tenant_concurrent=64,
            )
        ).start_background()
        try:
            for i in range(SERVE_FILES):
                one_request(router.host, router.port, bodies[i])
            outcomes = {"ok": 0, "typed": 0, "untyped": 0, "torn": 0}
            killed = threading.Event()

            def on_chaos_result(i, kind, detail, payload):
                if outcomes["ok"] >= SERVE_MESH_REQUESTS // 4:
                    if not killed.is_set():
                        procs[2][0].kill()  # mid-hammer, requests in flight
                        killed.set()
                if kind == "ok" and payload == expect[i]:
                    outcomes["ok"] += 1
                elif kind == "torn":
                    outcomes["torn"] += 1
                elif kind == "error":
                    try:
                        json.loads(payload)["error"]["code"]
                        outcomes["typed"] += 1
                    except (ValueError, KeyError):
                        outcomes["untyped"] += 1
                else:
                    outcomes["untyped"] += 1

            hammer(router.host, router.port, on_chaos_result)
            if not killed.is_set():
                procs[2][0].kill()
            status, retries = 0, {}
            conn = http.client.HTTPConnection(
                router.host, router.port, timeout=30
            )
            try:
                conn.request("GET", "/metrics")
                resp = conn.getresponse()
                text = resp.read().decode()
            finally:
                conn.close()
            for m in _re.finditer(
                r'parquet_tpu_mesh_retries_total\{reason="([a-z0-9_]+)"\} (\d+)',
                text,
            ):
                retries[m.group(1)] = int(m.group(2))
            out["chaos"] = {
                "replica_killed": killed.is_set(),
                "responses": dict(outcomes),
                "typed_only": outcomes["untyped"] == 0
                and outcomes["torn"] == 0,
                "retries": retries,
            }
            log(
                f"bench: serve-mesh chaos: {outcomes}, retries {retries}, "
                f"typed_only={out['chaos']['typed_only']}"
            )
        finally:
            router.close()
    finally:
        for proc, _u in procs:
            proc.terminate()
        for proc, _u in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
    log(
        f"bench: serve-mesh scaling {out['rps_1r']} -> {out['rps_4r']} req/s "
        f"(x{out['scaling_ratio']}, nproc={os.cpu_count()})"
    )
    _emit(out)


# -- the query push-down benchmark (--query / phase "query") ------------------

QUERY_ROWS = int(os.environ.get("PQT_QUERY_ROWS", 1_000_000))
QUERY_REQUESTS = int(os.environ.get("PQT_QUERY_REQUESTS", 24))


def _query_file() -> Path:
    """A cached 1M-row numeric file for the vec-vs-scalar residual-filter
    sweep (int64 id + float64 v, several row groups)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    p = Path(f"/tmp/pqt_query_{QUERY_ROWS}.parquet")
    if p.exists():
        return p
    rng = np.random.default_rng(23)
    t = pa.table(
        {
            "id": pa.array(np.arange(QUERY_ROWS, dtype=np.int64)),
            "v": pa.array(rng.standard_normal(QUERY_ROWS)),
        }
    )
    pq.write_table(t, str(p), compression="snappy", row_group_size=1 << 17)
    return p


def _phase_query() -> None:
    """Query push-down benchmark (`bench.py --query` / `make bench-query`).

    Two ceilings, measured head-on:
      * residual filtering: rows/s of a filtered iter_rows over a 1M-row
        numeric predicate, vectorized mask pipeline (core/filter_vec) vs
        the scalar row_matches walk (PQT_VEC_FILTER=0) — outputs asserted
        identical before timing;
      * the serialization plateau: req/s of a filtered AGGREGATE query
        (POST /v1/query — kilobyte bodies) vs the row-streaming jsonl scan
        of the same predicate (POST /v1/scan) against a warm daemon.
    Host-only; the result rides the --json artifact as "query"."""
    import http.client

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from parquet_tpu.core.reader import FileReader
    from parquet_tpu.serve import ScanServer, ServeConfig

    out = {"config": "query", "stat": "median"}

    # -- vec vs scalar residual filtering ------------------------------------
    path = _query_file()
    predicate = [["v", ">", 2.0]]  # ~2.3% selectivity: the dashboard shape

    def filtered_rows() -> int:
        with FileReader(str(path)) as r:
            return sum(1 for _ in r.iter_rows(filters=predicate))

    # restore the caller's engine choice afterwards: the serve comparison
    # below (and any later phase) must run whatever the round configured
    prior = os.environ.get("PQT_VEC_FILTER")
    try:
        os.environ["PQT_VEC_FILTER"] = "1"
        k_vec = filtered_rows()  # warm + correctness reference
        t_vec = timed_stats(
            filtered_rows, REPEATS, "filter-vec", rows=QUERY_ROWS
        )
        os.environ["PQT_VEC_FILTER"] = "0"
        k_scalar = filtered_rows()
        assert k_scalar == k_vec, f"engines disagree: {k_vec} vs {k_scalar}"
        t_scalar = timed_stats(
            filtered_rows, max(1, REPEATS // 2), "filter-scalar",
            rows=QUERY_ROWS,
        )
    finally:
        if prior is None:
            os.environ.pop("PQT_VEC_FILTER", None)
        else:
            os.environ["PQT_VEC_FILTER"] = prior
    out["filter"] = {
        "rows": QUERY_ROWS,
        "predicate": "v > 2.0",
        "rows_matched": k_vec,
        "rows_s_vec": round(QUERY_ROWS / t_vec["t"], 1),
        "rows_s_scalar": round(QUERY_ROWS / t_scalar["t"], 1),
        "vec_vs_scalar": round(t_scalar["t"] / t_vec["t"], 2),
    }
    log(
        f"bench: query filter 1M-row predicate: vec "
        f"{out['filter']['rows_s_vec'] / 1e6:.2f} M rows/s vs scalar "
        f"{out['filter']['rows_s_scalar'] / 1e6:.2f} M rows/s = "
        f"{out['filter']['vec_vs_scalar']}x"
    )

    # -- filtered aggregate vs row streaming on the serve corpus --------------
    # a production-shaped corpus: analytics files carry LARGE row groups
    # (64Ki rows here vs the serve bench's concurrency-shaped 16Ki), and
    # the aggregate's response is near-constant in result size while row
    # streaming pays per matching row — the contrast push-down exists for
    q_rows = int(os.environ.get("PQT_QUERY_SERVE_ROWS", 4 * SERVE_ROWS))
    d = _serve_dir(q_rows, SERVE_FILES, row_group=1 << 16)
    filt = [["v", ">", 0.0]]  # ~half the corpus survives: streaming hurts
    scan_body = json.dumps(
        {"paths": "shard-*.parquet", "filters": filt}
    ).encode()
    query_body = json.dumps(
        {
            "paths": "shard-*.parquet",
            "filters": filt,
            "aggregates": ["count", ["sum", "v"], ["min", "id"], ["max", "id"]],
        }
    ).encode()

    def one(host, port, route, body):
        conn = http.client.HTTPConnection(host, port, timeout=300)
        try:
            conn.request("POST", route, body=body)
            resp = conn.getresponse()
            payload = resp.read()
            assert resp.status == 200, payload[:200]
            return payload
        finally:
            conn.close()

    def hammer(host, port, route, body, n, conc=4):
        """Throughput at client concurrency `conc` — the production shape
        (and the serve bench's): req/s is what the ratio pin is about."""
        import threading

        lat: list = []
        sizes: list = []
        lock = threading.Lock()
        idx = iter(range(n))

        def worker():
            while True:
                with lock:
                    i = next(idx, None)
                if i is None:
                    return
                t1 = time.perf_counter()
                payload = one(host, port, route, body)
                with lock:
                    lat.append(time.perf_counter() - t1)
                    sizes.append(len(payload))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker) for _ in range(conc)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert len(lat) == n
        return {
            "rps": round(n / wall, 2),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
        }, sizes[-1]

    with ScanServer(
        ServeConfig(port=0, root=str(d), cache_mb=256, max_inflight=64)
    ) as srv:
        srv.start_background()
        host, port = srv.host, srv.port
        # warm caches end to end on both routes before timing
        hammer(host, port, "/v1/query", query_body, 2, conc=2)
        hammer(host, port, "/v1/scan", scan_body, 1, conc=1)
        agg, agg_bytes = hammer(
            host, port, "/v1/query", query_body, QUERY_REQUESTS
        )
        stream, stream_bytes = hammer(
            host, port, "/v1/scan", scan_body, max(4, QUERY_REQUESTS // 4)
        )
    out["serve"] = {
        "requests": QUERY_REQUESTS,
        "rows": q_rows,
        "files": SERVE_FILES,
        "aggregate": agg,
        "stream": stream,
        "aggregate_bytes": agg_bytes,
        "stream_bytes": stream_bytes,
        "aggregate_vs_stream": round(agg["rps"] / stream["rps"], 2),
    }
    log(
        f"bench: query serve: aggregate {agg['rps']} req/s "
        f"({agg_bytes} B/resp) vs row-stream {stream['rps']} req/s "
        f"({stream_bytes} B/resp) = {out['serve']['aggregate_vs_stream']}x"
    )
    _emit(out)


# -- the device-resident query/write benchmark (--device / make bench-device) --

DEVICE_QUERY_ROWS = int(os.environ.get("PQT_DEVICE_QUERY_ROWS", 500_000))


def _device_corpus() -> Path:
    """A cached numeric corpus written by OUR writer (int64 id + uint32 tag
    + float64 v, several row groups) — the device query lanes filter and
    aggregate it, and the write lane re-encodes its columns."""
    from parquet_tpu.core.writer import FileWriter
    from parquet_tpu.schema.dsl import parse_schema

    p = Path(f"/tmp/pqt_device_{DEVICE_QUERY_ROWS}.parquet")
    if p.exists():
        return p
    schema = parse_schema(
        """
        message bench {
          required int64 id;
          required int32 tag (UINT_32);
          required double v;
        }
        """
    )
    rng = np.random.default_rng(19)
    with FileWriter(
        str(p), schema, codec="snappy", row_group_size=1 << 21
    ) as w:
        done = 0
        while done < DEVICE_QUERY_ROWS:
            n = min(1 << 16, DEVICE_QUERY_ROWS - done)
            w.write_column(
                "id", np.arange(done, done + n, dtype=np.int64)
            )
            w.write_column(
                "tag",
                rng.integers(0, 1 << 32, n, dtype=np.uint64)
                .astype(np.uint32)
                .view(np.int32),
            )
            w.write_column("v", rng.standard_normal(n))
            w.flush_row_group()
            done += n
    return p


def _phase_device() -> None:
    """Device-resident query + write benchmark (`bench.py --device` /
    `make bench-device`). Three lanes, each asserted byte-identical to its
    host twin BEFORE any timing:
      * filter: iter_device_batches(filter_rows=True) — the resident mask
        + one shared compaction gather — vs host vec-mask filtering with a
        post-filter upload;
      * aggregate: POST /v1/query units on ServeConfig(device=True) vs the
        host pyarrow unit path (render_query_body compared verbatim);
      * write: FileWriter.write_device_column (device DELTA block scans +
        dictionary probe) vs write_column, full-file bytes compared.
    Runs on the process default device and refuses a non-TPU one
    (_require_device); the result carries the device facts. Rides the
    --json artifact as "device"."""
    _require_device()
    import jax
    import jax.numpy as jnp

    from parquet_tpu.core.filter import normalize_dnf
    from parquet_tpu.core.filter_vec import dnf_mask
    from parquet_tpu.core.reader import FileReader

    out = {"config": "device", "stat": "median", "rows": DEVICE_QUERY_ROWS}
    path = _device_corpus()
    lo, hi = DEVICE_QUERY_ROWS // 10, (DEVICE_QUERY_ROWS * 9) // 10
    pred = [[["id", ">=", lo], ["id", "<", hi], ["tag", ">=", 1 << 31]]]

    # -- lane 1: device-resident row filtering --------------------------------

    def device_filtered():
        ids = []
        with FileReader(str(path)) as r:
            for b in r.iter_device_batches(
                1 << 15,
                columns=["id", "v"],
                drop_remainder=False,
                filters=pred,
                filter_rows=True,
            ):
                ids.append(b[("id",)])
        jax.block_until_ready(ids)
        return np.concatenate([np.asarray(a) for a in ids]) if ids else np.empty(0, np.int64)

    def host_filtered():
        ids = []
        with FileReader(str(path)) as r:
            nd = normalize_dnf(r.schema, pred)
            for i in range(r.num_row_groups):
                chunks = r._read_row_group(i, None, pack=False)
                n = int(r.row_group(i).num_rows or 0)
                mask = dnf_mask(chunks, nd, n)
                kept = np.asarray(chunks[("id",)].values)[mask]
                ids.append(jnp.asarray(kept))
                jnp.asarray(np.asarray(chunks[("v",)].values)[mask])
        jax.block_until_ready(ids)
        return np.concatenate([np.asarray(a) for a in ids]) if ids else np.empty(0, np.int64)

    d_ids = device_filtered()  # also warms the jit caches
    h_ids = host_filtered()
    assert np.array_equal(d_ids, h_ids), (
        f"device/host filtered rows diverge: {d_ids.shape} vs {h_ids.shape}"
    )
    log(f"bench: device filter identity ✓ ({d_ids.shape[0]} rows kept)")
    t_dev = timed_stats(device_filtered, REPEATS, "filter-device", rows=DEVICE_QUERY_ROWS)
    t_host = timed_stats(host_filtered, REPEATS, "filter-host", rows=DEVICE_QUERY_ROWS)
    out["filter"] = {
        "rows_matched": int(d_ids.shape[0]),
        "rows_s_device": round(DEVICE_QUERY_ROWS / t_dev["t"], 1),
        "rows_s_host": round(DEVICE_QUERY_ROWS / t_host["t"], 1),
        "device_vs_host": round(t_host["t"] / t_dev["t"], 2),
    }
    log(
        f"bench: device filter {out['filter']['rows_s_device'] / 1e6:.2f} M rows/s "
        f"vs host-filter+upload {out['filter']['rows_s_host'] / 1e6:.2f} M rows/s "
        f"= {out['filter']['device_vs_host']}x"
    )

    # -- lane 2: device partial aggregation through the serve executor --------
    from parquet_tpu.serve.aggregate import render_query_body
    from parquet_tpu.serve.protocol import parse_query_request
    from parquet_tpu.serve.server import ScanService, ServeConfig

    q = parse_query_request(
        json.dumps(
            {
                "paths": [str(path)],
                "filters": pred,
                "aggregates": [
                    "count",
                    {"op": "sum", "column": "id"},
                    {"op": "min", "column": "id"},
                    {"op": "max", "column": "tag"},
                ],
            }
        ).encode()
    )
    svc_dev = ScanService(ServeConfig(root=str(path.parent), device=True))
    svc_host = ScanService(ServeConfig(root=str(path.parent)))

    def run_agg(svc):
        ticket, got = svc.query(q, "bench")
        ticket.release()
        return render_query_body(got)

    b_dev, b_host = run_agg(svc_dev), run_agg(svc_host)
    assert b_dev == b_host, f"aggregate bodies diverge: {b_dev} vs {b_host}"
    log(f"bench: device aggregate identity ✓ ({b_dev})")
    t_adev = timed_stats(lambda: run_agg(svc_dev), REPEATS, "agg-device", rows=DEVICE_QUERY_ROWS)
    t_ahost = timed_stats(lambda: run_agg(svc_host), REPEATS, "agg-host", rows=DEVICE_QUERY_ROWS)
    out["aggregate"] = {
        "rows_s_device": round(DEVICE_QUERY_ROWS / t_adev["t"], 1),
        "rows_s_host": round(DEVICE_QUERY_ROWS / t_ahost["t"], 1),
        "device_vs_host": round(t_ahost["t"] / t_adev["t"], 2),
    }
    log(
        f"bench: device aggregate {out['aggregate']['rows_s_device'] / 1e6:.2f} "
        f"M rows/s vs host {out['aggregate']['rows_s_host'] / 1e6:.2f} M rows/s "
        f"= {out['aggregate']['device_vs_host']}x"
    )

    # -- lane 3: the device write path ----------------------------------------
    from parquet_tpu.core.writer import FileWriter
    from parquet_tpu.schema.dsl import parse_schema

    wschema = parse_schema(
        """
        message w {
          required int64 seq;
          required int64 bucket;
        }
        """
    )
    rng = np.random.default_rng(5)
    w_rows = min(DEVICE_QUERY_ROWS, 1 << 19)
    seq = np.cumsum(rng.integers(0, 9, w_rows)).astype(np.int64)
    bucket = rng.integers(0, 128, w_rows, dtype=np.int64)
    d_seq, d_bucket = jnp.asarray(seq), jnp.asarray(bucket)
    enc = {"seq": "DELTA_BINARY_PACKED"}

    def write_host(dst):
        with FileWriter(
            dst, wschema, codec="snappy", column_encodings=enc,
            row_group_size=1 << 22,
        ) as w:
            w.write_column("seq", seq)
            w.write_column("bucket", bucket)

    def write_device(dst):
        with FileWriter(
            dst, wschema, codec="snappy", column_encodings=enc,
            row_group_size=1 << 22,
        ) as w:
            w.write_device_column("seq", d_seq)
            w.write_device_column("bucket", d_bucket)

    ph, pd = "/tmp/pqt_dev_write_h.parquet", "/tmp/pqt_dev_write_d.parquet"
    write_host(ph)
    write_device(pd)  # warms the device encode jit cache
    hb, db = Path(ph).read_bytes(), Path(pd).read_bytes()
    assert hb == db, f"write bytes diverge: {len(hb)} vs {len(db)}"
    log(f"bench: device write identity ✓ ({len(hb)} bytes)")
    t_wdev = timed_stats(lambda: write_device(pd), REPEATS, "write-device", rows=w_rows)
    t_whost = timed_stats(lambda: write_host(ph), REPEATS, "write-host", rows=w_rows)
    out["write"] = {
        "rows": w_rows,
        "rows_s_device": round(w_rows / t_wdev["t"], 1),
        "rows_s_host": round(w_rows / t_whost["t"], 1),
        "device_vs_host": round(t_whost["t"] / t_wdev["t"], 2),
    }
    log(
        f"bench: device write {out['write']['rows_s_device'] / 1e6:.2f} M rows/s "
        f"vs host {out['write']['rows_s_host'] / 1e6:.2f} M rows/s "
        f"= {out['write']['device_vs_host']}x"
    )
    _emit(out)


# -- the streaming-loader benchmark (--dataset / phase "dataset") -------------

DATASET_ROWS = int(os.environ.get("PQT_DATASET_ROWS", 2_000_000))
DATASET_FILES = int(os.environ.get("PQT_DATASET_FILES", 8))


def _dataset_glob() -> str:
    """A cached multi-file shard set: DATASET_ROWS taxi-like rows (int64 id
    PLAIN + DELTA_BINARY_PACKED int64 ts, snappy) split over DATASET_FILES
    files of several row groups each — enough units that prefetch depth has
    something to schedule."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    d = Path(f"/tmp/pqt_dataset_{DATASET_ROWS}_{DATASET_FILES}")
    marker = d / "DONE"
    if not marker.exists():
        d.mkdir(parents=True, exist_ok=True)
        rng = np.random.default_rng(7)
        per = DATASET_ROWS // DATASET_FILES
        log(f"bench: generating {DATASET_FILES} x {per:,}-row shard files in {d}")
        for i in range(DATASET_FILES):
            base = i * per
            t = pa.table(
                {
                    "trip_id": pa.array(
                        np.arange(base, base + per, dtype=np.int64)
                    ),
                    "ts": pa.array(
                        (
                            1_600_000_000_000_000
                            + np.cumsum(rng.integers(0, 1000, per))
                        ).astype(np.int64)
                    ),
                }
            )
            pq.write_table(
                t,
                d / f"shard-{i:03d}.parquet",
                compression="snappy",
                row_group_size=1 << 16,
                use_dictionary=False,
                column_encoding={
                    "trip_id": "PLAIN", "ts": "DELTA_BINARY_PACKED"
                },
            )
        marker.write_text("ok\n")
    return str(d / "shard-*.parquet")


def _phase_dataset() -> None:
    """Training-loop throughput at a prefetch-depth sweep over the shard glob.

    The consumer models a DEVICE-BOUND train step: after touching the
    delivered batch it blocks for PQT_DATASET_STEP_MS (default 2 ms — the
    host-side shape of `block_until_ready()` on an accelerator step: host
    blocked, cores free). rows/s therefore measures the PIPELINE — with
    depth 0 the loop pays decode + step serially; with depth >= 1 unit
    decode on the pqt-data workers overlaps the blocked consumer, and the
    wait-time share shows how much starvation remains. `loader_rows_s` is
    the step-free depth-0 reference (pure decode+rebatch capability).

    Measured constraint (why the consumer is not host compute): on a
    host whose cores the step itself saturates — e.g. an XLA CPU matmul on
    a 2-core box — there is nothing left for decode threads to overlap
    with, and prefetch can only lose; against a blocked consumer the
    overlap is the loader's to win."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # host-only: needs no device
    import time as _time

    from parquet_tpu.data import ParquetDataset
    from parquet_tpu.utils import metrics

    pattern = _dataset_glob()
    batch = 16384
    step_s = float(os.environ.get("PQT_DATASET_STEP_MS", "2")) / 1e3
    sweep = {}

    def run_epoch(depth: int, step: float):
        ds = ParquetDataset(
            pattern, batch_size=batch, prefetch=depth, num_epochs=1,
            remainder="keep",
        )
        total = 0
        with ds:
            for b in ds:
                int(b[("trip_id",)][0])  # touch the delivery
                if step:
                    _time.sleep(step)
                total += int(next(iter(b.values())).shape[0])
        return total

    rows = run_epoch(0, 0.0)  # warm: page cache + lazy imports + native load
    t_loader = timed_stats(
        lambda: run_epoch(0, 0.0), REPEATS, "dataset loader-only", rows=rows
    )
    for depth in (0, 1, 2, 4):
        s0 = metrics.snapshot()
        t = timed_stats(
            lambda d=depth: run_epoch(d, step_s), REPEATS,
            f"dataset depth={depth}", rows=rows,
        )
        d = metrics.delta(s0)
        # share = total wait / total sampled wall across the SAME repeats —
        # mixing a mean wait with the median time would let one outlier run
        # report a >100% share against a clean median
        wall_total = sum(t["samples"])
        wait_total = d.get("dataset_wait_seconds_sum", 0.0)
        sweep[str(depth)] = {
            "rows_s": round(rows / t["t"], 1),
            "t": t["t"],
            "wait_s": round(wait_total / REPEATS, 5),
            "wait_share": (
                round(wait_total / wall_total, 4) if wall_total > 0 else None
            ),
            "samples_s": t["samples"],
        }
    best = max((k for k in sweep if int(k) >= 2), key=lambda k: sweep[k]["rows_s"])
    out = {
        "config": "dataset",
        "rows": rows,
        "files": DATASET_FILES,
        "batch_size": batch,
        "step_ms": step_s * 1e3,
        "rows_s": sweep[best]["rows_s"],
        "best_depth": int(best),
        "vs_depth0": round(sweep["0"]["t"] / sweep[best]["t"], 3),
        "wait_share": sweep[best]["wait_share"],
        "loader_rows_s": round(rows / t_loader["t"], 1),
        "stat": "median",
        "sweep": sweep,
    }
    log(
        f"bench: dataset pipeline: depth {best} {out['rows_s'] / 1e6:.2f} M rows/s "
        f"({out['vs_depth0']:.2f}x over depth 0, wait share "
        f"{out['wait_share']:.1%}; loader-only "
        f"{out['loader_rows_s'] / 1e6:.2f} M rows/s)"
    )
    _emit(out)


# -- the chaos benchmark (--chaos / phase "chaos") -----------------------------

CHAOS_ROWS = int(os.environ.get("PQT_CHAOS_ROWS", 400_000))
CHAOS_FILES = int(os.environ.get("PQT_CHAOS_FILES", 6))
CHAOS_PHASE_S = float(os.environ.get("PQT_CHAOS_PHASE_S", 2.0))
# PQT_CHAOS_SMOKE=1: the `make check` fast gate — tiny corpus, sub-second
# phases, same code paths
CHAOS_SMOKE = os.environ.get("PQT_CHAOS_SMOKE", "0") == "1"


def _chaos_glob() -> str:
    """A cached shard set for the chaos runs (its own corpus: the dataset
    bench's files are sized for throughput, these for many quick units so
    phases see plenty of reads)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rows = 60_000 if CHAOS_SMOKE else CHAOS_ROWS
    files = 3 if CHAOS_SMOKE else CHAOS_FILES
    d = Path(f"/tmp/pqt_chaos_{rows}_{files}")
    marker = d / "DONE"
    if not marker.exists():
        d.mkdir(parents=True, exist_ok=True)
        rng = np.random.default_rng(13)
        per = rows // files
        log(f"bench: generating {files} x {per:,}-row chaos shards in {d}")
        for i in range(files):
            t = pa.table(
                {
                    "id": pa.array(
                        np.arange(i * per, (i + 1) * per, dtype=np.int64)
                    ),
                    "v": pa.array(
                        rng.integers(0, 1 << 30, per).astype(np.int64)
                    ),
                }
            )
            pq.write_table(
                t, d / f"shard-{i:03d}.parquet", compression="snappy",
                row_group_size=1 << 13, use_dictionary=False,
            )
        marker.write_text("ok\n")
    return str(d / "shard-*.parquet")


def _chaos_schedule(phase_s: float, base: dict):
    """The bench timeline: the standard acts, with the latency spike split
    into a CONVERGE phase (the controller is still adapting) and a STEADY
    phase (the acceptance pin reads this one: p99 within SLO once
    converged)."""
    from parquet_tpu.testing.chaos import FaultSchedule, Phase

    spike = {**base, "spike_rate": 0.5, "spike_s": 0.15}
    return FaultSchedule([
        Phase("warmup", phase_s * 0.5, base),
        Phase("spike_converge", phase_s, spike),
        Phase("spike_steady", phase_s, spike),
        Phase("error_burst", phase_s * 0.5, {**base, "error_rate": 0.3}),
        Phase("blackout", phase_s * 0.5, {**base, "permanent": True}),
        Phase("recovery", phase_s * 0.5, base),
    ])


def _chaos_dataset_run(pattern: str, *, slo_ms: float, phase_s: float,
                       controlled: bool) -> dict:
    """One dataset pass under the scripted schedule: breaker + retry (+
    hedge when controlled) installed, controller attached per
    `controlled`. Returns the run_dataset_chaos report."""
    from parquet_tpu.data.controller import AIMDController
    from parquet_tpu.testing.chaos import ChaosHarness, run_dataset_chaos

    base = {"latency_s": 0.001}
    schedule = _chaos_schedule(phase_s, base)
    controller = (
        AIMDController(
            slo_wait_ms=slo_ms, initial_depth=1, max_depth=16,
            window_s=max(0.2, phase_s / 8), violation_share=0.02,
            increase_step=2, idle_windows=6,
        )
        if controlled
        else None
    )
    with ChaosHarness(
        schedule,
        seed=17,
        breaker=True,
        retry=True,
        hedge=controlled,
        breaker_kw={"failure_threshold": 5, "open_s": phase_s / 4},
        retry_kw={"attempts": 3, "base_delay_s": 0.002, "max_delay_s": 0.02,
                  "sleep": time.sleep},
        hedge_kw={"delay_quantile": 0.9, "min_delay_s": 0.005,
                  "initial_delay_s": 0.02, "max_delay_s": 0.2},
    ) as chaos:
        return run_dataset_chaos(
            pattern,
            chaos=chaos,
            batch_size=4096,
            slo_wait_ms=slo_ms,
            enable_controller=controlled,
            controller=controller,
            prefetch=1,
            # a DEVICE-BOUND consumer (the block_until_ready shape): the
            # controller's depth buys real overlap against it, and a spike
            # that outruns depth-1 pipelining lands squarely on next()
            step_s=0.02,
        )


def _chaos_breaker_probe(pattern: str) -> dict:
    """Micro-measure of the blackout fast-fail: time-to-typed-error on a
    permanently failing source through the retry ladder alone vs through
    an OPEN breaker. The acceptance pin: breakered < 10% of un-breakered."""
    import glob as _glob

    from parquet_tpu.io import (
        BreakerSource,
        CircuitBreaker,
        LocalFileSource,
        RetryingSource,
    )
    from parquet_tpu.testing.flaky import FlakySource

    path = sorted(_glob.glob(pattern))[0]

    def t_read(src):
        t0 = time.perf_counter()
        try:
            src.read_at(0, 64)
        except OSError:
            pass
        return time.perf_counter() - t0

    # the un-breakered shape: every read spins the full ladder (real
    # backoff sleeps — that IS the cost being measured)
    ladder = RetryingSource(
        FlakySource(LocalFileSource(path), seed=5, permanent=True),
        attempts=4, base_delay_s=0.02, max_delay_s=0.1, seed=5,
    )
    t_unbreakered = min(t_read(ladder) for _ in range(3))
    ladder.close()
    # the breakered shape: ladder under a breaker; trip it, then measure
    # the steady-state fast-fail
    breaker = CircuitBreaker("bench-blackout", failure_threshold=1, open_s=60.0)
    gated = BreakerSource(
        RetryingSource(
            FlakySource(LocalFileSource(path), seed=5, permanent=True),
            attempts=4, base_delay_s=0.02, max_delay_s=0.1, seed=5,
        ),
        breaker,
    )
    t_read(gated)  # trips the breaker (pays one full ladder)
    t_breakered = min(t_read(gated) for _ in range(3))
    gated.close()
    return {
        "time_to_error_ms": round(t_unbreakered * 1e3, 3),
        "fast_fail_ms": round(t_breakered * 1e3, 3),
        "fast_fail_ratio": round(t_breakered / t_unbreakered, 5),
        "pin_under_10pct": t_breakered < 0.1 * t_unbreakered,
    }


def _chaos_serve_run(pattern: str, *, phase_s: float) -> dict:
    """Hammer an in-process daemon while its sources run the fault
    schedule: every response must be typed (2xx with a complete body, a
    structured error body, or a torn stream ENDING in a typed terminator
    record) — never a hang or a traceback. Brownout sheds and breaker
    fast-fails are counted from the metrics delta."""
    import glob as _glob
    import http.client
    import threading as _threading

    from parquet_tpu.io import (
        BreakerRegistry,
        BreakerSource,
        LocalFileSource,
        RetryingSource,
    )
    from parquet_tpu.serve import ScanServer, ServeConfig
    from parquet_tpu.testing.chaos import ChaosHarness, standard_schedule
    from parquet_tpu.utils import metrics

    files = sorted(_glob.glob(pattern))
    root = str(Path(files[0]).parent)
    names = [Path(f).name for f in files]
    schedule = standard_schedule(
        phase_s=phase_s * 0.5, spike_p=0.4, spike_ms=60.0, error_rate=0.4,
        base={"latency_s": 0.001},
    )
    chaos = ChaosHarness(schedule, seed=23)
    breakers = BreakerRegistry(failure_threshold=4, open_s=phase_s / 2)

    def factory(p):
        # the production resilience stack over the injected faults:
        # breaker under a short retry ladder — the blackout phase trips
        # the breaker, and the executor's fast-fail shows up as
        # serve_shed_total{reason="breaker_open"} 503s
        return RetryingSource(
            BreakerSource(chaos.wrap(LocalFileSource(p)), registry=breakers),
            attempts=2, base_delay_s=0.002, max_delay_s=0.01, seed=23,
        )

    config = ServeConfig(
        port=0,
        root=root,
        cache_mb=0,  # chaos must hit the source, not the block cache
        default_timeout_s=max(1.0, phase_s),
        brownout_wait_ms=200.0,
        brownout_window_s=max(0.25, phase_s / 4),
        source_factory=factory,
    )
    statuses: dict = {}
    anomalies = {"hang": 0, "untyped": 0, "torn_typed": 0}
    lock = _threading.Lock()
    snap0 = metrics.snapshot()
    schedule.start(time.monotonic())
    stop = time.monotonic() + schedule.total_s

    def tally(key):
        with lock:
            statuses[key] = statuses.get(key, 0) + 1

    def client(i: int):
        body = json.dumps(
            {"paths": [names[i % len(names)]], "format": "jsonl"}
        )
        while time.monotonic() < stop:
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.port, timeout=max(10.0, 4 * phase_s)
            )
            try:
                conn.request(
                    "POST", "/v1/scan", body=body,
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                try:
                    payload = resp.read()
                    complete = True
                except http.client.IncompleteRead as e:
                    payload, complete = e.partial, False
                tally(str(resp.status))
                if resp.status == 200 and not complete:
                    # torn stream: acceptable ONLY with a typed terminator
                    last = payload.rstrip(b"\n").rsplit(b"\n", 1)[-1]
                    try:
                        ok = "error" in json.loads(last)
                    except ValueError:
                        ok = False
                    with lock:
                        anomalies["torn_typed" if ok else "untyped"] += 1
                elif resp.status != 200:
                    try:
                        json.loads(payload)["error"]["code"]
                    except (ValueError, KeyError):
                        with lock:
                            anomalies["untyped"] += 1
            except (TimeoutError, OSError):
                with lock:
                    anomalies["hang"] += 1
            finally:
                conn.close()

    with ScanServer(config) as server:
        server.start_background()
        threads = [
            _threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=schedule.total_s + 30.0)
        hung_workers = sum(1 for t in threads if t.is_alive())
    d = metrics.delta(snap0)
    total = sum(statuses.values())
    return {
        "requests": total,
        "statuses": statuses,
        "torn_with_typed_terminator": anomalies["torn_typed"],
        "untyped_responses": anomalies["untyped"],
        "client_hangs": anomalies["hang"] + hung_workers,
        "shed_queue_wait": d.get('serve_shed_total{reason="queue_wait"}', 0),
        "shed_breaker_open": d.get('serve_shed_total{reason="breaker_open"}', 0),
        "typed_only": anomalies["untyped"] == 0
        and anomalies["hang"] + hung_workers == 0,
    }


def _phase_chaos() -> None:
    """Graceful-degradation measurement: the scripted fault schedule
    (latency spike -> error burst -> blackout -> recovery) against (a) the
    SLO-controlled dataset pipeline vs the same pipeline uncontrolled,
    (b) a breakered vs un-breakered blacked-out source, and (c) the serve
    daemon under brownout. Emits the "chaos" --json section; the three
    acceptance pins ride it as booleans. PQT_CHAOS_SMOKE=1 shrinks
    everything to a make-check-sized smoke."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    pattern = _chaos_glob()
    phase_s = 0.8 if CHAOS_SMOKE else CHAOS_PHASE_S
    # the SLO sits between the healthy wait (~ms) and a raw 150 ms spike:
    # absorbing a spike needs real depth/hedging, not luck
    slo_ms = 100.0
    controlled = _chaos_dataset_run(
        pattern, slo_ms=slo_ms, phase_s=phase_s, controlled=True
    )
    uncontrolled = _chaos_dataset_run(
        pattern, slo_ms=slo_ms, phase_s=phase_s, controlled=False
    )
    steady_c = controlled["phases"].get("spike_steady", {})
    steady_u = uncontrolled["phases"].get("spike_steady", {})
    hedges = controlled["hedge"]
    launched = hedges.get("launched", 0)
    breaker = _chaos_breaker_probe(pattern)
    serve = _chaos_serve_run(pattern, phase_s=phase_s)
    out = {
        "config": "chaos",
        "smoke": CHAOS_SMOKE,
        "phase_s": phase_s,
        "slo_ms": slo_ms,
        "controlled": controlled,
        "uncontrolled": uncontrolled,
        "slo_held_controlled": (
            steady_c.get("p99_ms") is not None
            and steady_c["p99_ms"] <= slo_ms
        ),
        "slo_violated_uncontrolled": (
            steady_u.get("p99_ms") is not None
            and steady_u["p99_ms"] > slo_ms
        ),
        "hedge_win_rate": (
            round(hedges.get("win_hedge", 0) / launched, 4) if launched else None
        ),
        "breaker": breaker,
        "serve": serve,
    }
    log(
        f"bench: chaos: spike-steady p99 {steady_c.get('p99_ms')} ms "
        f"controlled vs {steady_u.get('p99_ms')} ms uncontrolled "
        f"(slo {slo_ms} ms); breaker fast-fail "
        f"{breaker['fast_fail_ratio']:.1%} of ladder; serve typed-only="
        f"{serve['typed_only']} (shed {serve['shed_queue_wait']} brownout, "
        f"{serve['shed_breaker_open']} breaker)"
    )
    _emit(out)


# -- the data-lake ingest benchmark (--ingest / phase "ingest") ----------------

INGEST_ROWS = int(os.environ.get("PQT_INGEST_ROWS", 150_000))
INGEST_BATCH = int(os.environ.get("PQT_INGEST_BATCH", 5_000))


def _phase_ingest() -> None:
    """Data-lake loop benchmark (`bench.py --ingest` / `make bench-ingest`).

    Sustained append throughput into a lake table (every batch flushed:
    each commit is a real sort+encode+manifest-publish), then the
    compaction payoff: a sort-key point probe's pruned-unit ratio and the
    filtered-scan wall, before vs after ONE compaction pass folds the
    overlapping ingest files into clustered row groups. Batches
    interleave keys so pre-compaction files ALL overlap — the worst case
    compaction exists to fix. Tracked pins: ingest.append_rows_s (+),
    ingest.pruned_ratio_gain (+), ingest.scan_speedup (+). Host-only;
    the result rides the --json artifact as "ingest"."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import tempfile

    from parquet_tpu.core.reader import FileReader
    from parquet_tpu.lake import Compactor, IngestWriter, LakeTable, pruned_ratio

    batches = max(INGEST_ROWS // INGEST_BATCH, 4)
    rows_total = batches * INGEST_BATCH

    def filtered_scan_s(paths, filters):
        t0 = time.perf_counter()
        n = 0
        for p in paths:
            with FileReader(p) as r:
                for _row in r.iter_rows(filters=filters):
                    n += 1
        return time.perf_counter() - t0, n

    with tempfile.TemporaryDirectory(prefix="pqt_bench_lake_") as d:
        table = LakeTable.create(
            os.path.join(d, "tbl"),
            "message m { required int64 k; optional binary v (STRING); }",
            sort_key="k",
        )
        writer = IngestWriter(table)
        t0 = time.perf_counter()
        for b in range(batches):
            # batch b holds keys b, b+B, b+2B, ... — every flushed file
            # spans the whole key range, so nothing prunes until compaction
            writer.append(
                [
                    {"k": i * batches + b, "v": f"row-{b}-{i}"}
                    for i in range(INGEST_BATCH)
                ],
                flush=True,
            )
        append_s = time.perf_counter() - t0
        snap = table.manifest.open_snapshot()
        assert snap.total_rows == rows_total, snap.total_rows
        paths_before = table.snapshot_paths()
        probe = [("k", "==", rows_total // 2)]
        ratio_before = pruned_ratio(paths_before, probe)
        scan_before_s, hits_before = filtered_scan_s(paths_before, probe)

        t0 = time.perf_counter()
        result = Compactor(
            table, max_files=batches + 1, row_group_size=INGEST_BATCH
        ).compact_once()
        compact_s = time.perf_counter() - t0
        assert result is not None and result.rows == rows_total
        paths_after = table.snapshot_paths()
        ratio_after = pruned_ratio(paths_after, probe)
        scan_after_s, hits_after = filtered_scan_s(paths_after, probe)
        assert hits_after == hits_before, (hits_before, hits_after)

    out = {
        "config": "ingest",
        "rows": rows_total,
        "batch_rows": INGEST_BATCH,
        "flushes": batches,
        "append_rows_s": round(rows_total / append_s, 1),
        "append_wall_s": round(append_s, 4),
        "compact_wall_s": round(compact_s, 4),
        "files_before": len(paths_before),
        "files_after": len(paths_after),
        "pruned_ratio_before": round(ratio_before, 4),
        "pruned_ratio_after": round(ratio_after, 4),
        # the compaction payoff, as one trend-store-tracked leaf: how much
        # MORE of the table a sort-key point probe prunes after the fold
        "pruned_ratio_gain": round(ratio_after - ratio_before, 4),
        "scan_rows_s_before": round(rows_total / scan_before_s, 1),
        "scan_rows_s_after": round(rows_total / scan_after_s, 1),
        "scan_speedup": round(scan_before_s / scan_after_s, 3),
    }
    log(
        f"bench: ingest: {out['append_rows_s']:,} rows/s appended over "
        f"{batches} flushed generations; compaction folded "
        f"{out['files_before']} files -> {out['files_after']}, probe "
        f"pruned ratio {ratio_before:.2f} -> {ratio_after:.2f} "
        f"(gain {out['pruned_ratio_gain']:.2f}), filtered scan "
        f"{out['scan_speedup']}x faster"
    )
    _emit(out)


_PHASE_FNS = {
    "host": decode_all_host,
    "tpu_host": decode_all_tpu_to_host,
    "baseline": deliver_baseline,
    "device": deliver_device,
    "pyarrow": deliver_pyarrow,
}


def _phase_timed(name: str, path) -> None:
    fn = _PHASE_FNS[name]
    fn(path)  # warmup: compile (disk-cached) + connection establishment
    # the headline phases take extra samples: host-side run-to-run drift
    # is the dominant noise in the reported ratio
    reps = max(REPEATS, 7) if name in ("baseline", "device", "pyarrow") else REPEATS
    _emit(timed_stats(lambda: fn(path), reps, name))


def _run_phase(name: str, timeout_s: float = 1800.0) -> dict | None:
    """Run one phase in a child process and return its result line. A
    host-only phase that fails returns None and the round carries on
    without its section; a device phase that fails ends the run."""
    import subprocess

    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name]
    # strip the artifact path from phase subprocesses: only the TOP-level
    # invocation writes the --json/PQT_BENCH_JSON file, otherwise each phase
    # would clobber it mid-run and a crash would leave a mislabeled partial
    env = {k: v for k, v in os.environ.items() if k != "PQT_BENCH_JSON"}
    failure = None
    try:
        proc = subprocess.run(
            cmd, stdout=subprocess.PIPE, timeout=timeout_s, env=env,
            cwd=str(Path(__file__).parent)
        )
    except subprocess.TimeoutExpired:
        failure = f"timed out after {timeout_s:.0f}s"
    else:
        if proc.returncode != 0:
            failure = f"exited {proc.returncode}"
        else:
            for line in reversed(proc.stdout.decode().splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    return json.loads(line)
            failure = "produced no result line"
    if name in _DEVICE_PHASES:
        raise SystemExit(f"bench: device phase {name} {failure}")
    log(f"bench: phase {name} {failure}")
    return None


def _phase_encode() -> dict | None:
    """Fused-vs-staged encode ladder microbench (`bench.py --encode`,
    `make bench-encode`).

    Per column shape (dict-string / dict-int64 / delta-int64 / plain-double
    / plain-string), write one single-column file serially with the fused
    native encoder and again with PQT_FUSED_ENCODE=0 (the staged Python
    rung), assert the outputs BYTE-IDENTICAL before any timing, then report
    rows/s for both sides and the median of PAIRED fused/staged ratios.
    Skips cleanly (exit 0, "skipped" artifact) when the native extension
    is not built — the staged rung is then the only encoder and there is
    nothing to compare."""
    from parquet_tpu.core.writer import FileWriter
    from parquet_tpu.schema.dsl import parse_schema
    from parquet_tpu.sink import MemorySink
    from parquet_tpu.utils.native import get_native

    lib = get_native()
    if lib is None or not getattr(lib, "has_chunk_encode", False):
        out = {"config": "encode", "skipped": "native chunk_encode unavailable"}
        log("bench: encode — native chunk_encode unavailable, skipping cleanly")
        _emit(out)
        return out

    rows = int(os.environ.get("PQT_ENCODE_ROWS", "500000"))
    rng = np.random.default_rng(11)
    keys = [f"key_{i:05d}" for i in range(5000)]
    shapes = {
        "dict_string": (
            "message m { required binary s (UTF8); }",
            {"s": [keys[k] for k in rng.integers(0, len(keys), rows)]},
            {},
        ),
        "dict_int64": (
            "message m { required int64 a; }",
            {"a": rng.integers(0, 1000, rows).astype(np.int64)},
            {},
        ),
        "delta_int64": (
            "message m { required int64 ts; }",
            {"ts": np.cumsum(rng.integers(0, 1000, rows)).astype(np.int64)},
            {"column_encodings": {"ts": "DELTA_BINARY_PACKED"},
             "use_dictionary": False},
        ),
        "plain_double": (
            "message m { required double x; }",
            {"x": rng.random(rows)},
            {"use_dictionary": False},
        ),
        "plain_string": (
            # all-unique strings: the dictionary probe must bail and the
            # PLAIN byte-array route carries the page
            "message m { required binary u (UTF8); }",
            {"u": [f"u{i:07d}x{i % 911}" for i in range(rows)]},
            {},
        ),
    }

    def write(schema_text, cols, kw):
        schema = parse_schema(schema_text)
        sink = MemorySink()
        w = FileWriter(sink, schema, codec="snappy", **kw)
        for name, vals in cols.items():
            w.write_column(name, vals)
        w.close()
        return sink.getvalue()

    out = {"config": "encode", "rows": rows, "codec": "snappy", "shapes": {}}
    for name, (schema_text, cols, kw) in shapes.items():
        fused = write(schema_text, cols, kw)
        os.environ["PQT_FUSED_ENCODE"] = "0"
        try:
            staged = write(schema_text, cols, kw)
        finally:
            del os.environ["PQT_FUSED_ENCODE"]
        if fused != staged:
            raise SystemExit(
                f"bench: encode shape {name}: fused output is NOT "
                "byte-identical to the staged encoder"
            )
        # PAIRED sampling: each repeat times staged then fused back to back
        # (same load window), speedup = median of paired ratios
        ratios, t_f, t_s = [], [], []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            os.environ["PQT_FUSED_ENCODE"] = "0"
            try:
                write(schema_text, cols, kw)
            finally:
                del os.environ["PQT_FUSED_ENCODE"]
            s = time.perf_counter() - t0
            t0 = time.perf_counter()
            write(schema_text, cols, kw)
            f = time.perf_counter() - t0
            t_s.append(round(s, 5))
            t_f.append(round(f, 5))
            ratios.append(s / f)
        med_f = sorted(t_f)[len(t_f) // 2]
        med_s = sorted(t_s)[len(t_s) // 2]
        r = sorted(ratios)[len(ratios) // 2]
        out["shapes"][name] = {
            "fused_rows_s": round(rows / med_f, 1),
            "staged_rows_s": round(rows / med_s, 1),
            "fused_speedup": round(r, 3),
            "samples_fused_s": t_f,
            "samples_staged_s": t_s,
        }
        log(
            f"bench: encode {name}: fused {rows / med_f / 1e6:.2f} M rows/s "
            f"vs staged {rows / med_s / 1e6:.2f} M rows/s "
            f"({r:.2f}x, byte-identical ✓)"
        )
    out["byte_identical"] = True
    _emit(out)
    return out


def main() -> None:
    build_file()
    log("bench: parity checks (isolated process; also warms the compile cache)")
    _run_phase("verify")

    # host prepare breakdown (PQT_BENCH_PREPARE=0 to skip): the serial
    # prepare wall + per-stage split that bounds the device pipeline
    r_prep = None
    if os.environ.get("PQT_BENCH_PREPARE", "1") != "0":
        r_prep = _run_phase("prepare")
        if r_prep:
            log(
                f"bench: prepare: {r_prep['prepare_ms_per_1m_rows']:.1f} ms/1M rows "
                f"serial, stages {r_prep['stage_ms']}, fused "
                f"{r_prep['fused_engaged']}/{r_prep['fused_engaged'] + r_prep['fused_declined']} "
                f"chunks, scaling {r_prep['thread_scaling']}"
            )

    # secondary metric (stderr): classic decode-to-host rows/s
    r_h = _run_phase("host")
    r_t = _run_phase("tpu_host")
    if r_h:
        log(
            f"bench: decode-to-host: host {ROWS / r_h['t'] / 1e6:.2f} M rows/s | "
            f"tpu {ROWS / r_t['t'] / 1e6:.2f} M rows/s | ratio {r_h['t'] / r_t['t']:.2f}x"
        )

    # streaming loader (PQT_BENCH_DATASET=0 to skip): multi-file rows/s at a
    # prefetch-depth sweep — the training-input side of the north star
    r_ds = None
    if os.environ.get("PQT_BENCH_DATASET", "1") != "0":
        r_ds = _run_phase("dataset")
        if r_ds:
            log(
                f"bench: dataset loader {r_ds['rows_s'] / 1e6:.2f} M rows/s at "
                f"depth {r_ds['best_depth']} "
                f"({r_ds['vs_depth0']:.2f}x over depth 0)"
            )

    # record-assembly engine sweep (PQT_BENCH_ASSEMBLY=0 to skip): vec vs
    # scalar vs pyarrow on flat/1-level/2-level tables
    r_asm = None
    if os.environ.get("PQT_BENCH_ASSEMBLY", "1") != "0":
        r_asm = _run_phase("assembly")
        if r_asm:
            t1 = r_asm["tables"]["list1"]
            log(
                f"bench: assembly: nested vec {t1['rows_s_vec'] / 1e6:.2f} M rows/s, "
                f"{r_asm['nested_vec_vs_scalar']:.1f}x over the scalar engine"
            )

    # fused-vs-staged encode ladder (PQT_BENCH_ENCODE=0 to skip): per-shape
    # serial chunk-encode throughput, byte-identity asserted pre-timing
    r_enc = None
    if os.environ.get("PQT_BENCH_ENCODE", "1") != "0":
        r_enc = _run_phase("encode")
        if r_enc and "shapes" in r_enc:
            log(
                "bench: encode ladder: "
                + ", ".join(
                    f"{k} {v['fused_speedup']:.2f}x"
                    for k, v in r_enc["shapes"].items()
                )
            )

    # io-layer sweeps (PQT_BENCH_IO=0 to skip): coalesce gap + readahead
    # depth against a latency-injected flaky source
    r_io = None
    if os.environ.get("PQT_BENCH_IO", "1") != "0":
        r_io = _run_phase("io")
        if r_io:
            log(
                f"bench: io coalesce best gap {r_io['best_gap']} "
                f"({r_io['gap_speedup']:.2f}x over gap 0)"
            )

    # remote-IO sweep (PQT_BENCH_IO_REMOTE=0 to skip): httpstub at 0/5/25ms
    # injected RTT, auto-tuned vs fixed knobs, tiered-cache warm re-scan
    r_io_remote = None
    if os.environ.get("PQT_BENCH_IO_REMOTE", "1") != "0":
        r_io_remote = _run_phase("io_remote")
        if r_io_remote:
            log(
                f"bench: io-remote auto-tune "
                f"{r_io_remote['auto_speedup_at_max_rtt']:.2f}x fixed knobs "
                f"at {IO_REMOTE_RTTS_MS[-1]:g}ms RTT; warm tiered "
                f"{r_io_remote['warm_vs_fixed_at_max_rtt']:.1f}x"
            )

    # remote-WRITE sweep (PQT_BENCH_IO_WRITE=0 to skip): multipart HttpSink
    # into a writable httpstub at 0/5/25ms RTT, part-size sweep, every
    # committed object byte-verified
    r_io_write = None
    if os.environ.get("PQT_BENCH_IO_WRITE", "1") != "0":
        r_io_write = _run_phase("io_write")
        if r_io_write:
            log(
                f"bench: io-write {r_io_write['mb_s_at_max_rtt']:.0f} MB/s "
                f"at {IO_WRITE_RTTS_MS[-1]:g}ms RTT"
            )

    # chaos sweep (PQT_BENCH_CHAOS=0 to skip): the scripted fault schedule
    # against the SLO-controlled pipeline, breaker fast-fail, serve brownout
    r_chaos = None
    if os.environ.get("PQT_BENCH_CHAOS", "1") != "0":
        r_chaos = _run_phase("chaos")
        if r_chaos:
            log(
                f"bench: chaos: slo held (controlled) = "
                f"{r_chaos['slo_held_controlled']}, breaker fast-fail "
                f"{r_chaos['breaker']['fast_fail_ratio']:.1%} of ladder, "
                f"serve typed-only = {r_chaos['serve']['typed_only']}"
            )

    # scan-service sweep (PQT_BENCH_SERVE=0 to skip): requests/s + p50/p99
    # at client concurrency 1/4/16 against a warm daemon, cold-vs-warm plan
    r_serve = None
    if os.environ.get("PQT_BENCH_SERVE", "1") != "0":
        r_serve = _run_phase("serve")
        if r_serve:
            c16 = r_serve["concurrency_sweep"]["16"]
            log(
                f"bench: serve {c16['rps']} req/s at conc 16 "
                f"(p50 {c16['p50_ms']} ms, p99 {c16['p99_ms']} ms), "
                f"warm plan {r_serve['plan_cold_vs_warm']}x faster than cold"
            )

    # mesh-router scaling + chaos (PQT_BENCH_SERVE_MESH=0 to skip):
    # routed req/s at 1 vs 4 subprocess replicas + kill-one-replica leg
    r_mesh = None
    if os.environ.get("PQT_BENCH_SERVE_MESH", "1") != "0":
        r_mesh = _run_phase("serve_mesh")
        if r_mesh:
            log(
                f"bench: serve-mesh {r_mesh['rps_1r']} -> "
                f"{r_mesh['rps_4r']} req/s at 1->4 replicas "
                f"(x{r_mesh['scaling_ratio']}), chaos typed_only = "
                f"{r_mesh['chaos']['typed_only']}"
            )

    # data-lake ingest loop (PQT_BENCH_INGEST=0 to skip): sustained append
    # rows/s + the compaction payoff (pruned-ratio gain, filtered-scan
    # speedup) over one table
    r_ingest = None
    if os.environ.get("PQT_BENCH_INGEST", "1") != "0":
        r_ingest = _run_phase("ingest")
        if r_ingest:
            log(
                f"bench: ingest {r_ingest['append_rows_s']:,} rows/s "
                f"appended; compaction pruned-ratio gain "
                f"{r_ingest['pruned_ratio_gain']} and filtered-scan "
                f"speedup {r_ingest['scan_speedup']}x"
            )

    # query push-down sweep (PQT_BENCH_QUERY=0 to skip): vec-vs-scalar
    # residual filtering + filtered-aggregate vs row-streaming req/s
    r_query = None
    if os.environ.get("PQT_BENCH_QUERY", "1") != "0":
        r_query = _run_phase("query")
        if r_query:
            log(
                f"bench: query filter vec "
                f"{r_query['filter']['vec_vs_scalar']}x over scalar; "
                f"aggregate {r_query['serve']['aggregate_vs_stream']}x "
                "req/s over row streaming"
            )

    # BASELINE.md 5-config matrix (per-config JSON on stderr + BENCH_MATRIX.json)
    results = None
    if os.environ.get("PQT_BENCH_MATRIX", "1") != "0":
        results = run_matrix()
        try:
            Path(__file__).parent.joinpath("BENCH_MATRIX.json").write_text(
                json.dumps(results, indent=1) + "\n"
            )
        except OSError as e:  # pragma: no cover
            log(f"bench: could not write BENCH_MATRIX.json: {e}")

    # headline: columns delivered into HBM, each path in a clean process
    r_base = _run_phase("baseline")
    r_dev = _run_phase("device")
    t_base, t_dev = r_base["t"], r_dev["t"]
    r_pa = _run_phase("pyarrow")
    log(
        f"bench: external check: pyarrow decode+upload "
        f"{ROWS / r_pa['t'] / 1e6:.2f} M rows/s | device/pyarrow ratio "
        f"{r_pa['t'] / t_dev:.2f}x"
    )

    rate = ROWS / t_dev
    vs = t_base / t_dev
    log(
        f"bench: to-HBM: baseline {ROWS / t_base / 1e6:.2f} M rows/s | "
        f"device decode {rate / 1e6:.2f} M rows/s | speedup {vs:.2f}x "
        f"(medians of {max(REPEATS, 7)}; device spread "
        f"{ROWS / r_dev['t_max'] / 1e6:.1f}-{ROWS / r_dev['t_min'] / 1e6:.1f} M rows/s)"
    )
    headline = {
        "metric": (
            "rows/sec decoded into TPU HBM, NYC-taxi-like file "
            "(int64 + dict-string + delta-ts cols), device decode "
            "vs host decode + upload"
        ),
        "value": round(rate, 1),
        "unit": "rows/s",
        "vs_baseline": round(vs, 3),
        "device": r_dev["device"],
        "stat": "median",
        "value_min": round(ROWS / r_dev["t_max"], 1),
        "value_max": round(ROWS / r_dev["t_min"], 1),
        "vs_baseline_min": round(r_base["t_min"] / r_dev["t_max"], 3),
        "vs_baseline_max": round(r_base["t_max"] / r_dev["t_min"], 3),
        # the EXTERNAL comparator (pyarrow decode + upload at the
        # same delivery point): stable across rounds, unlike our
        # own host baseline, which each round's host-lane work
        # speeds up
        "rows_s_pyarrow": round(ROWS / r_pa["t"], 1),
        "vs_pyarrow": round(r_pa["t"] / t_dev, 3),
        # host prepare breakdown (make bench-prepare for the full
        # standalone report): the serial stage split that bounds
        # prepare/RPC overlap
        **(
            {
                "prepare_ms_per_1m_rows": r_prep["prepare_ms_per_1m_rows"],
                "prepare_stage_ms": r_prep["stage_ms"],
                "prepare_thread_scaling": r_prep["thread_scaling"],
            }
            if r_prep
            else {}
        ),
    }
    print(json.dumps(headline))
    # the file artifact carries the full structured round: headline +
    # complete prepare breakdown + the matrix configs (stdout keeps the
    # one-line headline contract)
    artifact = dict(headline)
    if r_prep:
        artifact["prepare"] = r_prep
    if r_ds:
        artifact["dataset"] = r_ds
    if r_io:
        artifact["io"] = r_io
    if r_io_remote:
        artifact["io_remote"] = r_io_remote
    if r_io_write:
        artifact["io_write"] = r_io_write
    if r_serve:
        artifact["serve"] = r_serve
    if r_mesh:
        artifact["mesh"] = r_mesh
    if r_query:
        artifact["query"] = r_query
    if r_ingest:
        artifact["ingest"] = r_ingest
    if r_chaos:
        artifact["chaos"] = r_chaos
    if r_asm:
        artifact["assembly"] = r_asm
    if r_enc:
        artifact["encode"] = r_enc
    if results is not None:
        artifact["matrix"] = results
        for r in results:
            if r.get("config") == "write":
                artifact["write"] = r  # the write-path result, addressable
    _write_artifact(artifact)


def _verify_host_paths(host, tpu) -> None:
    from parquet_tpu.core.arrays import ByteArrayData

    for rg_h, rg_t in zip(host, tpu):
        assert rg_h.keys() == rg_t.keys()
        for path in rg_h:
            a, b = rg_h[path].values, rg_t[path].values
            if isinstance(a, ByteArrayData):
                assert isinstance(b, ByteArrayData)
                assert np.array_equal(a.offsets, b.offsets) and a.data == b.data, path
            else:
                av, bv = np.asarray(a), np.asarray(b)
                assert av.dtype == bv.dtype, (path, av.dtype, bv.dtype)
                assert np.array_equal(
                    av.view((np.uint8, av.dtype.itemsize)),
                    bv.view((np.uint8, bv.dtype.itemsize)),
                ), path
            for attr in ("def_levels", "rep_levels"):
                la, lb = getattr(rg_h[path], attr), getattr(rg_t[path], attr)
                assert (la is None) == (lb is None), (path, attr)
                assert la is None or np.array_equal(la, lb), (path, attr)
    log("bench: byte-identical host vs tpu decode (values + levels) ✓")


def _metric_direction(key: str) -> int:
    """+1: higher is better (throughputs, speedups). -1: lower is better
    (latencies, walls). 0: untracked (counts, depths, config echoes) —
    reported but never gating. Keyed on the LEAF name only, so the rule
    set survives new sections without a registry."""
    k = key.lower()
    if k.endswith("_ms") or "ms_per" in k or k in ("t", "wall_s", "wait_s"):
        return -1
    if (
        "rows_s" in k
        or "req_s" in k
        or k.startswith("rps")  # serve "rps", mesh "rps_1r"/"rps_4r"
        or "speedup" in k
        or k.startswith("vs_")
        or k.endswith("_ratio")
        or k.endswith("_gain")  # ingest.pruned_ratio_gain and kin
        or k == "value"
    ):
        return +1
    return 0


def _numeric_leaves(obj, prefix=""):
    """Flatten nested dicts AND lists to {dotted.path: float} (bools
    excluded). Lists index positionally (`matrix.0.t`) — the artifact's
    matrix section is ordered by config, so position is identity; skipping
    lists would silently exempt the whole matrix from the gate."""
    out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(_numeric_leaves(v, f"{prefix}{k}."))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            out.update(_numeric_leaves(v, f"{prefix}{i}."))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix[:-1]] = float(obj)
    return out


# -- the persistent bench trend store ------------------------------------------

_HISTORY_DEFAULT = Path(__file__).resolve().parent / "BENCH_history.jsonl"


def _git_rev() -> str:
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            cwd=str(Path(__file__).resolve().parent),
            timeout=10,
        )
        if out.returncode == 0:
            rev = out.stdout.decode().strip()
            if rev:
                return rev
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def _config_fingerprint() -> tuple:
    """(digest, basis): a short stable hash of everything that shapes a
    bench round's numbers besides the code — the PQT_* size knobs, the jax
    platform selection, python and machine — so the trend view can tell a
    real regression from a config change."""
    import hashlib
    import platform

    basis = {
        "env": {
            k: v
            for k, v in sorted(os.environ.items())
            if k.startswith("PQT_") or k == "JAX_PLATFORMS"
        },
        "python": platform.python_version(),
        "machine": platform.machine(),
        # core count shapes every pool sweep (thread scaling, parallel
        # encode, serve concurrency): a 1.0x pool result on an nproc=1
        # box is the MACHINE, not a regression — record it so the trend
        # reader can tell
        "nproc": os.cpu_count() or 1,
    }
    digest = hashlib.sha256(
        json.dumps(basis, sort_keys=True).encode()
    ).hexdigest()[:12]
    return digest, basis


def _read_history(path) -> list:
    """Parse + schema-validate the trend store. Every entry must carry
    label/recorded_at/git_rev/config/artifact — a malformed line is a
    hard exit, not a skip: silently dropping rounds would make the trend
    LIE about the trajectory."""
    entries = []
    for i, line in enumerate(Path(path).read_text().splitlines()):
        if not line.strip():
            continue
        try:
            e = json.loads(line)
        except ValueError:
            raise SystemExit(
                f"bench history: {path} line {i + 1} is not valid JSON"
            ) from None
        if not isinstance(e, dict):
            raise SystemExit(f"bench history: {path} line {i + 1} is not an object")
        for k in ("label", "recorded_at", "git_rev", "config", "artifact"):
            if k not in e:
                raise SystemExit(
                    f"bench history: {path} line {i + 1} missing {k!r}"
                )
        if not isinstance(e["artifact"], dict):
            raise SystemExit(
                f"bench history: {path} line {i + 1} artifact is not an object"
            )
        entries.append(e)
    return entries


def _phase_record(artifact_path: str, history_path, label) -> None:
    """Append one --json artifact to the trend store with its provenance."""
    from datetime import datetime, timezone

    art = json.loads(Path(artifact_path).read_text())
    if not isinstance(art, dict):
        raise SystemExit(f"bench record: {artifact_path} is not a JSON object")
    history = Path(history_path)
    entries = _read_history(history) if history.exists() else []
    if label is None:
        # continue the rNN sequence from the HIGHEST recorded round (the
        # store ships seeded at r06; plain len+1 would collide with it)
        ns = [
            int(e["label"][1:])
            for e in entries
            if re.fullmatch(r"r\d+", e["label"])
        ]
        label = f"r{(max(ns) if ns else len(entries)) + 1:02d}"
    if any(e["label"] == label for e in entries):
        raise SystemExit(
            f"bench record: label {label!r} already recorded in {history} "
            "(pass --label to name this round)"
        )
    # provenance preference: the fingerprint the artifact captured at RUN
    # time (bench_config, stamped by _write_artifact) — the env of this
    # --record invocation may differ from the env the numbers ran under
    embedded = art.get("bench_config")
    if isinstance(embedded, dict) and embedded.get("fingerprint"):
        digest = embedded["fingerprint"]
        basis = embedded.get("basis", {})
    else:
        digest, basis = _config_fingerprint()
    entry = {
        "label": label,
        "recorded_at": datetime.now(timezone.utc).isoformat(
            timespec="seconds"
        ),
        "git_rev": _git_rev(),
        "config": digest,
        "config_basis": basis,
        "artifact": art,
    }
    with open(history, "a") as f:
        f.write(json.dumps(entry) + "\n")
    n_tracked = sum(
        1
        for k in _numeric_leaves(art)
        if _metric_direction(k.rsplit(".", 1)[-1]) != 0
    )
    print(
        f"bench record: {label} <- {artifact_path} "
        f"(git {entry['git_rev']}, cfg {digest}, {n_tracked} tracked "
        f"metrics) -> {history} ({len(entries) + 1} rounds)"
    )


def _phase_trend(history_path, section=None) -> None:
    """Render every tracked metric across the recorded rounds (newest on
    the right) with the last-vs-first ratio, direction-aware."""
    history = Path(history_path)
    if not history.exists():
        raise SystemExit(
            f"bench trend: no trend store at {history} "
            "(record a round first: bench.py --record artifact.json)"
        )
    entries = _read_history(history)
    if not entries:
        raise SystemExit(f"bench trend: {history} is empty")
    labels = [e["label"] for e in entries]
    leaves = [_numeric_leaves(e["artifact"]) for e in entries]
    keys = []  # tracked leaves, in first-seen order across rounds
    seen = set()
    for lv in leaves:
        for k in lv:
            if k in seen or _metric_direction(k.rsplit(".", 1)[-1]) == 0:
                continue
            seen.add(k)
            keys.append(k)
    if section is not None:
        keys = [
            k
            for k in keys
            if (k.split(".", 1)[0] if "." in k else "(headline)") == section
        ]
    configs = {e["config"] for e in entries}
    rounds = ", ".join(
        "{}@{}".format(e["label"], e["git_rev"][:7]) for e in entries
    )
    print(f"bench trend: {len(entries)} rounds in {history} ({rounds})")
    if len(configs) > 1:
        print(
            "bench trend: NOTE rounds span "
            f"{len(configs)} config fingerprints — deltas may reflect "
            "config changes, not code"
        )
    # surface the recorded core count: pool-scaling metrics (thread
    # sweeps, parallel encode, serve concurrency) are meaningless to
    # compare across machines with different nproc — and read as flat
    # "regressions" on an nproc=1 box
    nproc_cells = [
        str(e.get("config_basis", {}).get("nproc", "?")) for e in entries
    ]
    if any(c != "?" for c in nproc_cells):
        print(f"bench trend: nproc per round: {' -> '.join(nproc_cells)}")
    last_section = None
    width = max((len(k) for k in keys), default=10)
    for k in keys:
        sec = k.split(".", 1)[0] if "." in k else "(headline)"
        if sec != last_section:
            print(f"  [{sec}]")
            last_section = sec
        vals = [lv.get(k) for lv in leaves]
        cells = " -> ".join("-" if v is None else f"{v:g}" for v in vals)
        present = [v for v in vals if v is not None]
        tail = ""
        if len(present) >= 2 and present[0]:
            ratio = present[-1] / present[0]
            direction = _metric_direction(k.rsplit(".", 1)[-1])
            better = (ratio > 1) if direction > 0 else (ratio < 1)
            verdict = "improved" if better else "regressed"
            if 0.98 <= ratio <= 1.02:
                verdict = "held"
            tail = f"  x{ratio:.3f} {verdict}"
        print(f"    {k:<{width}}  {cells}{tail}")
    print(
        f"bench trend: {len(keys)} tracked metrics across "
        f"{len(labels)} rounds ✓"
    )


def _phase_compare(old_path, new_path: str, threshold: float) -> None:
    """Diff two --json artifacts; exit 1 when a tracked metric regresses
    past `threshold` (fractional, default 0.10). `old_path` may be a
    (name, dict) pair — how the single-path form passes the latest
    recorded history round in."""
    if isinstance(old_path, tuple):
        old_path, old = old_path
    else:
        old = json.loads(Path(old_path).read_text())
    new = json.loads(Path(new_path).read_text())
    ol, nl = _numeric_leaves(old), _numeric_leaves(new)
    shared = sorted(set(ol) & set(nl))
    only_old = sorted(set(ol) - set(nl))
    only_new = sorted(set(nl) - set(ol))
    regressions = []
    compared = 0
    last_section = None
    print(f"bench compare: {old_path} -> {new_path} (threshold {threshold:.0%})")
    for path in shared:
        section = path.split(".", 1)[0] if "." in path else "(headline)"
        leaf = path.rsplit(".", 1)[-1]
        direction = _metric_direction(leaf)
        a, b = ol[path], nl[path]
        if direction == 0:
            continue  # tracked table first; untracked summarized below
        compared += 1
        if section != last_section:
            print(f"  [{section}]")
            last_section = section
        ratio = (b / a) if a else float("inf")
        # the regression sign follows the metric's direction: a throughput
        # regresses by FALLING, a latency by RISING
        delta = (b - a) / a if a else 0.0
        regressed = (
            (direction > 0 and delta < -threshold)
            or (direction < 0 and delta > threshold)
        )
        better = "lower" if direction < 0 else "higher"
        flag = "  REGRESSED" if regressed else ""
        print(
            f"    {path}: {a:g} -> {b:g}  x{ratio:.3f} "
            f"({better}-is-better){flag}"
        )
        if regressed:
            regressions.append((path, a, b))
    changed = sum(
        1
        for p in shared
        if _metric_direction(p.rsplit(".", 1)[-1]) == 0 and ol[p] != nl[p]
    )
    print(
        f"bench compare: {len(shared)} shared leaves "
        f"({changed} untracked changed), "
        f"{len(only_old)} only in old, {len(only_new)} only in new"
    )
    if only_new:
        print(f"bench compare: new sections/leaves: {', '.join(only_new[:8])}"
              + (" ..." if len(only_new) > 8 else ""))
    # a tracked metric that VANISHED can't gate numerically, but silence
    # would read as "held" — name it so the reader decides
    lost = [
        p for p in only_old if _metric_direction(p.rsplit(".", 1)[-1]) != 0
    ]
    for p in lost:
        print(f"bench compare: WARNING tracked metric only in old: {p}")
    if regressions:
        for path, a, b in regressions:
            print(f"bench compare: REGRESSION {path}: {a:g} -> {b:g}")
        raise SystemExit(1)
    if compared == 0:
        # disjoint artifacts (different phases, a crashed run): exiting 0
        # here would green a CI gate that compared NOTHING
        raise SystemExit(
            "bench compare: no tracked metrics in common — nothing was "
            "compared (are these artifacts from the same bench phase?)"
        )
    print(f"bench compare: no tracked regressions in {compared} metrics ✓")


def _pop_opt(args: list, name: str):
    """Pop `NAME VALUE` out of args (mutating); None when absent, clean
    SystemExit when the value is missing — the one copy of the edge case
    every hand-rolled flag below shares."""
    if name not in args:
        return None
    k = args.index(name)
    if k + 1 >= len(args):
        raise SystemExit(f"bench: {name} needs a value")
    val = args[k + 1]
    del args[k : k + 2]
    return val


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--json" in argv:
        k = argv.index("--json")
        if k + 1 >= len(argv):
            raise SystemExit("bench: --json needs a path")
        _JSON_OUT = argv[k + 1]
        del argv[k : k + 2]
    if argv and argv[0] == "--compare":
        rest = argv[1:]
        raw_thr = _pop_opt(rest, "--threshold")
        if raw_thr is None:
            thr = 0.10
        else:
            try:
                thr = float(raw_thr)
            except ValueError:
                raise SystemExit(
                    f"bench: --threshold needs a number, got {raw_thr!r}"
                ) from None
        history = _pop_opt(rest, "--history") or _HISTORY_DEFAULT
        paths = [a for a in rest if not a.startswith("--")]
        if len(paths) not in (1, 2) or len(paths) != len(rest):
            raise SystemExit(
                "bench: --compare needs [OLD.json] NEW.json "
                "[--threshold FRACTION] [--history PATH] — with one path "
                "the old side is the latest round in BENCH_history.jsonl"
            )
        if len(paths) == 1:
            # old side defaults to the LATEST recorded round: the one-arg
            # form IS the trajectory gate against the trend store
            if not Path(history).exists():
                raise SystemExit(
                    f"bench compare: no trend store at {history} to "
                    "compare against (record a round first, or pass "
                    "OLD.json explicitly)"
                )
            entries = _read_history(history)
            if not entries:
                raise SystemExit(f"bench compare: {history} is empty")
            latest = entries[-1]
            old_side = (
                f"{history}[{latest['label']}]",
                latest["artifact"],
            )
            _phase_compare(old_side, paths[0], thr)
        else:
            _phase_compare(paths[0], paths[1], thr)
    elif argv and argv[0] == "--record":
        rest = argv[1:]
        history = _pop_opt(rest, "--history") or _HISTORY_DEFAULT
        label = _pop_opt(rest, "--label")
        paths = [a for a in rest if not a.startswith("--")]
        if not paths and _JSON_OUT:
            paths = [_JSON_OUT]  # record the artifact --json just named
        if len(paths) != 1 or [a for a in rest if a.startswith("--")]:
            raise SystemExit(
                "bench: --record needs ARTIFACT.json "
                "[--label NAME] [--history PATH]"
            )
        _phase_record(paths[0], history, label)
    elif argv and argv[0] == "--trend":
        rest = argv[1:]
        history = _pop_opt(rest, "--history") or _HISTORY_DEFAULT
        section = _pop_opt(rest, "--section")
        if rest:
            raise SystemExit(
                "bench: --trend takes [--history PATH] [--section NAME]"
            )
        _phase_trend(history, section)
    elif argv and argv[0] == "--dataset":
        _phase_dataset()
    elif argv and argv[0] == "--assembly":
        _phase_assembly()
    elif argv and argv[0] == "--io":
        _phase_io()
    elif argv and argv[0] == "--io-remote":
        _phase_io_remote()
    elif argv and argv[0] == "--io-write":
        _phase_io_write()
    elif argv and argv[0] == "--write":
        _phase_write()
    elif argv and argv[0] == "--encode":
        _phase_encode()
    elif argv and argv[0] == "--serve":
        _phase_serve()
    elif argv and argv[0] == "--serve-mesh":
        _phase_serve_mesh()
    elif argv and argv[0] == "--query":
        _phase_query()
    elif argv and argv[0] == "--device":
        _phase_device()
    elif argv and argv[0] == "--chaos":
        _phase_chaos()
    elif argv and argv[0] == "--ingest":
        _phase_ingest()
    elif len(argv) >= 2 and argv[0] == "--phase":
        name = argv[1]
        if name in _DEVICE_PHASES:
            _require_device()
        if name.startswith("matrix"):
            _phase_matrix(int(name[len("matrix") :]))
        elif name == "write":
            _phase_write()
        elif name == "encode":
            _phase_encode()
        elif name == "verify":
            _phase_verify(build_file())
        elif name == "prepare":
            _phase_prepare()
        elif name == "dataset":
            _phase_dataset()
        elif name == "io":
            _phase_io()
        elif name == "io_remote":
            _phase_io_remote()
        elif name == "io_write":
            _phase_io_write()
        elif name == "serve":
            _phase_serve()
        elif name == "serve_mesh":
            _phase_serve_mesh()
        elif name == "query":
            _phase_query()
        elif name == "device_query":
            _phase_device()
        elif name == "chaos":
            _phase_chaos()
        elif name == "ingest":
            _phase_ingest()
        elif name == "assembly":
            _phase_assembly()
        else:
            _phase_timed(name, build_file())
    else:
        main()
