"""FileReader: the low-level public read API.

Equivalent of the reference's FileReader (reference: file_reader.go:15-27
type, :32-63 ctor, :186-207 row-group seek/skip, :258-272 NextRow), redesigned
column-first: the primary read unit is a row group's worth of decoded column
arrays (`read_row_group`), which is what the TPU pipeline consumes; row
iteration (`iter_rows`) is record assembly layered on top.

Options mirror the reference's functional options (file_reader.go:89-149):
column projection, CRC validation, memory ceiling, pre-parsed metadata, and —
new here — decoder backend selection (host NumPy vs TPU kernels), the
WithDecoderBackend(TPU) of the north star.
"""

from __future__ import annotations

import gc
import itertools
import os
import threading
from collections import deque
from contextlib import contextmanager, nullcontext
from functools import partial
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from pathlib import Path
from typing import NamedTuple

from ..io.planner import DEFAULT_COALESCE_GAP, fetch_ranges
from ..io.source import SourceFile, open_source
from ..meta.file_meta import ParquetFileError, read_file_metadata
from ..meta.parquet_types import FileMetaData, RowGroup
from .alloc import AllocTracker
from .assembly import RecordAssembler
from .assembly_vec import (
    _zip_dict_rows,
    assemble_row_columns,
    slice_column,
    vec_enabled,
)
from .chunk import ChunkData, ChunkError, read_chunk
from .page import PageError
from .schema import Schema
from ..meta.thrift import ThriftError
from ..obs.log import log_event as _log_event
from ..obs.pool import instrumented_submit
from ..utils import metrics as _metrics
from ..utils.trace import active as _trace_active, bump, name_os_thread, span, stage, timed_stage

__all__ = ["FileReader", "PARQUET_ERRORS", "resolve_column_prefixes"]

# The typed malformed-file error family: everything a corrupt or lying file
# can legally raise out of a read. Anything else escaping a decode is a bug
# the fault-injection harness (parquet_tpu.testing.faults) hunts for.
PARQUET_ERRORS = (ParquetFileError, ChunkError, PageError, ThriftError)


def resolve_column_prefixes(schema: Schema, columns):
    """Resolve a column projection against a parsed schema: each entry is a
    dotted (or tuple) path prefix selecting every leaf under it — the
    reference's SetSelectedColumns convention. Returns the selected leaf
    path set (None = all), raising the typed error for unknown prefixes.
    Module-level so metadata-only callers (serve planning) validate with
    the exact semantics FileReader applies, without opening the file."""
    if columns is None:
        return None
    selected = set()
    for c in columns:
        path = tuple(c.split(".")) if isinstance(c, str) else tuple(c)
        hits = [
            leaf.path
            for leaf in schema.leaves
            if leaf.path[: len(path)] == path
        ]
        if not hits:
            raise ParquetFileError(f"parquet: selected column {c!r} not in schema")
        selected.update(hits)
    return selected


class _GroupQuarantined(Exception):
    """Internal control flow for on_error != 'raise': the current row group
    cannot be delivered (a required column was corrupt, or the policy is
    'skip'). Never escapes FileReader."""

_pool: ThreadPoolExecutor | None = None
_pool_lock = threading.Lock()

# How many row groups lists="pack" stages ahead of the one it delivers. A
# group is one chunk, which one pool thread prepares in about twice the time
# the consumer takes to deliver and pack a group (tok-8k.packed): with one
# group ahead the prepare would set the pace, with two it stays under the
# consumer's.
_PACKED_LOOKAHEAD = 2


def _host_pool() -> ThreadPoolExecutor | None:
    """Shared worker pool for the host-side chunk prepare phase.

    Sized by PQT_HOST_THREADS (default: cpu count, capped at 16). The cap is
    real parallelism, not oversubscription insurance: the fused native
    chunk-prepare walk (decompress + level decode + prescan + repack) runs
    the whole chunk in one GIL-free C call, so N workers deliver ~N cores of
    prepare throughput until memory bandwidth saturates. Returns None when
    threading cannot help (single worker): single-core hosts, or the knob
    set to 0/1.
    """
    global _pool
    env = os.environ.get("PQT_HOST_THREADS")
    workers = int(env) if env else min(os.cpu_count() or 1, 16)
    if workers <= 1:
        return None
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                max_workers=workers,
                thread_name_prefix="pqt-host",
                initializer=name_os_thread,
            )
        return _pool


def _with_device(fn, device):
    """Run `fn` under jax.default_device(device) (plain call when None).

    Device placement must travel WITH the callable onto whatever thread runs
    it: jax.default_device is thread-local, so a context entered on the
    caller's thread never reaches the `pqt-dispatch` worker. Every dispatch
    submission routes through this so an explicit `device=` is honored by
    every jnp.asarray the plan issues."""
    if device is None:
        return fn()
    import jax

    with jax.default_device(device):
        return fn()


def _chunk_args(group: int, path) -> dict:
    """The identifier one chunk's chunk.prepare -> dispatch -> deliver spans
    share across the three threads that serve it."""
    return {"group": group, "column": ".".join(path)}


def _wait(name: str, fut, group: int, path):
    """Block on a chunk's pool future as the `name` stage, on the waiting
    thread: plan.wait_prepare (the planning thread, before it may enqueue
    the chunk's dispatch) or plan.wait_dispatch (the consumer, before
    _deliver). Time WAITED, beside chunk.prepare's and dispatch's time
    busy: where an idle gap of the device falls under a wait and under no
    producer, it is the hop between threads. A future that is done costs
    the stage() call; with no trace active, one contextvar read."""
    if not _trace_active():
        return fut.result()
    with stage(name, args=_chunk_args(group, path)):
        return fut.result()


def _dispatch_traced(fn, device, args):
    """Dispatch-thread task wrapper: device pinning plus a 'dispatch' stage
    so traces attribute transfer/launch wall time to the pqt-dispatch lane
    (the trace itself arrives via instrumented_submit's context carry);
    the plan's dispatch.upload / dispatch.launch stages nest inside it."""
    with stage("dispatch", args=args):
        return _with_device(fn, device)


def _dispatch_pool() -> ThreadPoolExecutor:
    """The process-wide single-thread device-dispatch executor. Lives in
    kernels/pipeline.py (next to the device pipeline it feeds, shared with
    the dataset layer's batch uploads); imported lazily so pure host reads
    never pull jax in."""
    from ..kernels.pipeline import dispatch_pool

    return dispatch_pool()


def _timed_rows(assembler):
    """Stream rows from the scalar cursor walk, billing per-row time to the
    'assembly.rows' stage without materializing the row group.
    record_span=False: one sub-microsecond span PER ROW would flood the
    trace's event budget and crowd out the chunk/page hierarchy — the
    aggregate stays exact. Row count and wall time also feed the always-on
    assembly_rows_total{engine="scalar"} / assembly_seconds families."""
    it = iter(assembler)
    n = 0
    seconds = 0.0
    try:
        while True:
            with timed_stage("assembly.rows", record_span=False) as el:
                try:
                    row = next(it)
                except StopIteration:
                    break
            n += 1
            seconds += el.seconds
            yield row
    finally:
        # also runs when the consumer abandons the generator: delivered
        # rows still count
        _metrics.inc("assembly_rows_total", n, engine="scalar")
        _metrics.observe("assembly_seconds", seconds)


def _scatter_byte_offsets(valid: np.ndarray, offsets) -> np.ndarray:
    """Dense byte-array offsets (non-null cells only) -> offsets positioned
    at every slot, int64[len(valid) + 1], null slots zero-length. Shared by
    the flat and list to_arrow paths."""
    idx = np.clip(np.cumsum(valid) - 1, 0, None)
    ends = np.asarray(offsets[1:], dtype=np.int64)
    picked = ends[idx] if len(ends) else np.zeros(len(valid), dtype=np.int64)
    out = np.concatenate(
        [np.zeros(1, dtype=np.int64), np.where(valid, picked, 0)]
    )
    np.maximum.accumulate(out, out=out)
    return out


def _concat_group_tables(pa, parts):
    """Concatenate per-row-group pyarrow tables of the SAME selection,
    normalizing dictionary-vs-plain per column exactly like to_arrow's
    cross-group chunk assembly (a group with PLAIN fallback pages decodes
    plain while its siblings stay dictionary-typed). None for no parts."""
    if not parts:
        return None
    if len(parts) == 1:
        return parts[0]
    names = parts[0].column_names
    arrays = []
    for name in names:
        cols = [p.column(name) for p in parts]
        is_dict = [pa.types.is_dictionary(c.type) for c in cols]
        if any(is_dict) and not all(is_dict):
            cols = [
                c.cast(c.type.value_type) if pa.types.is_dictionary(c.type) else c
                for c in cols
            ]
        arrays.append(
            pa.chunked_array(
                [ch for c in cols for ch in c.chunks], type=cols[0].type
            )
        )
    return pa.table(dict(zip(names, arrays)))


class RaggedColumn(NamedTuple):
    """A LIST column in device-batch form: `values` is row-padded to a
    static [rows, max_list_len] matrix (unused slots zero-filled on device)
    and `lengths` is the int32 element count per row — the TPU-native
    ragged representation (a NamedTuple = a jax pytree node, so a jitted
    step takes the pair and masks with
    `jnp.arange(K) < col.lengths[:, None]`). Null and empty lists both have
    length 0."""

    values: object  # jax.Array[rows, max_list_len]
    lengths: object  # jax.Array[rows] int32


_pad_ragged_jit = None


def _pad_ragged_device(values, lengths, max_len: int) -> RaggedColumn:
    """Scatter a flat element vector into [rows, max_len] ON DEVICE: row
    offsets come from a cumsum of lengths, each row gathers its slice, and
    slots past the row's length zero-fill. Static shapes — one compile per
    (rows, element-count bucket, max_len, dtype)."""
    global _pad_ragged_jit
    import jax
    import jax.numpy as jnp

    if _pad_ragged_jit is None:
        from ..kernels.device_ops import prefix_sum

        @partial(jax.jit, static_argnames=("max_len",))
        def pad(v, ln, max_len):
            offs = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), prefix_sum(ln.astype(jnp.int32))]
            )
            idx = offs[:-1, None] + jnp.arange(max_len, dtype=jnp.int32)[None, :]
            nv = v.shape[0]
            mask = jnp.arange(max_len, dtype=jnp.int32)[None, :] < ln[:, None]
            zero = jnp.zeros((), v.dtype)
            return jnp.where(mask, v[jnp.clip(idx, 0, nv - 1)], zero)

        _pad_ragged_jit = pad
    from ..kernels.pipeline import _pad_device

    # the element count differs in every group: bucket-pad it (see
    # _expand_nullable_device); slots past a row's length are masked
    return RaggedColumn(
        values=_pad_ragged_jit(_pad_device(values), lengths, max_len),
        lengths=lengths,
    )


class MaskedColumn(NamedTuple):
    """A nullable column in device-batch form: `values` are row-aligned with
    null rows zero-filled on device; `mask` is True where the row is
    non-null — the TPU-native validity representation (NamedTuple = a jax
    pytree node, so a jitted step takes the pair directly and computes e.g.
    `jnp.where(col.mask, col.values, fill)` with no host trip)."""

    values: object  # jax.Array[n] of the column dtype
    mask: object    # jax.Array[n] bool


_expand_nullable_jit = None


def _expand_nullable_device(values, mask) -> MaskedColumn:
    """Scatter the dense non-null values into row positions ON DEVICE (nulls
    zero-filled): prefix-sum the validity mask into a gather index — the same
    levels-to-rows math as host null expansion, but no host round-trip. The
    jitted kernel is module-cached so repeated groups hit the compile cache."""
    global _expand_nullable_jit
    import jax
    import jax.numpy as jnp

    if _expand_nullable_jit is None:
        from ..kernels.device_ops import prefix_sum

        @jax.jit
        def expand(v, m):
            idx = prefix_sum(m.astype(jnp.int32)) - 1
            idx = jnp.clip(idx, 0, v.shape[0] - 1)
            zero = jnp.zeros((), v.dtype)
            return jnp.where(m, v[idx], zero)

        _expand_nullable_jit = expand
    from ..kernels.pipeline import _pad_device

    # the non-null count differs in every group: pad to its bucket so the
    # program compiles once per bucket, not once per group (seconds each on
    # a TPU); the padding is never gathered — idx stays below the count
    return MaskedColumn(
        values=_expand_nullable_jit(_pad_device(values), mask), mask=mask
    )


# Rows materialize in windows this size: cyclic GC cost scales with LIVE
# tracked containers, so bounded windows keep collections cheap while
# consumers that drop rows as they go (aggregations, filters) never hold a
# whole 1M-row group of dicts.
_ASSEMBLE_WINDOW = 1 << 16


@contextmanager
def _gc_paused():
    """Pause cyclic GC around a bulk container build: each incremental
    collection re-scans the still-growing result (~25% of assembly wall
    time) and nothing in row assembly creates reference cycles."""
    was_enabled = gc.isenabled()
    if was_enabled:
        gc.disable()
    try:
        yield
    finally:
        if was_enabled:
            gc.enable()


class FileReader:
    """Reads Parquet files: footer metadata, row groups, records.

    Usage:
        with FileReader("file.parquet") as r:
            cols = r.read_row_group(0)          # columnar (dict path -> ChunkData)
            for row in r.iter_rows():           # assembled records
                ...
    """

    def __init__(
        self,
        source,
        columns=None,
        *,
        validate_crc: bool = False,
        max_memory: int | None = None,
        metadata: FileMetaData | None = None,
        schema: Schema | None = None,
        backend: str = "host",
        compact_levels: bool = False,
        device=None,
        on_error: str = "raise",
        block_cache=None,
        footer_cache=None,
        coalesce_gap: int | None = None,
    ):
        # Every byte this reader touches flows through a ByteSource
        # (parquet_tpu.io.source): str/Path opens a lock-free pread-backed
        # LocalFileSource, a ByteSource (e.g. a RetryingSource over a remote
        # store) passes through, bytes/BytesIO/file-likes adapt. self._f is
        # a per-reader SourceFile cursor for the stream-shaped page walks.
        self._source, self._owns_file = open_source(source)
        self._f = SourceFile(self._source)
        # block_cache: a shared io.cache.BlockCache (or io.tiercache
        # TieredCache — same contract) chunk/range reads check before
        # touching the source (the dataset layer passes one so readahead
        # and repeated epochs hit memory). footer_cache: an
        # io.cache.FooterCache consulted/filled for path sources, so a
        # re-opened file parses its footer zero times. coalesce_gap:
        # an explicit byte gap, None (the 64 KiB local default) or
        # "auto" — resolve per fetch through the io.autotune profile of
        # this source's transport (remote stores coalesce MiB-scale).
        self._block_cache = block_cache
        if coalesce_gap is None:
            self._coalesce_gap = DEFAULT_COALESCE_GAP
        elif coalesce_gap == "auto":
            self._coalesce_gap = "auto"
        else:
            self._coalesce_gap = int(coalesce_gap)
        try:
            if metadata is not None:
                self.metadata = metadata
            else:
                path_key = (
                    str(source) if isinstance(source, (str, Path)) else None
                )
                # URL keys can't os.stat: validate against the remote
                # source's generation (size, ETag) instead
                gen = (
                    self._source.generation() if path_key is not None else None
                )
                cached = (
                    footer_cache.get(path_key, sig=gen)
                    if footer_cache is not None and path_key is not None
                    else None
                )
                if cached is not None:
                    self.metadata = cached
                else:
                    self.metadata = read_file_metadata(self._f)
                    if footer_cache is not None and path_key is not None:
                        footer_cache.put(path_key, self.metadata, sig=gen)
            # schema=: a pre-built Schema for this metadata (high-churn
            # callers like the dataset layer open one reader per row group;
            # rebuilding the schema tree from thrift every open is waste)
            self.schema = (
                schema
                if schema is not None
                else Schema.from_thrift(self.metadata.schema)
            )
            self.validate_crc = validate_crc
            self.alloc = AllocTracker(max_memory) if max_memory else None
            if backend not in ("host", "tpu", "tpu_roundtrip"):
                raise ValueError(
                    f"unknown backend {backend!r}: expected 'host', 'tpu', "
                    "or 'tpu_roundtrip'"
                )
            self.backend = backend
            # on_error: corruption-isolation policy for host-delivery reads
            # (read_row_group / iter_rows / to_arrow).
            #   "raise" (default)  the first typed Parquet error aborts the read
            #   "skip"             a corrupt column chunk quarantines its whole
            #                      row group (dropped; counters:
            #                      chunks_quarantined / row_groups_quarantined)
            #   "null"             the corrupt chunk delivers as all-null when
            #                      its column is optional; required columns
            #                      degrade to "skip" for that group
            # Device-resident delivery (read_row_group_device, device batches)
            # always raises: a training loop silently missing rows is worse
            # than a crash.
            if on_error not in ("raise", "skip", "null"):
                raise ValueError(
                    f"unknown on_error {on_error!r}: expected 'raise', "
                    "'skip', or 'null'"
                )
            self.on_error = on_error
            # compact_levels: R/D levels of delivered columns are stored
            # bit-packed (PackedLevels, width = bits(max_level)) instead of
            # uint16 arrays — the reference's packed_array memory layout
            # (packed_array.go:13-101), ~16x smaller at rest. Consumers widen
            # windows on demand; NumPy comparisons work transparently.
            self.compact_levels = compact_levels
            # device: an explicit jax.Device every delivered array is pinned
            # to — including work issued from the internal dispatch thread,
            # which a caller-side jax.default_device context (thread-local)
            # can never reach. None = the process default device.
            self.device = device
            self._selected = self._resolve_columns(columns)
        except BaseException:
            if self._owns_file:
                self._source.close()
            raise

    # -- properties ------------------------------------------------------------

    @property
    def num_rows(self) -> int:
        return self.metadata.num_rows or 0

    @property
    def num_row_groups(self) -> int:
        return len(self.metadata.row_groups or [])

    @property
    def created_by(self) -> str | None:
        return self.metadata.created_by

    @property
    def key_value_metadata(self) -> dict[str, str | None]:
        return {
            kv.key: kv.value for kv in (self.metadata.key_value_metadata or [])
        }

    def row_group(self, i: int) -> RowGroup:
        groups = self.metadata.row_groups or []
        if not 0 <= i < len(groups):
            raise IndexError(f"row group {i} out of range (file has {len(groups)})")
        return groups[i]

    # -- column selection (reference: file_reader.go SetSelectedColumns, schema.go:347-367)

    def _resolve_columns(self, columns):
        return resolve_column_prefixes(self.schema, columns)

    def set_selected_columns(self, *columns) -> None:
        self._selected = self._resolve_columns(columns if columns else None)

    # -- columnar reads --------------------------------------------------------

    def _pack_chunk_levels(self, path, delivered):
        """Swap a delivered ChunkData/DeviceColumn's level arrays for their
        bit-packed form (compact_levels contract). Widened arrays existed
        transiently during decode; this bounds the at-rest footprint."""
        if not self.compact_levels or delivered is None:
            return delivered
        from ..ops.packed_levels import PackedLevels

        col = self.schema.column(path)
        dl, rl = delivered.def_levels, delivered.rep_levels
        if dl is not None and not isinstance(dl, PackedLevels):
            delivered.def_levels = PackedLevels.from_array(dl, col.max_def)
        if rl is not None and not isinstance(rl, PackedLevels):
            delivered.rep_levels = PackedLevels.from_array(rl, col.max_rep)
        return delivered

    def read_row_group(self, i: int, columns=None) -> dict[tuple, ChunkData]:
        """Decode one row group into {leaf path: ChunkData}.

        Host-bound delivery always decodes on the host, even on the TPU
        backend: round-tripping every value through the device for a host
        destination is a measured net loss (fetching decoded columns back
        over the transfer link costs more than decoding them locally). The
        device path pays off when values *stay* in HBM — that's
        read_row_group_device. backend="tpu_roundtrip" forces the device
        decode + fetch anyway: it is the byte-identical parity oracle used
        by tests/test_tpu_backend.py.

        On the roundtrip backend all selected chunks are *planned* first
        (host prescan + async device dispatch), then finalized — every
        chunk's device work is in flight before the first fetch blocks."""
        return self._read_row_group(i, columns, pack=True)

    def _read_row_group(
        self, i: int, columns, pack: bool, dict_paths=frozenset()
    ) -> dict[tuple, ChunkData]:
        """pack=False is the internal iteration path: rows/batches consume
        the levels immediately, so bit-packing them (compact_levels) would be
        a pure pack+widen round trip with no at-rest benefit. `dict_paths`
        keeps those columns' dictionary indices unmaterialized when their
        chunk allows it (to_arrow read_dictionary=; both backends — the
        roundtrip path passes its decoded indices through finalize).

        Under on_error != 'raise' a corrupt chunk is quarantined instead of
        aborting: 'null' substitutes an all-null chunk (optional columns
        only), otherwise the WHOLE row group is dropped — columns of a group
        must stay row-aligned, so a single undeliverable chunk poisons the
        group. A dropped group returns {}."""
        with span("row_group", {"group": i}):
            return self._read_row_group_impl(i, columns, pack, dict_paths)

    def _read_row_group_impl(
        self, i: int, columns, pack: bool, dict_paths=frozenset()
    ) -> dict[tuple, ChunkData]:
        try:
            if self.backend == "tpu_roundtrip":
                try:
                    plans = self._plan_row_group(i, columns)
                    out = {
                        path: plan.finalize(keep_dict_indices=path in dict_paths)
                        for path, plan in plans.items()
                    }
                except PARQUET_ERRORS as e:
                    # chunks plan/finalize as a batch here, so isolation is
                    # group-granular on this backend
                    if self.on_error == "raise":
                        raise
                    bump("chunks_quarantined")
                    _log_event(
                        "chunk_quarantined", level="warning",
                        source=self._source.source_id, group=i,
                        error=f"{type(e).__name__}: {e}",
                    )
                    raise _GroupQuarantined() from e
            else:
                out = {}
                selected = list(self._selected_chunks(i, columns))
                # batched range fetch (coalesced, cache-aware); None falls
                # back to streaming page-by-page through the shared cursor
                windows = self._chunk_windows(selected)
                for path, cc, column in selected:
                    f = windows[path] if windows is not None else self._f
                    try:
                        out[path] = read_chunk(
                            f,
                            cc,
                            column,
                            validate_crc=self.validate_crc,
                            alloc=self.alloc,
                            keep_dict_indices=path in dict_paths,
                        )
                    except PARQUET_ERRORS as e:
                        if self.on_error == "raise":
                            raise
                        bump("chunks_quarantined")
                        _log_event(
                            "chunk_quarantined", level="warning",
                            source=self._source.source_id, group=i,
                            column=".".join(path),
                            error=f"{type(e).__name__}: {e}",
                        )
                        if self.on_error == "null":
                            nc = self._null_chunk(i, column)
                            if nc is not None:
                                bump("chunks_nulled")
                                out[path] = nc
                                continue
                        raise _GroupQuarantined() from e
        except _GroupQuarantined:
            bump("row_groups_quarantined")
            _log_event(
                "row_group_quarantined", level="warning",
                source=self._source.source_id, group=i,
            )
            return {}
        if pack and self.compact_levels:
            for path, cd in out.items():
                self._pack_chunk_levels(path, cd)
        return out

    def _null_chunk(self, i: int, column) -> "ChunkData | None":
        """An all-null stand-in for a quarantined chunk (on_error='null'):
        one level entry per row at definition 0. Only possible when the
        column is optional somewhere along its path (max_def > 0) — a
        REQUIRED column has no null representation, so the caller degrades
        to quarantining the group."""
        if column.max_def <= 0:
            return None
        rows = self.row_group(i).num_rows or 0
        from ..meta.parquet_types import Type
        from .arrays import ByteArrayData
        from .chunk import _empty_dtype

        if column.type == Type.BYTE_ARRAY:
            values = ByteArrayData(offsets=np.zeros(1, dtype=np.int64), data=b"")
        elif column.type == Type.FIXED_LEN_BYTE_ARRAY:
            # fixed-width values decode as (n, width) uint8 rows; a 1-D empty
            # here would type the Arrow chunk uint8 and crash concatenation
            # against clean groups' fixed_size_binary chunks
            values = np.empty((0, column.type_length or 0), dtype=np.uint8)
        elif column.type == Type.INT96:
            values = np.empty((0, 12), dtype=np.uint8)
        else:
            values = np.empty(0, dtype=_empty_dtype(column))
        return ChunkData(
            column=column,
            num_values=rows,
            values=values,
            def_levels=np.zeros(rows, dtype=np.uint16),
            rep_levels=(
                np.zeros(rows, dtype=np.uint16) if column.max_rep > 0 else None
            ),
        )

    def _effective_device(self, device=None):
        """Precedence rule, in one place: per-call override > reader default
        > process default (None)."""
        return device if device is not None else self.device

    def _devctx(self, device=None):
        """Context manager that pins caller-thread jax work to the effective
        device."""
        dev = self._effective_device(device)
        if dev is None:
            return nullcontext()
        import jax

        return jax.default_device(dev)

    def read_row_group_device(
        self, i: int, columns=None, device=None, *, filters=None, doubles=None
    ):
        """Decode one row group straight into device memory (HBM).

        The TPU-native delivery point: returns {leaf path: DeviceColumn} whose
        value arrays are jax arrays resident on the accelerator — encoded
        bytes go up, decoded columns never come back down. Works regardless
        of the reader's configured backend. `device` pins this call's arrays
        to one jax.Device (overriding the reader-level `device=`); unlike a
        caller-side jax.default_device context it also reaches the internal
        dispatch thread.

        `doubles` picks the delivered form of DOUBLE columns (here and in
        read_row_groups_device / iter_device_batches):
          None (default)  float64 where the platform holds it bit-exactly,
                          else DeviceDoubleError (any TPU: f64 is emulated)
          "bits"          DeviceColumn.values is uint64, the IEEE-754 bit
                          pattern of every non-null value: exact everywhere
          "float32"       DeviceColumn.values is float32, each value the
                          round-to-nearest-even narrowing of the file's
                          float64 — bit for bit numpy's astype(float32)
        DeviceColumn.double_form names the form; no float64 value enters a
        device program under either (kernels/pipeline.py DOUBLE_FORMS).

        `filters` (same spec as iter_rows) additionally evaluates the
        predicate over the DELIVERED columns and returns ({leaf path:
        DeviceColumn}, mask) — the mask a device bool[num_rows] row array
        computed IN HBM (core/filter_device; the host vec engine takes over,
        typed and counted, for any shape the device engine declines). Any
        filter column missing from `columns` is read and delivered too (the
        mask needs it resident). The columns are NOT compacted: feed the
        mask to kernels.device_ops.mask_take_device for the gather, or carry
        it into masked reductions unsliced — that is the
        predicate -> mask -> gather pipeline with one jit cache entry per
        (schema, pad-bucket)."""
        if filters is None:
            return self._read_row_group_device(
                i, columns, pack=True, device=device, doubles=doubles
            )
        from .filter import normalize_dnf

        normalized = normalize_dnf(self.schema, filters)
        read_columns = self._columns_with_filters(columns, normalized)
        cols = self._read_row_group_device(
            i, read_columns, pack=True, device=device, doubles=doubles
        )
        n = int(self.row_group(i).num_rows or 0)
        with self._devctx(device):
            mask = self._device_group_mask(i, cols, normalized, n)
        return cols, mask

    def _columns_with_filters(self, columns, normalized):
        """The read set a row-filtered device read needs: the caller's
        projection plus any filter-referenced leaf it misses (None = all
        columns, which already covers every filter leaf)."""
        if columns is None:
            return None
        proj = self._resolve_columns(columns)
        if proj is None:
            return None
        fpaths = {e[0] for conj in normalized for e in conj}
        return sorted(proj) + sorted(p for p in fpaths if p not in proj)

    def _device_group_mask(self, i, group, normalized, n, *, null_mode="row"):
        """bool[n] DEVICE row mask for group i's delivered columns — the
        engine ladder: device kernels (filter_device.device_dnf_mask) first;
        any typed decline counts device_filter_declined and re-derives the
        mask with the host vec engine (exact for everything the zoo holds;
        a shape even IT declines raises its typed error)."""
        import jax.numpy as jnp

        from ..utils.trace import bump as trace_bump
        from .filter_device import DeviceFilterError, device_dnf_mask

        with stage("query.mask", args={"group": i, "terms": len(normalized)}):
            try:
                mask = device_dnf_mask(group, normalized, n, null_mode=null_mode)
            except DeviceFilterError:
                trace_bump("device_filter_declined")
                return jnp.asarray(
                    self._host_row_mask(i, normalized, n, null_mode)
                )
            trace_bump("device_filter_engaged")
            return mask

    def _host_row_mask(self, i, normalized, n, null_mode="row"):
        """Host-engine fallback mask: decode the filter columns on host and
        run the vec mask pipeline (np bool[n])."""
        from .filter_vec import dnf_mask

        cols = sorted({e[0] for conj in normalized for e in conj})
        chunks = self._read_row_group(i, cols, pack=False)
        if not chunks:
            # quarantined under an on_error policy: no rows to admit
            return np.zeros(n, dtype=bool)
        return dnf_mask(chunks, normalized, n, null_mode=null_mode)

    def _device_filter_rows(self, i, group, normalized, arrs, n):
        """Row-level compaction for one staged group (iter_device_batches
        filter_rows=True): DNF -> resident mask (_device_group_mask, with
        its typed + counted host fallback) -> ONE mask_take_device index
        shared by every delivered leaf — each pytree leaf compacts with a
        single padded gather, so the jit cache stays bounded by the
        (schema, pad-bucket) pair. Returns (filtered arrs, kept rows)."""
        import jax
        import jax.numpy as jnp

        from ..kernels.device_ops import mask_take_device
        from ..kernels.pipeline import _bucket

        mask = self._device_group_mask(i, group, normalized, n)
        with span("query.take", {"group": i, "rows": n}):
            sel, cnt = mask_take_device(
                jnp.arange(n, dtype=jnp.int32), mask, _bucket(n)
            )
            kept = int(cnt)
            if kept == n:
                return arrs, n
            if kept == 0:
                return arrs, 0
            arrs = jax.tree_util.tree_map(lambda a: a[sel][:kept], arrs)
            return arrs, kept

    def _read_row_group_device(
        self, i: int, columns, pack: bool, device=None, doubles=None
    ):
        """pack=False mirrors _read_row_group: the batch iterator consumes
        levels immediately (mask build), so packing them would be overhead."""
        with span("row_group.device", {"group": i}):
            plans = self._plan_row_group(i, columns, device=device, doubles=doubles)
            with self._devctx(device):
                return {
                    path: self._deliver(i, path, plan, pack)
                    for path, plan in plans.items()
                }

    def _deliver(self, i: int, path, plan, pack: bool = True):
        """The consumer thread's share of one chunk, as the 'deliver' stage:
        the plan's last device launches (dictionary gather, concatenation)
        and the level packing. Waiting for the plan's future stays outside:
        the plan.wait_dispatch stage (_wait)."""
        with stage("deliver", args=_chunk_args(i, path)):
            dc = plan.device_column()
            return self._pack_chunk_levels(path, dc) if pack else dc

    def read_row_groups_device(
        self, row_groups=None, columns=None, device=None, *, doubles=None
    ):
        """Decode row groups into device memory with full pipelining.

        Unlike per-group read_row_group_device calls — which resolve each
        group's dispatch futures before the next group's host prepare starts
        — this plans EVERY chunk of every requested group first (prepare on
        worker threads / dispatch on the dispatch thread, all overlapped) and
        only then materializes results. Returns [{leaf path: DeviceColumn}]
        in row-group order. `doubles`: see read_row_group_device."""
        indices = list(
            range(self.num_row_groups) if row_groups is None else row_groups
        )
        if self.alloc is not None:
            # A memory ceiling is per-row-group (released between groups on
            # the host path); cross-group pipelining would account all
            # groups' decoded buffers at once and spuriously trip it, so
            # ceiling-capped readers stage one group at a time.
            return [
                self.read_row_group_device(i, columns, device=device, doubles=doubles)
                for i in indices
            ]
        staged = self._plan_row_groups_async(
            indices, columns, device=device, doubles=doubles
        )
        out = []
        for i, group in zip(indices, staged):
            with self._devctx(device):
                out.append(
                    {
                        path: self._deliver(
                            i, path, _wait("plan.wait_dispatch", fut, i, path)
                        )
                        for path, fut in group
                    }
                )
        return out

    def _plan_row_group_async(
        self, i: int, columns=None, device=None, doubles=None, list_lengths=False
    ):
        """Stage one row group: prepare (pool or inline) + enqueue dispatch.
        Returns [(path, future-of-dispatched-plan)] without resolving."""
        return self._plan_row_groups_async(
            [i], columns, device=device, doubles=doubles, list_lengths=list_lengths
        )[0]

    def iter_device_batches(
        self,
        batch_size: int,
        columns=None,
        drop_remainder: bool = True,
        sharding=None,
        nullable: str = "error",
        filters=None,
        filter_rows: bool = False,
        lists: str = "error",
        max_list_len: int | None = None,
        device=None,
        doubles=None,
        seq_len: int | None = None,
    ):
        """Stream the file as fixed-size device-resident batches.

        The TPU-native consumption pattern: each yielded batch is
        {leaf path: jax.Array} with exactly `batch_size` rows (static shape —
        a jitted train step compiles once), values already decoded in HBM.
        Dictionary-encoded byte-array columns yield their int32 indices
        (embedding-lookup style). Unsupported shapes raise: raw byte-array
        columns (no device form), repeated/LIST columns (leaf slots are not
        rows) — project them out with `columns=` or transform upstream.

        `nullable` picks the policy for columns with nulls:
          "error" (default)  raise — non-null cells would silently shift rows
          "mask"             yield MaskedColumn(values, mask): values are
                             row-aligned with nulls zero-filled ON DEVICE and
                             mask is a bool row validity array — the
                             TPU-native null representation (a jit step takes
                             the pair as a pytree: jnp.where(m, v, ...)).

        While the consumer runs on group i's batches, group i+1 is already
        preparing and dispatching (one-group lookahead); memory stays
        bounded by two row groups plus the carry. With drop_remainder=False
        the final short batch is yielded as-is (dynamic shape: callers pad
        or accept a recompile).

        `sharding` (a jax.sharding.Sharding, e.g. NamedSharding(mesh,
        P("data"))) lays every batch out across a device mesh — the
        data-parallel input pipeline: decode once, shard over ICI. The
        batch size must divide evenly over the sharded axis.

        `lists` picks the policy for single-level LIST columns:
          "error" (default)  raise — leaf slots are not rows
          "pad"              yield RaggedColumn(values, lengths): values
                             row-padded ON DEVICE to a static
                             [rows, max_list_len] matrix (zero-filled past
                             each row's length), lengths the per-row element
                             count — the TPU-native ragged representation
                             for sequence data. Requires max_list_len; a row
                             exceeding it raises. Null and empty lists both
                             have length 0.
          "pack"             yield PackedBatch(tokens, segment_ids,
                             positions), each int32[batch_size, seq_len] and
                             resident on the device: the ONE selected leaf —
                             a single-level LIST of INT32 or INT64 elements,
                             one document a row — as a token stream cut every
                             seq_len elements (core/packing.py). Documents
                             are concatenated in row order with nothing
                             between them (a null or empty document adds
                             nothing); a document cut by a sequence's end
                             continues in the next sequence, across row
                             groups too; only the file's last sequence is
                             padded. segment_ids counts the pieces of a
                             sequence from 1 (a piece starts at slot 0 and at
                             every document's first token), positions
                             restarts at 0 in every piece; padding reads 0 in
                             all three. batch_size counts sequences: with
                             drop_remainder=False the file's last batch has
                             fewer. INT64 elements are delivered as their low
                             32 bits. Requires seq_len; takes `device` and
                             `sharding`, none of nullable=, filters=,
                             max_list_len=, doubles=. No compiled shape
                             follows a row group's element or document count.

        `filters` pushes a predicate (a (column, op, value) conjunction, or
        a list of lists — the OR-of-ANDs DNF convention) down to ROW-GROUP
        granularity: groups whose statistics/bloom filters exclude the
        predicate are never prepared, uploaded, or decoded. Surviving groups
        stream whole (batches keep their static shape; rows are NOT
        individually filtered — filter columns may admit non-matching rows,
        exact per-row masking is the consumer's jnp.where).

        `filter_rows=True` (requires `filters`) extends the push-down to ROW
        granularity IN HBM: each surviving group's predicate evaluates as a
        device mask over the resident columns (core/filter_device) and one
        mask_take_device compaction gathers only matching rows into the
        batch stream — predicate -> mask -> gather, never round-tripping the
        host. Batches keep their static shape (matching rows pack densely
        across group boundaries); a predicate shape the device engine
        cannot run falls back, typed and counted
        (device_filter_engaged/declined), to the host vec engine's mask
        with the same compaction. Filter columns missing from `columns=`
        are read for the mask but not delivered in batches.

        `device` pins every batch's arrays to one jax.Device (overriding the
        reader-level `device=`); unlike a caller-side jax.default_device
        context it also reaches the internal dispatch thread. Mutually
        useful with `sharding`: decode lands on `device`, device_put lays
        each batch out over the mesh.

        `doubles` picks the form of DOUBLE columns ("bits" -> uint64 arrays,
        "float32" -> float32 arrays; None: float64 where the platform holds
        it, else DeviceDoubleError): see read_row_group_device.
        """
        if batch_size <= 0:
            raise ValueError("batch_size must be positive")
        if nullable not in ("error", "mask"):
            raise ValueError('nullable must be "error" or "mask"')
        if lists not in ("error", "pad", "pack"):
            raise ValueError('lists must be "error", "pad" or "pack"')
        if (seq_len is not None) != (lists == "pack"):
            raise ValueError('seq_len goes with lists="pack", and only with it')
        if lists == "pack":
            return self._iter_packed_batches(
                batch_size, self._packed_leaf(
                    columns, seq_len, nullable=nullable != "error", filters=filters,
                    filter_rows=filter_rows, max_list_len=max_list_len, doubles=doubles,
                ),
                seq_len, drop_remainder, sharding, device,
            )
        if lists == "pad":
            if max_list_len is None or max_list_len <= 0:
                raise ValueError('lists="pad" requires a positive max_list_len')
            # eager, like every other argument: nested lists fail at the
            # call, not at the first next() deep in a train loop
            sel = self._resolve_columns(columns) if columns else self._selected
            for leaf in self.schema.leaves:
                if (sel is None or leaf.path in sel) and leaf.max_rep > 1:
                    raise ParquetFileError(
                        f"parquet: column {leaf.path_str} has {leaf.max_rep} "
                        "repetition levels; ragged batching covers "
                        "single-level LIST columns only"
                    )
        normalized = None
        if filters is not None:
            # eager validation, like batch_size/nullable: a bad column or op
            # should fail HERE, not at the first next() deep in a train loop
            from .filter import normalize_dnf

            normalized = normalize_dnf(self.schema, filters)
        if filter_rows and normalized is None:
            raise ValueError("filter_rows=True requires filters")
        if doubles is not None:
            from ..kernels.pipeline import check_doubles_form

            check_doubles_form(doubles)
        return self._iter_device_batches(
            batch_size, columns, drop_remainder, sharding, nullable,
            normalized, lists, max_list_len, device, filter_rows, doubles,
        )

    def _iter_device_batches(
        self, batch_size: int, columns, drop_remainder: bool, sharding=None,
        nullable: str = "error", normalized=None, lists: str = "error",
        max_list_len=None, device=None, filter_rows: bool = False, doubles=None,
    ):
        import jax
        import jax.numpy as jnp

        def _ragged(path, dc, arr):
            from ..meta.parquet_types import FieldRepetitionType

            leaf = self.schema.column(path)
            if leaf.max_rep != 1:
                raise ParquetFileError(
                    f"parquet: column {'.'.join(path)} has {leaf.max_rep} "
                    "repetition levels; ragged batching covers single-level "
                    "LIST columns only"
                )
            from ..ops.levels import LevelError, list_lengths

            try:
                lengths, elements = list_lengths(
                    dc.rep_levels, dc.def_levels, leaf.max_def,
                    leaf.repetition == FieldRepetitionType.OPTIONAL,
                )
            except LevelError as e:
                # a null ELEMENT (optional leaf, def one below max) would
                # silently left-shift its row's survivors — corruption for
                # position-sensitive sequences, so refuse
                raise ParquetFileError(
                    f"parquet: column {'.'.join(path)} has {e}; ragged "
                    "batching would shift positions (fill nulls upstream)"
                ) from e
            if arr.shape[0] != elements:
                raise ParquetFileError(
                    f"parquet: column {'.'.join(path)} level/value mismatch"
                )
            if len(lengths) and int(lengths.max()) > max_list_len:
                raise ParquetFileError(
                    f"parquet: column {'.'.join(path)} has a row with "
                    f"{int(lengths.max())} elements > max_list_len="
                    f"{max_list_len} (raise it, or filter upstream)"
                )
            return _pad_ragged_device(
                arr, jnp.asarray(lengths), int(max_list_len)
            )

        def _array_of(path, dc):
            arr = dc.values if dc.values is not None else dc.indices
            if arr is None:
                raise ParquetFileError(
                    f"parquet: column {'.'.join(path)} has no device array form "
                    "(raw byte-array columns cannot batch; project them out)"
                )
            if dc.rep_levels is not None:
                if lists == "pad":
                    return _ragged(path, dc, arr)
                raise ParquetFileError(
                    f"parquet: column {'.'.join(path)} is repeated; its leaf "
                    "slots are not rows, so it cannot batch (project it "
                    'out, or pass lists="pad" with max_list_len)'
                )
            has_nulls = arr.shape[0] != dc.num_values
            if nullable == "mask" and dc.def_levels is not None:
                max_def = self.schema.column(path).max_def
                if max_def > 0:
                    mask = jnp.asarray(dc.def_levels == max_def)
                    if has_nulls:
                        return _expand_nullable_device(arr, mask)
                    # no nulls in THIS group, but the column is declared
                    # optional: keep the pytree structure stable across
                    # groups/batches
                    return MaskedColumn(values=arr, mask=mask)
            if has_nulls:
                raise ParquetFileError(
                    f"parquet: column {'.'.join(path)} contains nulls; "
                    "device batches need null-free columns (filter or fill "
                    'upstream, project the column out, or pass nullable="mask")'
                )
            return arr

        if normalized is not None:
            # group-level pushdown: excluded groups never touch the device
            groups = self._prune_groups_normalized(normalized)
        else:
            groups = list(range(self.num_row_groups))
        # row-level pushdown reads filter-referenced leaves too (the mask
        # needs them resident), but only the caller's projection batches
        proj = None
        read_columns = columns
        if filter_rows:
            proj = self._resolve_columns(columns) if columns else self._selected
            read_columns = self._columns_with_filters(
                columns if columns else (sorted(proj) if proj else None),
                normalized,
            )
        # a memory ceiling forbids the lookahead's two-groups residency
        lookahead = self.alloc is None

        def stage(i):
            if lookahead:
                return self._plan_row_group_async(
                    i, read_columns, device=device, doubles=doubles
                )
            return None

        staged_next = stage(groups[0]) if groups and lookahead else None
        carry: dict = {}
        carry_n = 0
        for gi, i in enumerate(groups):
            # device work scoped so the pin never leaks across a yield into
            # the consumer's frame (jax.default_device is thread-local and
            # the consumer runs on this thread between batches)
            with self._devctx(device):
                if lookahead:
                    staged = staged_next
                    staged_next = (
                        stage(groups[gi + 1]) if gi + 1 < len(groups) else None
                    )
                    # no level packing here: _array_of consumes the levels
                    # (mask build) within this iteration, so they never rest
                    group = {
                        path: self._deliver(
                            i, path, _wait("plan.wait_dispatch", fut, i, path),
                            pack=False,
                        )
                        for path, fut in staged
                    }
                else:
                    group = self._read_row_group_device(
                        i, read_columns, pack=False, device=device, doubles=doubles
                    )
                arrs = {
                    path: _array_of(path, dc)
                    for path, dc in group.items()
                    if proj is None or path in proj
                }
                if not arrs:
                    continue
                lengths = {a.shape[0] for a in jax.tree_util.tree_leaves(arrs)}
                if len(lengths) != 1:
                    raise ParquetFileError(
                        f"parquet: columns disagree on row count in group {i}: "
                        f"{sorted(lengths)}"
                    )
                n = lengths.pop()
                if filter_rows:
                    arrs, n = self._device_filter_rows(i, group, normalized, arrs, n)
                    if not n:
                        continue
                if carry_n:
                    cat = jax.tree_util.tree_map(
                        lambda c, a: jnp.concatenate([c, a]), carry, arrs
                    )
                else:
                    cat = arrs
            total = carry_n + n
            # cursor slicing: each batch is one static-shape slice; the tail
            # is sliced once per row group, not once per batch
            off = 0
            while total - off >= batch_size:
                lo = off
                with self._devctx(device):
                    batch = jax.tree_util.tree_map(
                        lambda a, lo=lo: a[lo : lo + batch_size], cat
                    )
                    if sharding is not None:
                        batch = jax.device_put(batch, sharding)
                yield batch
                off += batch_size
            carry_n = total - off
            with self._devctx(device):
                carry = (
                    jax.tree_util.tree_map(lambda a: a[off:], cat) if carry_n else {}
                )
        if carry_n and not drop_remainder:
            if sharding is not None:
                try:
                    carry = jax.device_put(carry, sharding)
                except ValueError:
                    # tail not divisible over the mesh axis: deliver it
                    # unsharded rather than dying on the last batch (callers
                    # already handle the tail's dynamic shape)
                    pass
            yield carry

    def _packed_leaf(self, columns, seq_len, **unused):
        """The one leaf lists="pack" batches, or the refusal — eager, like
        every other argument of iter_device_batches."""
        from ..meta.parquet_types import Type

        given = sorted(k for k, v in unused.items() if v)
        if given:
            raise ValueError(f'lists="pack" takes no {", ".join(given)}')
        if seq_len <= 0:
            raise ValueError('lists="pack" requires a positive seq_len')
        sel = self._resolve_columns(columns) if columns else self._selected
        leaves = [lf for lf in self.schema.leaves if sel is None or lf.path in sel]
        if len(leaves) != 1:
            raise ValueError(
                f'lists="pack" batches ONE leaf; {len(leaves)} are selected '
                "(name the token column with columns=)"
            )
        leaf = leaves[0]
        if leaf.max_rep != 1:
            raise ParquetFileError(
                f"parquet: column {leaf.path_str} has {leaf.max_rep} "
                "repetition levels; sequence packing covers single-level "
                "LIST columns only"
            )
        if leaf.type not in (Type.INT32, Type.INT64):
            raise ParquetFileError(
                f"parquet: column {leaf.path_str} holds {Type(leaf.type).name} "
                "elements; sequence packing covers INT32 and INT64 token ids"
            )
        return leaf

    def _iter_packed_batches(
        self, batch_size: int, leaf, seq_len: int, drop_remainder: bool,
        sharding=None, device=None,
    ):
        """lists="pack": the row groups' decoded ids, delivered at their
        padded lengths, go through one SequencePacker (core/packing.py),
        _PACKED_LOOKAHEAD groups staged ahead (none under a memory ceiling,
        as in _iter_device_batches). The packer's launches are the
        deliver.pack stage, inside deliver."""
        import jax

        from .packing import PackedBatch, SequencePacker

        columns = [leaf.path]
        # a memory ceiling forbids the lookahead (see _iter_device_batches)
        depth = _PACKED_LOOKAHEAD if self.alloc is None else 0

        def plan_of(i, staged):
            if staged is not None:
                return _wait("plan.wait_dispatch", staged[0][1], i, leaf.path)
            return self._plan_row_group(
                i, columns, device=device, list_lengths=True
            )[leaf.path]

        def stage_group(i):
            return self._plan_row_group_async(
                i, columns, device=device, list_lengths=True
            )

        def placed(batch):
            return batch if sharding is None else jax.device_put(batch, sharding)

        packer = SequencePacker(batch_size, seq_len)
        groups = range(self.num_row_groups)
        ahead = deque(map(stage_group, groups[:depth]))
        try:
            for i in groups:
                # device work scoped so the pin never leaks across a yield
                with self._devctx(device):
                    staged = ahead.popleft() if ahead else None
                    if depth and i + depth < len(groups):
                        ahead.append(stage_group(i + depth))
                    plan = plan_of(i, staged)
                    with stage("deliver", args=_chunk_args(i, leaf.path)):
                        values, count = plan.device_values_padded()
                        with stage("deliver.pack"):
                            packer.append(values, plan.dev_lengths, plan.list_lengths, count)
                while packer.ready():
                    with self._devctx(device), stage("deliver"), stage("deliver.pack"):
                        batch = placed(packer.emit())
                    yield batch
        finally:
            # a consumer that stops early, or a group that raised, leaves
            # staged groups in flight: they finish (their errors read and
            # dropped) before the reader's source may close under them
            for futs in ahead:
                for _, fut in futs:
                    fut.exception()
        sequences = packer.sequences_left()
        if not sequences or (drop_remainder and sequences < batch_size):
            return
        with self._devctx(device), stage("deliver"), stage("deliver.pack"):
            batch = packer.tail()
            if sequences == batch_size:
                batch = placed(batch)
            else:
                # the short last batch is cut on the host: a device slice is
                # a compiled program a remainder, and the remainder is data.
                # Once a file, at most batch_size * seq_len * 12 bytes
                batch = PackedBatch(*(
                    jax.device_put(np.asarray(a)[:sequences], a.sharding) for a in batch
                ))
                if sharding is not None:
                    try:
                        batch = jax.device_put(batch, sharding)
                    except ValueError:
                        pass  # not divisible over the mesh axis: as _iter_device_batches
        yield batch

    def _plan_row_groups_async(
        self, indices, columns=None, device=None, doubles=None, list_lengths=False
    ):
        """Stage chunks of several row groups at once. `list_lengths`
        prepares the padded delivery of a LIST leaf (lists="pack":
        kernels/pipeline.py prepare_chunk_plan).

        Every chunk's prepare is submitted to the worker pool up front (no
        per-group barrier — the pool never drains between groups); device
        dispatch is enqueued per chunk in deterministic (group, column) order
        as its prepare resolves. A stage of ONE chunk is one pool task,
        prepare then dispatch, and returns without waiting on either
        (counter pooled_single_chunk_stages). Returns [[(path,
        future-of-dispatched-plan)]] per group, unresolved."""
        from ..kernels.pipeline import check_doubles_form, prepare_chunk_plan
        from ..utils.native import get_native
        from .chunk import ChunkWindow, chunk_byte_range

        check_doubles_form(doubles)
        groups = [(i, list(self._selected_chunks(i, columns))) for i in indices]
        # with a block cache attached the planner bills its own io.read
        # (misses only); the direct source read is billed here
        direct_read = self._block_cache is None

        def prep(i, path, cc, column):
            with span("chunk.prepare", _chunk_args(i, path)):
                offset, total = chunk_byte_range(cc)
                with stage("io.read", total) if direct_read else nullcontext():
                    raw = self._fetch_chunk(offset, total)
                return prepare_chunk_plan(
                    ChunkWindow(raw, offset),
                    cc,
                    column,
                    validate_crc=self.validate_crc,
                    alloc=self.alloc,
                    doubles=doubles,
                    list_lengths=list_lengths,
                )

        dev = self._effective_device(device)
        dispatcher = _dispatch_pool()
        pool = _host_pool()

        def dispatch(i, path, plan):
            return instrumented_submit(
                dispatcher,
                _dispatch_traced,
                plan.dispatch_device,
                dev,
                _chunk_args(i, path),
            )

        # Both pool hops use instrumented_submit: an active decode_trace is
        # a contextvar, which ThreadPoolExecutor does NOT carry into workers
        # by itself — without the explicit copy_context() carry a traced
        # device read would lose every prepare/dispatch stage to the void
        # (and two concurrent traced readers sharing the pools would have no
        # way to attribute worker time to the right trace). It also records
        # how long each task waited for its pool (pool.wait): for the single
        # pqt-dispatch thread, the wait of a prepared chunk behind the queue.
        if pool is None:
            # Single-core host: prepare serially; device dispatch (transfer
            # RPCs, which release the GIL) still overlaps the next prepare.
            return [
                [
                    (path, dispatch(i, path, prep(i, path, cc, column)))
                    for path, cc, column in chunks
                ]
                for i, chunks in groups
            ]
        get_native()  # thread-safe lazy init before fan-out
        if sum(len(chunks) for _, chunks in groups) == 1:
            # One chunk (a one-column group: lists="pack", a single-column
            # batch stream): ONE pool task prepares it and enqueues its
            # dispatch, so the caller's thread stages without preparing and
            # a prepare error waits in the future for the group's own
            # delivery. The task may wait on the dispatch thread, which
            # never waits on the pool.
            def prep_and_dispatch(i, path, cc, column):
                return dispatch(i, path, prep(i, path, cc, column)).result()

            bump("pooled_single_chunk_stages")
            return [
                [
                    (path, instrumented_submit(pool, prep_and_dispatch, i, path, cc, column))
                    for path, cc, column in chunks
                ]
                for i, chunks in groups
            ]
        prep_futs = [
            (
                i,
                [
                    (path, instrumented_submit(pool, prep, i, path, cc, column))
                    for path, cc, column in chunks
                ],
            )
            for i, chunks in groups
        ]
        return [
            [
                (path, dispatch(i, path, _wait("plan.wait_prepare", fut, i, path)))
                for path, fut in chunks
            ]
            for i, chunks in prep_futs
        ]

    def _plan_row_group(
        self, i: int, columns=None, device=None, doubles=None, list_lengths=False
    ):
        """Plan every selected chunk of a row group for device decode.

        The host-only prepare phase (one pread per chunk, page walk,
        decompress, level decode, prescan) fans out over worker threads —
        decompression and the native prescans release the GIL — while device
        dispatch runs on the dispatch thread, in deterministic column order,
        overlapped with the next chunk's prepare.
        """
        return {
            path: _wait("plan.wait_dispatch", fut, i, path)
            for path, fut in self._plan_row_group_async(
                i, columns, device=device, doubles=doubles, list_lengths=list_lengths
            )
        }

    def _pread(self, offset: int, size: int) -> bytes:
        """Positional read through the reader's ByteSource — os.pread on
        local files, so there is no shared cursor, no lock, and no position
        save/restore. Clamps at EOF (short return, like a plain handle):
        truncated files surface as the decode ladder's typed errors, not a
        raw source exception."""
        end = self._source.size()
        if offset >= end or offset < 0 or size <= 0:
            return b""
        return self._source.read_at(offset, min(size, end - offset))

    def _fetch_chunk(self, offset: int, size: int):
        """One chunk's page bytes, through the block cache when attached.
        Out-of-bounds or degenerate ranges (truncated/lying files) bypass
        the cache and return short via _pread so corruption keeps its typed
        decode error."""
        if size <= 0 or offset < 0 or offset + size > self._source.size():
            return self._pread(offset, size)
        if self._block_cache is None:
            return self._source.read_at(offset, size)
        return fetch_ranges(
            self._source,
            [(offset, size)],
            cache=self._block_cache,
            gap=0,
        )[(offset, size)]

    def _chunk_windows(self, selected) -> "dict | None":
        """Planner-driven batched fetch of the selected chunks' byte ranges:
        exact extents from the footer, neighbors coalesced (io.coalesce)
        into batched source reads (io.read), each chunk handed back as a
        preloaded ChunkWindow. Returns None when the planner path does not
        apply — memory-ceiling readers (preloading a whole group would
        charge every page at once) and chunks whose metadata ranges are
        unusable or out of bounds (the streaming walk raises the precise
        typed error there)."""
        if self.alloc is not None or not selected:
            return None
        from .chunk import ChunkWindow, chunk_byte_range

        ranges = {}
        end = self._source.size()
        for path, cc, _col in selected:
            try:
                off, total = chunk_byte_range(cc)
            except ChunkError:
                return None
            # total == 0 included: coalesce() drops empty ranges, so the
            # fetch would come back without the key — the streaming walk
            # instead raises the exact typed value-count error
            if off < 0 or total <= 0 or off + total > end:
                return None
            ranges[path] = (off, total)
        fetched = fetch_ranges(
            self._source,
            list(ranges.values()),
            cache=self._block_cache,
            gap=self._coalesce_gap,
        )
        return {
            path: ChunkWindow(fetched[r], r[0]) for path, r in ranges.items()
        }

    def _selected_chunks(self, i: int, columns=None):
        """Yield (path, ColumnChunk, Column) for the selected leaves of group i."""
        rg = self.row_group(i)
        selected = self._resolve_columns(columns) if columns else self._selected
        if self.alloc is not None:
            self.alloc.release()
        for cc in rg.columns or []:
            md = cc.meta_data
            if md is None:
                raise ParquetFileError("parquet: column chunk without metadata")
            path = tuple(md.path_in_schema or [])
            if selected is not None and path not in selected:
                continue  # skipChunk (reference: chunk_reader.go:271)
            yield path, cc, self.schema.column(path)

    # -- record iteration ------------------------------------------------------

    def prune_row_groups(self, filters) -> list[int]:
        """Row-group indices whose chunk statistics admit the filters —
        groups provably excluded by written min/max/null-count never load
        (statistics-driven pruning; the reference writes stats but never
        consumes them, README.md:47)."""
        return self.prune_row_groups_counted(filters)[0]

    def prune_row_groups_counted(self, filters) -> tuple:
        """`(admitted_indices, stats_pruned, bloom_pruned)` — the same
        pruning walk as prune_row_groups, attributing each excluded group
        to the rung that excluded it (statistics first, then bloom). The
        plan layer's pruning summary (`ScanPlan.pruning_summary()`) is fed
        from here so the semantics live in ONE place."""
        from .filter import normalize_dnf, row_group_may_match

        dnf = normalize_dnf(self.schema, filters)
        admitted: list[int] = []
        stats_pruned = bloom_pruned = 0
        for i in range(self.num_row_groups):
            # one walk per (group, conjunction): dnf_group_may_match's OR
            # semantics, unrolled so each stats evaluation happens once and
            # the excluding rung is known without a second pass
            rg = self.row_group(i)
            stats_ok = survives = False
            for conj in dnf:
                if not row_group_may_match(rg, conj):
                    continue
                stats_ok = True
                if self._bloom_excludes(i, conj):
                    continue
                survives = True
                break
            if survives:
                admitted.append(i)
            elif stats_ok:
                bloom_pruned += 1
            else:
                stats_pruned += 1
        return admitted, stats_pruned, bloom_pruned

    def _prune_groups_normalized(self, dnf) -> list[int]:
        from .filter import dnf_group_may_match

        return [
            i
            for i in range(self.num_row_groups)
            if dnf_group_may_match(self.row_group(i), dnf, self._bloom_excludes, i)
        ]

    def read_page_index(self, i: int, columns=None) -> dict:
        """The Parquet page index of row group i: {leaf path: (ColumnIndex,
        OffsetIndex)}; columns whose chunk carries no index map to
        (None, None). Beyond the reference (no page-index support there);
        parity oracle is pyarrow's write_page_index=True output."""
        from ..meta.parquet_types import ColumnIndex, OffsetIndex
        from ..meta.thrift import ThriftError

        out = {}
        for path, cc, _col in self._selected_chunks(i, columns):
            ci = oi = None
            try:
                # _fetch_chunk, not _pread: with a block cache attached the
                # index ranges persist across readers, so warm re-planning
                # (the serve daemon's repeat requests) reads zero bytes
                if cc.column_index_offset and cc.column_index_length:
                    ci = ColumnIndex.loads(
                        self._fetch_chunk(
                            cc.column_index_offset, cc.column_index_length
                        )
                    )
                if cc.offset_index_offset and cc.offset_index_length:
                    oi = OffsetIndex.loads(
                        self._fetch_chunk(
                            cc.offset_index_offset, cc.offset_index_length
                        )
                    )
            except ThriftError as e:
                raise ParquetFileError(
                    f"parquet: corrupt page index for {'.'.join(path)}: {e}"
                ) from e
            out[path] = (ci, oi)
        return out

    def read_bloom_filter(self, i: int, column):
        """The split-block bloom filter of one column chunk, or None when
        the chunk carries none. Beyond the reference; pyarrow's
        bloom_filter_options output is the cross-implementation oracle."""
        from .bloom import BloomFilter

        path = tuple(column.split(".")) if isinstance(column, str) else tuple(column)
        cache = getattr(self, "_bloom_cache", None)
        if cache is None:
            cache = self._bloom_cache = {}
        if (i, path) in cache:
            return cache[(i, path)]
        rg = self.row_group(i)
        for cc in rg.columns or []:
            md = cc.meta_data
            if md is None or tuple(md.path_in_schema or []) != path:
                continue
            off = md.bloom_filter_offset
            if not off or off <= 0:
                cache[(i, path)] = None
                return None
            length = md.bloom_filter_length
            if not length or length <= 0:
                # header precedes the bitset; peek enough for the header,
                # parse numBytes, then take exactly header+bitset
                # (cache-routed so warm re-pruning repeats it from memory)
                peek = self._fetch_chunk(off, 64)
                from ..meta.parquet_types import BloomFilterHeader
                from ..meta.thrift import CompactReader, ThriftError

                try:
                    r = CompactReader(peek)
                    h = BloomFilterHeader.read(r)
                except ThriftError as e:
                    raise ParquetFileError(
                        f"parquet: corrupt bloom header for {'.'.join(path)}: {e}"
                    ) from e
                length = r.pos + (h.numBytes or 0)
            try:
                bf = BloomFilter.from_buffer(self._fetch_chunk(off, length))
            except ValueError as e:
                raise ParquetFileError(
                    f"parquet: corrupt bloom filter for {'.'.join(path)}: {e}"
                ) from e
            cache[(i, path)] = bf
            return bf
        raise ParquetFileError(f"parquet: column {'.'.join(path)} not in row group")

    def _bloom_excludes(self, i: int, normalized) -> bool:
        """True when some equality predicate's value is PROVABLY absent from
        row group i per its bloom filter (false-positive-only structure:
        never excludes a group that contains the value)."""
        from .filter import chunks_by_path
        from .stats import column_is_unsigned

        by_path = chunks_by_path(self.row_group(i))
        for path, leaf, op, _rv, vlo, vhi in normalized:
            if op == "==":
                if vlo is None or vlo != vhi:
                    continue
                probes = [vlo]
            elif op == "in":
                # exclusion needs EVERY member provably absent, so every
                # bracket must be exact ([] is handled by stats pruning)
                if not vlo or any(a != b for a, b in vlo):
                    continue
                probes = [a for a, _ in vlo]
            else:
                continue
            cc = by_path.get(path)
            if cc is None or not cc.meta_data.bloom_filter_offset:
                continue
            try:
                bf = self.read_bloom_filter(i, path)
            except ParquetFileError:
                continue  # corrupt filter: never exclude on it
            if bf is not None and all(
                not bf.might_contain(leaf.type, p, column_is_unsigned(leaf))
                for p in probes
            ):
                return True
        return False

    def prune_pages(self, i: int, filters) -> list[tuple[int, int]]:
        """Row ranges of row group i that may contain rows matching
        `filters`, proven by the page index — sorted disjoint [(start,
        stop)); [(0, num_rows)] when the file has no page index or nothing
        can be pruned, [] when the whole group is provably empty of
        matches."""
        from .filter import dnf_page_ranges, normalize_dnf

        dnf = normalize_dnf(self.schema, filters)
        num_rows = self.row_group(i).num_rows or 0
        paths = [p for conj in dnf for p, *_ in conj]
        indexes = self.read_page_index(i, columns=paths) if paths else {}
        return dnf_page_ranges(dnf, indexes, num_rows)

    def iter_rows(self, row_groups=None, raw: bool = False, filters=None):
        """Yield rows as dicts (returns an iterator). `raw=True` gives
        reference-style nested maps (no LIST/MAP unwrapping, bytes not
        decoded). `filters` is a flat list of (column, op, value) triples (a
        conjunction) or a list of LISTS of triples (an OR of conjunctions —
        pyarrow's DNF convention): row groups whose statistics/bloom/
        page-index exclude the predicate are skipped wholesale and the
        surviving rows are predicate-checked exactly."""
        if filters is None and row_groups is None and self.num_row_groups == 1:
            # single-group scan: hand back the group's list/generator with
            # no extra per-row generator hop (~10% of assembled-rows time)
            rows = self._iter_group_rows(0, raw)
            return iter(rows) if isinstance(rows, list) else rows
        return self._iter_rows_gen(row_groups, raw, filters)

    def _iter_rows_gen(self, row_groups, raw: bool, filters):
        dnf = None
        if filters is not None:
            from .filter import (
                FilterError,
                dnf_group_may_match,
                dnf_page_ranges,
                dnf_row_matches,
                normalize_dnf,
            )

            if raw:
                # row_matches compares in the converted domain (datetime,
                # Decimal, str); raw rows are wire-shaped (ints, undecoded
                # bytes, nested wrappers), so the predicate would silently
                # mismatch — mirror floor.Reader, which only prunes for the
                # unmarshal path
                raise FilterError("filters cannot be combined with raw=True")
            dnf = normalize_dnf(self.schema, filters)
        # Filter columns OUTSIDE the projection still evaluate: decode them
        # alongside the selection, predicate-check, then strip them from the
        # yielded rows (silently returning zero rows because the predicate
        # column was projected out is a correctness trap). Stripping is
        # LEAF-granular: each missing leaf is deleted at the shallowest
        # path component no selected leaf shares, so g.c vanishes from a
        # row that keeps g.b, and a whole unselected root vanishes outright.
        read_cols = None
        strips: list = []  # (parent path parts, key to pop)
        if dnf is not None and self._selected is not None:
            fpaths = {p for conj in dnf for p, *_ in conj}
            missing = fpaths - self._selected
            if missing:
                read_cols = list(self._selected | fpaths)
                for path in missing:
                    cut = 1
                    while cut < len(path) and any(
                        sel[:cut] == path[:cut] for sel in self._selected
                    ):
                        cut += 1
                    strips.append((path[: cut - 1], path[cut - 1]))
        indices = range(self.num_row_groups) if row_groups is None else row_groups
        for i in indices:
            if dnf is None:
                # no predicate: delegate the whole group (C-level yield from
                # the assembled list — no per-row Python frame)
                yield from self._iter_group_rows(i, raw)
                continue
            if not dnf_group_may_match(
                self.row_group(i), dnf, self._bloom_excludes, i
            ):
                continue
            # page index (when written): restrict row materialization to the
            # ranges whose pages may match — row assembly is the dominant
            # cost of a filtered scan, so pruned ranges never build rows
            ranges = None
            indexes = None
            try:
                # one parse covers both uses: range computation here and
                # selective page decode in _read_group_ranges. Filter columns
                # outside the projection still prune, so their index is
                # fetched alongside the selected columns'.
                indexes = self.read_page_index(i, columns=read_cols)
                if any(ci is not None for ci, _ in indexes.values()):
                    num_rows = self.row_group(i).num_rows or 0
                    ranges = dnf_page_ranges(dnf, indexes, num_rows)
                    if ranges == [(0, num_rows)]:
                        # nothing pruned: keep the unpruned fast paths
                        # (direct list / plain windows, no extra slicing)
                        ranges = None
            except ParquetFileError:
                ranges = None  # corrupt index: scan everything, stay correct
                indexes = None
            if ranges is not None and not ranges:
                continue
            yield from self._filtered_group_rows(
                i, raw, dnf, ranges, indexes, read_cols, strips
            )

    def _filtered_group_rows(
        self, i: int, raw: bool, dnf, ranges, indexes, read_cols, strips
    ):
        """One row group's rows surviving the residual predicate.

        The vectorized path: the decoded chunks compile into ONE boolean
        row mask (core/filter_vec.dnf_mask — per-leaf masks over the
        columnar buffers, AND within conjunctions, OR across them) and only
        matching rows ever materialize, windowed over the mask's True-runs.
        Shapes or value domains the mask pipeline cannot prove raise the
        typed VecFilterError and this falls back to the scalar per-row
        `row_matches` walk — identical output, the engine-ladder contract
        of assembly_vec (PQT_VEC_FILTER=0 forces the scalar oracle)."""
        from .filter import dnf_row_matches
        from .filter_vec import (
            VecFilterError,
            dnf_mask,
            group_row_count,
            masked_flat_columns,
            vec_filter_enabled,
        )

        chunks, sliced = self._decode_group_chunks(i, ranges, indexes, read_cols)
        if not chunks:
            return  # quarantined group (on_error='skip'), or empty selection
        mask = None
        if vec_filter_enabled() and vec_enabled():
            try:
                with timed_stage("assembly.filter") as el:
                    mask = dnf_mask(chunks, dnf, group_row_count(chunks))
                _metrics.observe("filter_mask_seconds", el.seconds)
            except VecFilterError:
                mask = None
        if mask is not None:
            kept = int(mask.sum())
            if kept:
                # rows assemble from the PROJECTION only: filter-only leaf
                # chunks never build row values, so the strip pass the
                # scalar path needs does not exist here
                row_chunks = (
                    chunks
                    if self._selected is None
                    else {p: cd for p, cd in chunks.items() if p in self._selected}
                )
                # flat schemas gather ONLY the kept rows (value boxing and
                # logical conversion scale with matches, not group size)
                flat = None
                try:
                    with stage("assemble"):
                        flat = masked_flat_columns(row_chunks, raw, mask)
                except VecFilterError:
                    flat = None
                if flat is not None:
                    bump("assemble_vec")
                    _metrics.inc(
                        "query_rows_filtered_total",
                        len(mask) - kept,
                        engine="vec",
                    )
                    names, columns, k = flat
                    if names and k:
                        yield from self._column_rows(names, columns, k)
                    return
                rc = None
                with stage("assemble"):
                    with _gc_paused():
                        rc = assemble_row_columns(self.schema, row_chunks, raw)
                if rc is not None and rc[2] == len(mask):
                    bump("assemble_vec")
                    _metrics.inc(
                        "query_rows_filtered_total",
                        len(mask) - kept,
                        engine="vec",
                    )
                    names, columns, _n = rc
                    if names:
                        yield from self._masked_rows(names, columns, mask)
                    return
            else:
                # the mask alone proved the group empty of matches: no rows
                # assemble under either engine, the filtering was vec's
                _metrics.inc(
                    "query_rows_filtered_total", len(mask), engine="vec"
                )
                return
            # row assembly couldn't prove the shape: the scalar walk below
            # decides (and raises its precise error on real inconsistency) —
            # the metric is counted THERE, never here too (one engine, one
            # count)
            mask = None
        evaluated = kept = 0
        try:
            for row in self._rows_from_chunks(chunks, raw, ranges, sliced):
                evaluated += 1
                if not dnf_row_matches(row, dnf):
                    continue
                kept += 1
                for parents, key in strips:
                    d = row
                    for part in parents:
                        d = d.get(part) if isinstance(d, dict) else None
                        if d is None:
                            break
                    if isinstance(d, dict):
                        d.pop(key, None)
                yield row
        finally:
            _metrics.inc(
                "query_rows_filtered_total", evaluated - kept, engine="scalar"
            )

    def _decode_group_chunks(self, i: int, ranges, indexes, columns):
        """(chunks, sliced) for one row group: selective page decode when
        the page index proves `ranges` (sorted disjoint row windows) cover
        few enough rows, else the full decode. sliced=True means the chunks
        hold exactly the ranges' rows."""
        chunks = None
        sliced = False
        if ranges is not None:
            try:
                chunks = self._read_group_ranges(i, ranges, indexes, columns)
            except ValueError:
                # inconsistent index, or a page shape the range decoder
                # doesn't cover (ChunkError/PageError/...): full decode
                # below stays correct and raises the precise error if the
                # file is genuinely corrupt
                chunks = None
            sliced = chunks is not None
            if sliced:
                bump("selective_page_decode")
        if chunks is None:
            chunks = self._read_row_group(i, columns, pack=False)
        return chunks, sliced

    def _iter_group_rows(
        self, i: int, raw: bool, ranges=None, indexes=None, columns=None
    ):
        """One row group's rows: a LIST for small vectorized shapes (callers
        iterate without an extra generator frame per row), a window-batched
        generator for large ones (bounds the live tracked-object count so
        cyclic GC passes stay cheap), or the streaming Dremel fallback.
        `ranges` (sorted disjoint [(start, stop)), from the page index)
        limits which rows materialize; when every selected column is flat
        and indexed, only the pages covering the ranges are even READ and
        decoded (selective page decode). The Dremel fallback ignores ranges
        (the caller's exact predicate check keeps the result correct)."""
        chunks, sliced = self._decode_group_chunks(i, ranges, indexes, columns)
        if not chunks:
            return []  # quarantined group (on_error='skip'), or empty selection
        return self._rows_from_chunks(chunks, raw, ranges, sliced)

    def _rows_from_chunks(self, chunks: dict, raw: bool, ranges=None, sliced=False):
        rc = None
        if vec_enabled():
            # the vectorized engine: level prefix scans -> offsets/validity
            # columns (core/assembly_vec.py). None when the scans cannot
            # prove the shape — or always when PQT_VEC_ASSEMBLY=0.
            with stage("assemble"):
                with _gc_paused():
                    rc = assemble_row_columns(self.schema, chunks, raw)
            if rc is not None:
                bump("assemble_vec")
        if rc is None:
            # per-row Dremel fallback: streams one row at a time (constant
            # memory) and raises precise errors on inconsistent level data
            bump("assemble_cursor")
            return _timed_rows(
                RecordAssembler(self.schema, chunks, raw=raw, engine="scalar")
            )
        names, columns, n = rc
        if not names or n == 0:
            return []
        if ranges is not None and not sliced:
            # full decode happened: restrict materialization to the ranges
            return self._ranged_rows(names, columns, ranges)
        if n <= _ASSEMBLE_WINDOW:
            with timed_stage("assembly.rows") as el, _gc_paused():
                rows = _zip_dict_rows(names, columns)
            _metrics.inc("assembly_rows_total", n, engine="vec")
            _metrics.observe("assembly_seconds", el.seconds)
            return rows
        return self._ranged_rows(names, columns, [(0, n)])

    def _read_group_ranges(
        self, i: int, ranges, indexes=None, columns=None
    ) -> dict | None:
        """Selective page decode of row group i restricted to `ranges`, or
        None when it doesn't apply (no/partial offset index, repeated
        columns, or ranges covering most rows — whole-chunk decode wins
        then). All returned chunks hold exactly the ranges' rows, aligned.
        `indexes` reuses an already-parsed page index for this group."""
        from .chunk import read_chunk_row_ranges

        rg = self.row_group(i)
        num_rows = rg.num_rows or 0
        covered = sum(e - s for s, e in ranges)
        if num_rows == 0 or covered * 4 > num_rows * 3:
            return None
        selected = list(self._selected_chunks(i, columns))
        if any(col.max_rep > 0 for _, _, col in selected):
            return None
        if indexes is None:
            indexes = self.read_page_index(i, columns=columns)
        out = {}
        for path, cc, col in selected:
            oi = indexes.get(path, (None, None))[1]
            if oi is None or not oi.page_locations:
                return None
            firsts = [loc.first_row_index for loc in oi.page_locations]
            if (
                any(not isinstance(x, int) for x in firsts)
                or firsts[0] != 0
                or any(b <= a for a, b in zip(firsts, firsts[1:]))
                or any(
                    not isinstance(loc.offset, int) or loc.offset <= 0
                    for loc in oi.page_locations
                )
            ):
                return None  # foreign/corrupt index: full decode
            out[path] = read_chunk_row_ranges(
                self._f,
                cc,
                col,
                oi,
                ranges,
                num_rows,
                validate_crc=self.validate_crc,
                alloc=self.alloc,
            )
        return out

    @staticmethod
    def _column_rows(names, columns, n):
        """Row dicts from already-gathered column value lists, windowed to
        bound live tracked objects like every other materialization path."""

        def windows():
            for s in range(0, n, _ASSEMBLE_WINDOW):
                e = min(s + _ASSEMBLE_WINDOW, n)
                with timed_stage("assembly.rows") as el, _gc_paused():
                    rows = _zip_dict_rows(names, [c[s:e] for c in columns])
                _metrics.inc("assembly_rows_total", e - s, engine="vec")
                _metrics.observe("assembly_seconds", el.seconds)
                yield rows

        return itertools.chain.from_iterable(windows())

    @staticmethod
    def _masked_rows(names, columns, mask):
        """Materialize only the rows a boolean mask keeps, windowed like
        _ranged_rows. One itertools.compress pass per window gathers
        arbitrary (even per-row fragmented) masks at C speed — a run-list
        gather would pay a Python window round trip PER RUN, which for a
        selective predicate over random data is one per kept row."""
        from itertools import compress

        from .assembly_vec import _materialize_spec

        n = len(mask)

        def windows():
            for s in range(0, n, _ASSEMBLE_WINDOW):
                e = min(s + _ASSEMBLE_WINDOW, n)
                wm = mask[s:e]
                k = int(wm.sum())
                if not k:
                    continue
                with timed_stage("assembly.rows") as el, _gc_paused():
                    if k == e - s:
                        cols = [slice_column(c, s, e) for c in columns]
                    else:
                        wml = wm.tolist()
                        cols = []
                        for c in columns:
                            wc = slice_column(c, s, e)
                            if isinstance(wc, tuple):
                                wc = _materialize_spec(wc)
                            cols.append(list(compress(wc, wml)))
                    rows = _zip_dict_rows(names, cols)
                _metrics.inc("assembly_rows_total", k, engine="vec")
                _metrics.observe("assembly_seconds", el.seconds)
                yield rows

        return itertools.chain.from_iterable(windows())

    @staticmethod
    def _ranged_rows(names, columns, ranges):
        # chain.from_iterable over window LISTS: the per-row next() is pure
        # C (no Python generator frame resumes per row — those cost more
        # than the dict build itself at multi-M rows/s); the Python frame
        # below only wakes once per 64Ki-row window
        def windows():
            for start, stop in ranges:
                for s in range(start, stop, _ASSEMBLE_WINDOW):
                    e = min(s + _ASSEMBLE_WINDOW, stop)
                    # build INSIDE the contexts, yield OUTSIDE them: the
                    # consumer must run with GC enabled and off the stage
                    # timer (a yield inside `with` would hold both open
                    # across arbitrary consumer code)
                    with timed_stage("assembly.rows") as el, _gc_paused():
                        rows = _zip_dict_rows(
                            names, [slice_column(c, s, e) for c in columns]
                        )
                    _metrics.inc("assembly_rows_total", e - s, engine="vec")
                    _metrics.observe("assembly_seconds", el.seconds)
                    yield rows

        return itertools.chain.from_iterable(windows())

    def to_arrow(
        self, row_groups=None, columns=None, filters=None, read_dictionary=None
    ):
        """Decoded columns as a pyarrow.Table. Flat leaves (numerics,
        booleans, strings/binary, FLBA) and canonical single-level LIST
        columns take zero-copy fast paths; every deeper shape — structs,
        MAPs, multi-level lists, list-of-struct, struct-of-list, legacy
        repeated groups/leaves — assembles through the vectorized
        Dremel-levels builder (core/arrow_nested.py), matching the
        reference's full nested read surface (reference schema.go:216-312,
        floor/reader.go:302-409). The reverse of write_column's arrow
        ingest: a pyarrow user can hand columns either way without a
        rewrite.

        `filters` mirrors pyarrow.parquet.read_table's: a flat list of
        (column, op, value) triples (a conjunction) or a list of lists
        (an OR of conjunctions). Row groups that statistics/bloom exclude
        are never decoded; surviving rows are filtered EXACTLY. Filter
        columns outside the projection still apply, then drop.

        `read_dictionary` (list of flat string/binary column names, like
        pyarrow's) returns those columns DICTIONARY-ENCODED
        (dictionary<int32, large_string>) — indices and the (small)
        dictionary pass through without materializing the strings. Chunks
        with PLAIN fallback pages decode plain; a column mixing both
        normalizes to plain across groups so the type stays uniform."""
        if filters is not None:
            return self._to_arrow_filtered(
                row_groups, columns, filters, read_dictionary
            )
        import pyarrow as pa

        from .arrow_nested import nested_arrow_type

        dict_paths = self._dict_paths(read_dictionary)
        indices = list(
            range(self.num_row_groups) if row_groups is None else row_groups
        )
        if not indices:
            # zero groups selected: a zero-ROW table with the selected
            # schema, so cross-file concatenation never hits a mismatch
            # (nested_arrow_type derives the same type every data branch
            # produces, fast paths included)
            sel = self._resolve_columns(columns) if columns else self._selected
            by_top: dict[str, list] = {}
            for leaf in self.schema.leaves:
                if sel is None or leaf.path in sel:
                    by_top.setdefault(leaf.path[0], []).append(leaf.path)
            def _empty_type(top_name):
                t = nested_arrow_type(pa, self.schema.column((top_name,)), sel)
                if (top_name,) in dict_paths:
                    return pa.dictionary(pa.int32(), t)
                return t
            return pa.table({
                top_name: pa.array([], type=_empty_type(top_name))
                for top_name in by_top
            })
        per_group: list[dict] = []
        names: list[str] | None = None
        for i in indices:
            chunks = self._read_row_group(
                i, columns, pack=False, dict_paths=dict_paths
            )
            if not chunks:
                continue  # quarantined group (on_error != 'raise')
            cols = self._arrow_group_cols(pa, chunks, dict_paths)
            if names is None:
                names = list(cols)
            per_group.append(cols)
        if names is None:
            names = []
        if not per_group:
            if indices:
                # every selected group was quarantined (on_error != 'raise'):
                # deliver the zero-row table WITH the selected schema, like
                # an empty row-group selection would
                return self.to_arrow(
                    row_groups=[], columns=columns, read_dictionary=read_dictionary
                )
            return pa.table({})
        arrays = []
        for name in names:
            parts = [g[name] for g in per_group]
            is_dict = [pa.types.is_dictionary(a.type) for a in parts]
            if any(is_dict) and not all(is_dict):
                # a group with PLAIN fallback pages decoded plain: the
                # column normalizes to plain so the chunked type is uniform
                parts = [
                    a.dictionary_decode() if pa.types.is_dictionary(a.type) else a
                    for a in parts
                ]
            arrays.append(pa.chunked_array(parts))
        return pa.table(dict(zip(names, arrays)))

    def _dict_paths(self, read_dictionary) -> frozenset:
        """The dictionary-preserving projection (read_dictionary=): flat
        BYTE_ARRAY tops only."""
        from ..meta.parquet_types import Type

        if not read_dictionary:
            return frozenset()
        wanted = set()
        for name in read_dictionary:
            path = (
                tuple(name.split(".")) if isinstance(name, str) else tuple(name)
            )
            try:
                leaf = self.schema.column(path)
            except Exception as e:
                raise ParquetFileError(
                    f"parquet: read_dictionary column {name!r} not in schema"
                ) from e
            if (
                len(path) == 1
                and leaf.is_leaf
                and leaf.max_rep == 0
                and leaf.type == Type.BYTE_ARRAY
            ):
                wanted.add(path)
        return frozenset(wanted)

    def _arrow_group_cols(self, pa, chunks: dict, dict_paths) -> dict:
        """{top-level name: pyarrow array} for one decoded row group — the
        per-group body of to_arrow, shared with the filtered fast path so
        a group's chunks decode exactly once however they were read."""
        from ..meta.parquet_types import Type
        from .arrow_nested import build_top_field, retype_leaf
        from .arrays import ByteArrayData

        def _fast_kind(paths):
            """'flat' | 'list' | 'nested' for one top-level field's leaves."""
            if len(paths) != 1:
                return "nested"
            path = paths[0]
            leaf = self.schema.column(path)
            if leaf.max_rep == 0 and len(path) == 1:
                return "flat"
            if self._is_canonical_list(path, leaf) and leaf.type not in (
                Type.FIXED_LEN_BYTE_ARRAY, Type.INT96,
            ):
                return "list"
            return "nested"

        by_top: dict[str, dict] = {}
        for path, cd in chunks.items():
            by_top.setdefault(path[0], {})[path] = cd
        cols = {}
        for top_name, sub in by_top.items():
            kind = _fast_kind(list(sub))
            if kind == "nested":
                cols[top_name] = build_top_field(pa, self.schema, top_name, sub)
                continue
            (path, cd), = sub.items()
            leaf = self.schema.column(path)
            if kind == "list":
                cols[top_name] = self._arrow_list_column(pa, path, leaf, cd)
                continue
            if cd.indices is not None and isinstance(
                cd.dictionary, ByteArrayData
            ):
                cols[top_name] = self._arrow_dictionary_column(pa, leaf, cd)
                continue
            mask = None
            if cd.def_levels is not None and leaf.max_def > 0:
                valid = np.asarray(cd.def_levels) == leaf.max_def
                if not valid.all():
                    mask = ~valid
            values = cd.values
            if isinstance(values, ByteArrayData):
                atype = (
                    pa.large_string() if leaf.is_string() else pa.large_binary()
                )
                offsets = np.ascontiguousarray(values.offsets, dtype=np.int64)
                data = values.data
                if mask is not None:
                    # expand offsets to row positions: null rows repeat
                    # the running offset (zero-length slot)
                    offsets = _scatter_byte_offsets(valid, offsets)
                n = len(offsets) - 1
                bufs = [
                    None
                    if mask is None
                    else pa.py_buffer(
                        np.packbits(valid, bitorder="little").tobytes()
                    ),
                    pa.py_buffer(offsets),
                    pa.py_buffer(data),
                ]
                arr = pa.Array.from_buffers(
                    atype, n, bufs,
                    null_count=int(mask.sum()) if mask is not None else 0,
                )
            else:
                np_vals = np.asarray(values)
                if np_vals.ndim == 2:  # FLBA / INT96 rows
                    atype = pa.binary(np_vals.shape[1])
                    if mask is None:
                        flat = np.ascontiguousarray(np_vals).reshape(-1)
                        arr = pa.Array.from_buffers(
                            atype, len(np_vals), [None, pa.py_buffer(flat)]
                        )
                    else:
                        # values are DENSE (non-null cells only):
                        # scatter them to their row positions
                        it = iter(np_vals)
                        rows = [
                            bytes(next(it)) if ok else None for ok in valid
                        ]
                        arr = pa.array(rows, atype)
                elif mask is not None:
                    # dense non-null cells scatter to row positions
                    expanded = np.zeros(len(valid), np_vals.dtype)
                    expanded[valid] = np_vals
                    arr = pa.array(expanded, mask=mask)
                else:
                    arr = pa.array(np_vals)
            cols[path[0]] = retype_leaf(pa, leaf, arr)
        return cols

    def _arrow_dictionary_column(self, pa, leaf, cd):
        """A dictionary-preserved chunk -> pyarrow DictionaryArray: the
        (small) dictionary transfers zero-copy into large_string/
        large_binary, indices scatter to row positions with validity from
        the definition levels (read_dictionary= lane)."""
        d = cd.dictionary
        offs = np.ascontiguousarray(d.offsets, dtype=np.int64)
        dict_arr = pa.Array.from_buffers(
            pa.large_string() if leaf.is_string() else pa.large_binary(),
            len(d),
            [None, pa.py_buffer(offs), pa.py_buffer(d.data)],
        )
        n = cd.num_values
        idx = np.asarray(cd.indices, dtype=np.int32)
        valid = None
        if cd.def_levels is not None and leaf.max_def > 0:
            v = np.asarray(cd.def_levels) == leaf.max_def
            if not v.all():
                valid = v
        if valid is None:
            ind = pa.array(idx)
        else:
            expanded = np.zeros(n, dtype=np.int32)
            expanded[valid] = idx
            ind = pa.array(expanded, mask=~valid)
        return pa.DictionaryArray.from_arrays(ind, dict_arr)

    def _to_arrow_filtered(self, row_groups, columns, filters, read_dictionary=None):
        """Pruned + exactly-filtered columnar read (to_arrow's filters=).

        The row mask evaluates over a SEPARATE read of just the filter
        leaves, so a predicate on a projected-out column — even a nested
        sibling leaf — filters without leaking into the output schema
        (leaf-granular, like iter_rows' strips).

        Fast path: when the vectorized mask pipeline covers every predicate
        (core/filter_vec, arrow null semantics), each group's mask compiles
        straight off the decoded filter-leaf chunks and applies as ONE
        buffer-level take (`table.filter`) — no combine_chunks copies, no
        per-row work, record batches stream zero-copy into the IPC writer.
        VecFilterError falls back to the pyarrow-compute path below."""
        import pyarrow as pa
        import pyarrow.compute as pc

        from .filter import FilterError, dnf_group_may_match, normalize_dnf

        dnf = normalize_dnf(self.schema, filters)
        indices = [
            i
            for i in (
                range(self.num_row_groups) if row_groups is None else row_groups
            )
            if dnf_group_may_match(self.row_group(i), dnf, self._bloom_excludes, i)
        ]
        vacuous = not dnf or any(not conj for conj in dnf)
        if indices and not vacuous and self.on_error == "raise":
            out = self._to_arrow_vec_filtered(
                pa, dnf, indices, columns, read_dictionary
            )
            if out is not None:
                return out
        # flat top-level filter columns already in the projection evaluate
        # straight off `table`; only projected-out or nested paths pay a
        # second (filter-leaves-only) read
        sel = self._resolve_columns(columns) if columns else self._selected
        fpaths = sorted({p for conj in dnf for p, *_ in conj})
        extra = [
            p
            for p in fpaths
            if len(p) > 1 or (sel is not None and p not in sel)
        ]
        ftab = None
        if extra and not vacuous and self.on_error != "raise":
            # Quarantine decisions depend on which columns a read touches,
            # so the projection read and the filter-leaves read can drop
            # DIFFERENT groups (a corrupt chunk outside one projection) —
            # misaligned row masks below would escape as a raw pyarrow
            # length error. Read both sides group-by-group, keep only groups
            # BOTH deliver in full, and concatenate the kept per-group
            # tables directly (each group decodes exactly once, same as the
            # bulk read — to_arrow iterates per group internally anyway).
            kept_t, kept_f = [], []
            for i in indices:
                expect = self.row_group(i).num_rows or 0
                t_i = self.to_arrow(
                    row_groups=[i], columns=columns,
                    read_dictionary=read_dictionary,
                )
                if t_i.num_rows != expect:
                    continue  # group already dropped: skip the filter read
                f_i = self.to_arrow(row_groups=[i], columns=extra)
                if f_i.num_rows == expect:
                    kept_t.append(t_i)
                    kept_f.append(f_i)
            table = _concat_group_tables(pa, kept_t)
            if table is None:
                table = self.to_arrow(
                    row_groups=[], columns=columns,
                    read_dictionary=read_dictionary,
                )
            ftab = _concat_group_tables(pa, kept_f)
        else:
            table = self.to_arrow(
                row_groups=indices, columns=columns,
                read_dictionary=read_dictionary,
            )
        if vacuous or table.num_rows == 0:
            return table  # an empty conjunction is vacuously true
        if ftab is None and extra:
            ftab = self.to_arrow(row_groups=indices, columns=extra)

        # A column referenced in N DNF conjunctions must combine its chunks
        # once, not N times (combine_chunks copies the whole column); the
        # filter_combine_chunks counter pins the memoization in tests.
        combined: dict = {}
        leaf_cache: dict = {}

        def base_col(path):
            key = (path in extra or len(path) > 1, path[0])
            base = combined.get(key)
            if base is None:
                src = ftab if key[0] else table
                base = combined[key] = src.column(path[0]).combine_chunks()
                bump("filter_combine_chunks")
            return base

        def leaf_col(path):
            arr = leaf_cache.get(path)
            if arr is not None:
                return arr
            arr = base_col(path)
            if len(path) > 1:
                arr = pc.struct_field(arr, list(path[1:]))
            leaf_cache[path] = arr
            return arr

        try:
            mask = None
            for conj in dnf:
                m = None
                for path, _leaf, op, rv, _lo, _hi in conj:
                    if op == "contains":
                        # the LIST wrapper itself carries the predicate: its
                        # leaf path addresses the element for stats, but the
                        # arrow column is the top-level list
                        p = self._arrow_contains_mask(pa, pc, base_col(path), rv)
                        m = p if m is None else pc.and_kleene(m, p)
                        continue
                    arr = leaf_col(path)
                    if op == "is_null":
                        p = pc.is_null(arr)
                    elif op == "not_null":
                        p = pc.is_valid(arr)
                    elif op == "in":
                        p = pc.is_in(arr, value_set=pa.array(list(rv)))
                    elif op == "not_in":
                        p = pc.invert(
                            pc.is_in(arr, value_set=pa.array(list(rv)))
                        )
                    else:
                        p = {
                            "==": pc.equal, "!=": pc.not_equal,
                            "<": pc.less, "<=": pc.less_equal,
                            ">": pc.greater, ">=": pc.greater_equal,
                        }[op](arr, rv)
                    m = p if m is None else pc.and_kleene(m, p)
                mask = m if mask is None else pc.or_kleene(mask, m)
        except (pa.lib.ArrowInvalid, pa.lib.ArrowNotImplementedError,
                TypeError) as err:  # literal pyarrow cannot compare
            raise FilterError(
                f"filter: cannot evaluate over arrow columns: {err}"
            ) from err
        # Null handling mirrors pyarrow.parquet.read_table exactly: a null
        # comparison yields a null mask entry (dropped), EXCEPT not_in —
        # pc.is_in maps null to false, so invert KEEPS null rows (pyarrow's
        # convention). iter_rows' row predicate instead fails every op on
        # null (SQL-ish); the difference is pinned by tests.
        out = table.filter(mask)
        _metrics.inc(
            "query_rows_filtered_total",
            table.num_rows - out.num_rows,
            engine="arrow",
        )
        return out

    def _arrow_contains_mask(self, pa, pc, col, rv):
        """Row mask for a ('tags', 'contains', x) predicate over an arrow
        LIST column: one vectorized equality over the FLATTENED elements,
        lifted to rows through list_parent_indices — null lists contribute
        no elements and null elements compare null, so neither matches
        (identical to the scalar walk and the chunk-level mask)."""
        value = rv
        t = col.type
        if isinstance(rv, (bytes, bytearray)) and (
            pa.types.is_list(t) or pa.types.is_large_list(t)
        ) and (
            pa.types.is_string(t.value_type)
            or pa.types.is_large_string(t.value_type)
        ):
            # string element leaves coerce to bytes in the filter domain;
            # the arrow column compares in str space
            value = bytes(rv).decode("utf-8", errors="replace")
        flat = pc.list_flatten(col)
        parents = pc.list_parent_indices(col)
        em = pc.fill_null(pc.equal(flat, value), False)
        if isinstance(em, pa.ChunkedArray):
            em = em.combine_chunks()
        if isinstance(parents, pa.ChunkedArray):
            parents = parents.combine_chunks()
        hits = np.asarray(parents)[np.asarray(em)]
        m = np.zeros(len(col), dtype=bool)
        m[hits] = True
        return pa.array(m)

    def _to_arrow_vec_filtered(self, pa, dnf, indices, columns, read_dictionary):
        """The zero-copy filtered-read fast path: per group, the residual
        mask compiles off the decoded filter-leaf chunks (core/filter_vec,
        arrow null semantics so both paths stay value-identical) and
        applies as ONE buffer-level take (`Table.filter`) — no
        combine_chunks copies, no per-row predicate work. Returns None when
        the mask pipeline declines any predicate (VecFilterError), letting
        the pyarrow-compute path decide."""
        from .filter_vec import (
            VecFilterError,
            dnf_mask,
            group_row_count,
            vec_filter_enabled,
        )

        if not vec_filter_enabled() or not vec_enabled():
            return None
        fcols = {p for conj in dnf for p, *_ in conj}
        sel = self._resolve_columns(columns) if columns else self._selected
        # ONE decode per group covers projection AND filter leaves; the
        # mask compiles off the same chunks the table is built from
        read_cols = None if sel is None else sorted(sel | fcols)
        dict_paths = self._dict_paths(read_dictionary)
        parts = []
        filtered = 0
        try:
            for i in indices:
                chunks = self._read_row_group(
                    i, read_cols, pack=False, dict_paths=dict_paths
                )
                if not chunks:
                    raise VecFilterError("filter_vec: group undecodable")
                n_rows = group_row_count(chunks)
                with timed_stage("assembly.filter") as el:
                    mask = dnf_mask(chunks, dnf, n_rows, null_mode="arrow")
                _metrics.observe("filter_mask_seconds", el.seconds)
                kept = int(mask.sum())
                filtered += n_rows - kept
                if not kept:
                    continue  # the whole group drops: never build its table
                proj = (
                    chunks
                    if sel is None
                    else {p: cd for p, cd in chunks.items() if p in sel}
                )
                t_i = pa.table(self._arrow_group_cols(pa, proj, dict_paths))
                if t_i.num_rows != n_rows:
                    raise VecFilterError("filter_vec: projection row drift")
                parts.append(
                    t_i if kept == n_rows else t_i.filter(pa.array(mask))
                )
        except VecFilterError:
            return None
        _metrics.inc("query_rows_filtered_total", filtered, engine="vec")
        table = _concat_group_tables(pa, parts)
        if table is None:
            return self.to_arrow(
                row_groups=[], columns=columns, read_dictionary=read_dictionary
            )
        return table

    def _is_canonical_list(self, path, leaf) -> bool:
        """True for the one list shape _arrow_list_column's level math
        covers: top group > repeated mid group > element leaf, with no other
        optional layer (anything else — e.g. an optional group whose child
        is a bare repeated leaf — has different level semantics and must
        take the nested-deeper error, not silently corrupt)."""
        from ..meta.parquet_types import FieldRepetitionType

        if len(path) != 3 or leaf.max_rep != 1:
            return False
        top = self.schema.column((path[0],))
        mid = next((c for c in top.children if c.name == path[1]), None)
        if (
            mid is None
            or mid.repetition != FieldRepetitionType.REPEATED
            # exactly ONE element leaf: a legacy list-of-STRUCT repeated
            # group has several, and collapsing them to one column would
            # silently drop fields
            or len(mid.children) != 1
            or mid.children[0].path != leaf.path
        ):
            return False
        t = 1 if top.repetition == FieldRepetitionType.OPTIONAL else 0
        e = 1 if leaf.repetition == FieldRepetitionType.OPTIONAL else 0
        return leaf.max_def == t + 1 + e

    def _arrow_list_column(self, pa, path, leaf, cd):
        """One canonical LIST column chunk -> pyarrow LargeListArray: row
        lengths and validity from the levels (the same derivation as ragged
        device batches), element array from the dense non-null cells."""
        from ..meta.parquet_types import FieldRepetitionType, Type
        from .arrow_nested import retype_leaf
        from .arrays import ByteArrayData

        top = self.schema.column((path[0],))
        t = 1 if top.repetition == FieldRepetitionType.OPTIONAL else 0
        n = cd.num_values
        rl = (
            np.asarray(cd.rep_levels)
            if cd.rep_levels is not None
            else np.zeros(n, dtype=np.uint16)
        )
        dl = (
            np.asarray(cd.def_levels)
            if cd.def_levels is not None
            else np.full(n, leaf.max_def, dtype=np.uint16)
        )
        starts = np.nonzero(rl == 0)[0]
        slot = dl >= t + 1  # level entries that denote a list ELEMENT
        elem_valid = (dl == leaf.max_def)[slot]
        lengths = (
            np.add.reduceat(slot.astype(np.int64), starts)
            if len(starts)
            else np.zeros(0, dtype=np.int64)
        )
        row_null = (dl[starts] < t) if t else np.zeros(len(starts), dtype=bool)
        n_slots = int(slot.sum())
        values = cd.values
        if isinstance(values, ByteArrayData):
            etype = pa.large_string() if leaf.is_string() else pa.large_binary()
            if elem_valid.all():
                offs = np.ascontiguousarray(values.offsets, dtype=np.int64)
                elem = pa.Array.from_buffers(
                    etype, n_slots,
                    [None, pa.py_buffer(offs), pa.py_buffer(values.data)],
                )
            else:
                offs = _scatter_byte_offsets(elem_valid, values.offsets)
                elem = pa.Array.from_buffers(
                    etype, n_slots,
                    [
                        pa.py_buffer(
                            np.packbits(elem_valid, bitorder="little").tobytes()
                        ),
                        pa.py_buffer(offs),
                        pa.py_buffer(values.data),
                    ],
                    null_count=int((~elem_valid).sum()),
                )
        else:
            npv = np.asarray(values)
            if npv.ndim != 1 or leaf.type in (
                Type.FIXED_LEN_BYTE_ARRAY, Type.INT96,
            ):
                raise ParquetFileError(
                    f"parquet: to_arrow does not cover fixed-width elements "
                    f"inside lists ({'.'.join(path)}); use iter_rows"
                )
            if elem_valid.all():
                elem = pa.array(npv)
            else:
                expanded = np.zeros(n_slots, dtype=npv.dtype)
                expanded[elem_valid] = npv
                elem = pa.array(expanded, mask=~elem_valid)
        elem = retype_leaf(pa, leaf, elem)
        offsets = np.zeros(len(lengths) + 1, dtype=np.int64)
        np.cumsum(lengths, out=offsets[1:])
        if row_null.any():
            # a null offset at i marks list i null; the final offset (the
            # appended False) must stay valid
            offsets_pa = pa.array(
                offsets, pa.int64(), mask=np.append(row_null, False)
            )
        else:
            offsets_pa = pa.array(offsets, pa.int64())
        return pa.LargeListArray.from_arrays(offsets_pa, elem)

    def iter_row_groups(self, columns=None):
        for i in range(self.num_row_groups):
            yield self.read_row_group(i, columns=columns)

    def __iter__(self):
        """Iterating the reader yields rows — the `for reader.NextRow()` loop
        of the reference (file_reader.go:258) as a Python iterator."""
        return self.iter_rows()

    # -- lifecycle -------------------------------------------------------------

    @classmethod
    def open_metadata(cls, path, footer_cache=None) -> FileMetaData:
        """Parse ONLY the footer of `path` — no data pages are touched and
        no reader object (or open handle) survives the call. The cheap
        multi-file planning primitive: a dataset scanning a thousand-file
        glob footers every file once here, then opens per-unit readers
        with `metadata=` so the footer never re-parses. `footer_cache` (an
        io.cache.FooterCache) makes the parse once-per-file-GENERATION: a
        warm hit performs zero source reads; staleness is checked against
        the file's (size, mtime).

        `path` may be an http(s):// URL (io.remote.HttpSource under the
        installed resilience policy): the footer cache then validates
        against the object's (size, ETag) generation — a warm remote
        re-plan costs one HEAD and zero body bytes per file."""
        if isinstance(path, str) and path.startswith(("http://", "https://")):
            from ..io.source import open_source

            src, owns = open_source(path)
            try:
                gen = src.generation()
                if footer_cache is not None:
                    meta = footer_cache.get(path, sig=gen)
                    if meta is not None:
                        return meta
                meta = read_file_metadata(SourceFile(src))
                if footer_cache is not None:
                    footer_cache.put(path, meta, sig=gen)
                return meta
            finally:
                if owns:
                    src.close()
        if footer_cache is not None:
            meta = footer_cache.get(path)
            if meta is not None:
                return meta
        from ..io.source import LocalFileSource

        with LocalFileSource(path) as src:
            meta = read_file_metadata(SourceFile(src))
        if footer_cache is not None:
            footer_cache.put(path, meta)
        return meta

    @classmethod
    def open_many(cls, paths, columns=None, **options) -> "list[FileReader]":
        """Open several files at once (footer parse only — FileReader's
        constructor never touches data pages). All-or-nothing: if any open
        fails, the already-opened readers are closed before the error
        propagates, so no handles leak. Every option forwards to each
        reader (`on_error=`, `validate_crc=`, ...)."""
        readers: list[FileReader] = []
        try:
            for p in paths:
                readers.append(cls(p, columns=columns, **options))
        except BaseException:
            for r in readers:
                r.close()
            raise
        return readers

    def close(self) -> None:
        """Release the underlying source when this reader owns it (paths,
        bytes). Idempotent: the dataset layer's lazy open/close churn (and
        `with` blocks wrapped in error paths) may close the same reader
        more than once. Caller-provided sources/file objects stay open —
        their lifetime belongs to the caller."""
        if self._owns_file:
            self._source.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
