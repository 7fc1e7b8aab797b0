"""Batched TPU page-decode pipeline — the pluggable decoder backend.

The north-star architecture (BASELINE.json): the host walks pages, parses
Thrift headers, decompresses blocks and decodes R/D levels; the *value* streams
of a whole chunk are fused into one batch of device tensors and decoded by the
kernels in device_ops.py. Users opt in per reader:
FileReader(..., backend="tpu") — the WithDecoderBackend(TPU) analogue.

Batching model per chunk:
  RLE_DICTIONARY  all pages' run tables concatenate into one table (bit
                  offsets rebased into one packed buffer, run counts clamped
                  to each page's real value count so no padding enters the
                  output), and the freeze re-frames the runs position by
                  position at the chunk's one static width (the hybrid frame,
                  device_ops.pack_hybrid_upload: no run table reaches the
                  link) -> ONE device unpack for the whole chunk, then one
                  device gather against the dictionary.
  DELTA_BP        all pages' miniblocks re-framed the same way (the delta
                  frame, device_ops.pack_delta_upload); a single wrapping
                  prefix sum decodes every page at once, rebased at each page
                  start (valid in modular arithmetic).
  PLAIN           raw little-endian bytes upload + device bitcast.

The decode of one chunk is split into two phases so a whole row group's worth
of device work can be in flight before anything synchronizes (JAX async
dispatch; the host<->device link is the scarce resource, SURVEY §7.3.4):

  plan_chunk_tpu()   host prescan + device dispatch; returns a _ChunkPlan
                     holding un-synchronized device arrays.
  plan.finalize()    fetches results and reassembles a ChunkData, byte-
                     identical to the host path.
  plan.device_column()  keeps the decoded values in HBM instead: the
                     decode-to-device delivery point (DeviceColumn).

All shapes are padded to power-of-two buckets so XLA compiles each kernel a
bounded number of times (static shapes, SURVEY §7.1). All device index math is
int32 (device_ops.py); batches are split at MAX_DEVICE_BATCH_BITS.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from ..meta import ParquetFileError
from ..meta.parquet_types import Encoding, FieldRepetitionType, PageType, Type
from ..core.alloc import decoded_nbytes
from ..core.arrays import ByteArrayData
from ..core.chunk import ChunkData, ChunkError, iter_chunk_pages, _check_crc
from ..core.compress import decompress_block
from ..core.page import PageError, decode_dict_page
from ..core.schema import Column
from ..ops.packed_levels import PackedLevels
from ..ops.rle_hybrid import prescan_hybrid
from ..ops.delta import prescan_delta_packed
from ..utils import metrics as _metrics
from ..utils import trace as _trace
from .device_ops import (
    MAX_DEVICE_BATCH_BITS,
    FrozenDelta,
    FrozenHybrid,
    _bucket,
    delta_block_encode_device,
    delta_packed_decode_device,
    dict_gather_device,
    dict_indices_device,
    dict_lookup_tier,
    double_narrow_device,
    expand_hybrid_device,
    pack_delta_upload,
    pack_hybrid_upload,
    plain_bytearray_encode_device,
    rle_hybrid_encode_device,
)

__all__ = [
    "read_chunk_tpu",
    "plan_chunk_tpu",
    "DeviceColumn",
    "DeviceDoubleError",
    "DOUBLE_FORMS",
    "check_double_delivery",
    "TpuDecodeStats",
    "dispatch_pool",
    "device_put_pipelined",
    "assemble_hybrid_device_stream",
    "assemble_delta_device_stream",
    "encode_device_column",
]

# Patchable in tests to force multi-batch splitting on small inputs.
_BATCH_BITS_CAP = MAX_DEVICE_BATCH_BITS
# Floor of the bucket a LIST leaf's per-document lengths upload at (the padded
# delivery): 16 KB, so that a row group's document count, which is data, stays
# in one bucket unless its documents average under 256 elements a 2^20 of them.
_LENGTHS_FLOOR = 4096


# -- the dispatch thread -------------------------------------------------------
#
# One process-wide single-thread executor owns device dispatch (uploads +
# kernel launches). It lives HERE — next to the device pipeline it feeds —
# and is shared by every consumer (FileReader's chunk plans, the dataset
# layer's batch uploads): jax calls stay serialized in deterministic order
# while their RPC latency overlaps host-side work on other threads.

_dispatcher = None
_dispatcher_lock = threading.Lock()


def dispatch_pool():
    """The process-wide single-thread device-dispatch executor."""
    global _dispatcher
    from concurrent.futures import ThreadPoolExecutor

    with _dispatcher_lock:
        if _dispatcher is None:
            _dispatcher = ThreadPoolExecutor(
                max_workers=1,
                thread_name_prefix="pqt-dispatch",
                initializer=_trace.name_os_thread,
            )
        return _dispatcher


def device_put_pipelined(
    batches, placement=None, depth: int = 2, stage_name: str = "device_put"
):
    """Yield device-resident copies of host pytrees, keeping up to `depth`
    transfers in flight ahead of the consumer (depth 2 = classic double
    buffering: while the consumer works on batch k, batch k+1's upload is
    already running on the dispatch thread).

    `placement` is anything jax.device_put accepts — a jax.Device, a
    Sharding laying each batch over a mesh, or None for the process default.
    Order is preserved; an exception from `batches` or from a transfer
    surfaces at the yield that would have produced that batch. Each upload
    runs under a `stage_name` stage that carries the batch's bytes
    (instrumented_submit carries the caller's active decode_trace onto the
    dispatch thread and records the wait for it as pool.wait)."""
    from collections import deque

    from ..obs.pool import instrumented_submit

    def nbytes(b) -> int:
        if not _trace.active():
            return 0
        return sum(getattr(x, "nbytes", 0) for x in jax.tree_util.tree_leaves(b))

    if depth <= 0:
        for b in batches:
            # upload INSIDE the stage, yield OUTSIDE it: a yield under the
            # context would bill arbitrary consumer time to the transfer
            with _trace.stage(stage_name, nbytes(b)):
                out = jax.device_put(b, placement)
            yield out
        return

    def put(b):
        with _trace.stage(stage_name, nbytes(b)):
            return jax.device_put(b, placement)

    pool = dispatch_pool()
    it = iter(batches)
    pending = deque()
    source_err = None

    def fill():
        # A source failure is DEFERRED, not raised here: batches already
        # decoded and uploaded must still reach the consumer, and the error
        # must surface at the stream position where the source actually
        # failed — raising mid-fill would drop up to `depth` in-flight
        # batches and misattribute the failure (docstring contract).
        nonlocal source_err
        if source_err is not None:
            return
        while len(pending) < depth:
            try:
                b = next(it)
            except StopIteration:
                return
            except BaseException as e:  # noqa: BLE001 — re-raised in order
                source_err = e
                return
            pending.append(instrumented_submit(pool, put, b))

    fill()
    while pending:
        fut = pending.popleft()
        fill()
        yield fut.result()
    if source_err is not None:
        raise source_err


class DeviceDoubleError(ParquetFileError):
    """A DOUBLE column was asked for on a device that does not hand float64
    back bit-identical. A TPU has no native f64: XLA rewrites it to a pair
    of f32, so neither a transfer nor a u64->f64 bitcast keeps the bits
    (v5e, libtpu 0.0.34: 19.88 resident in HBM fetches back as
    19.879999999999995, and f64->u64 bitcast is UNIMPLEMENTED in the x64
    rewriter). Exactness is part of the result, so the device path refuses
    rather than delivering values an ulp off. The device entry points take
    `doubles=` for the two forms a TPU does hold exactly (DOUBLE_FORMS):
    "bits" (uint64 IEEE-754 patterns) and "float32" (the round-to-nearest-
    even narrowing, bit for bit numpy's astype). Or project the column out,
    or read it on the host."""


# What FileReader's device entry points accept as `doubles=` besides None:
#   "bits"     DeviceColumn.values is uint64, the IEEE-754 bit pattern of every
#              non-null value: exact on every platform
#   "float32"  DeviceColumn.values is float32, each value the round-to-nearest-
#              even narrowing of the file's float64 (numpy's astype(float32):
#              +-inf on overflow, f32 subnormals, -0.0 kept, NaN stays NaN)
# Either way no float64 value enters a device program: bit patterns stay
# unsigned from the upload to the delivery, a dictionary narrows on the host
# before it is uploaded, PLAIN / BYTE_STREAM_SPLIT values narrow on the device
# in integer arithmetic (device_ops.double_narrow_device).
DOUBLE_FORMS = ("bits", "float32")


def check_doubles_form(doubles) -> None:
    if doubles is not None and doubles not in DOUBLE_FORMS:
        raise ValueError(f'doubles must be None, "bits" or "float32", not {doubles!r}')


@functools.lru_cache(maxsize=None)
def _platform_holds_f64(platform: str) -> bool:
    """Measured once per platform: does a float64 round-trip bit-exactly?"""
    probe = np.array([19.88, 0.1, 1e300, 5e-324, -0.0], dtype=np.float64)
    back = np.asarray(jax.device_put(probe, jax.devices(platform)[0]))
    return back.dtype == probe.dtype and back.tobytes() == probe.tobytes()


def check_double_delivery(names, placement=None) -> None:
    """Raise DeviceDoubleError when float64 columns `names` are bound for a
    device that cannot hold them exactly. `placement` is a jax.Device, a
    Sharding, or None for the device jax work on this thread lands on."""
    if placement is None:
        placement = jax.config.jax_default_device or jax.devices()[0]
    if isinstance(placement, str):
        platform = placement
    elif hasattr(placement, "device_set"):
        platform = next(iter(placement.device_set)).platform
    else:
        platform = placement.platform
    if not _platform_holds_f64(platform):
        raise DeviceDoubleError(
            f"parquet: DOUBLE column(s) {', '.join(names)} cannot be held "
            f"bit-exactly on {platform} (f64 is emulated as an f32 pair); "
            'ask the device entry points for doubles="bits" (uint64 IEEE-754 '
            'bit patterns) or doubles="float32" (round-to-nearest-even '
            "narrowing), project them out, or read on the host"
        )


def _pad_device(arr):
    """Zero-pad a device array to its power-of-two bucket so kernels taking
    it compile a bounded number of times (one device op; no host copy) — an
    eager concatenate, itself a program a length: what the padded delivery
    and the mixed numeric merge avoid by padding on the host (_pad_host).
    Callers left: the index stream of the byte-array merge
    (_merge_ragged_bytes), device_values_padded's exact fallback, _narrow
    outside the mixed merge, the device PLAIN BYTE_ARRAY encoder and
    core/reader's ragged and nullable expansions."""
    import jax.numpy as jnp

    n = int(arr.shape[0])
    pad = _bucket(max(n, 1)) - n
    if pad:
        arr = jnp.concatenate([arr, jnp.zeros(pad, dtype=arr.dtype)])
    return arr


def _page_merge_tables(page_infos, plain_entries):
    """Padded per-page tables for the mixed-merge device kernels:
    (page_kind, page_row_start, aux, n_rows). `plain_entries(payload)` maps a
    'values' payload to (aux entries consumed, rows contributed)."""
    kinds_t: list[int] = []
    row_starts: list[int] = [0]
    aux: list[int] = []
    idx_base = plain_base = rowpos = 0
    for _n, _d, _r, kind, payload in page_infos:
        if kind == "dict":
            kinds_t.append(1)
            aux.append(idx_base)
            idx_base += payload
            rowpos += payload
            row_starts.append(rowpos)
        elif kind == "values":
            adv, rows = plain_entries(payload)
            kinds_t.append(0)
            aux.append(plain_base)
            plain_base += adv
            rowpos += rows
            row_starts.append(rowpos)
    P = len(kinds_t)
    P_pad = _bucket(max(P, 1), 16)
    page_kind = np.zeros(P_pad, dtype=np.int32)
    page_kind[:P] = kinds_t
    prs = np.full(P_pad + 1, rowpos, dtype=np.int32)
    prs[: P + 1] = row_starts
    aux_np = np.zeros(P_pad, dtype=np.int32)
    aux_np[:P] = aux
    return page_kind, prs, aux_np, rowpos


def _mixed_segments(page_infos, batch_totals, batch_lens):
    """The segment table of merge_mixed_numeric_device: (seg_kind,
    seg_row_start, seg_src, n_rows), the first three padded to a power-of-two
    bucket of segments (floor 4; a padding segment is empty). Adjacent pages
    of one kind whose rows are contiguous in their source as in the output
    are ONE segment: the PLAIN pool is one concatenation, and dictionary rows
    are contiguous within an index batch — `batch_totals` real indices each,
    laid end to end at their padded lengths `batch_lens`, so a batch boundary
    closes a segment. A chunk that fell back to PLAIN once has two. The true
    counts travel here, as data: no array the kernel takes is cut to them."""
    kinds: list[int] = []
    row_starts: list[int] = []
    src: list[int] = []
    rowpos = plain_base = batch = in_batch = batch_base = 0
    for _n, _d, _r, kind, payload in page_infos:
        if kind == "dict":
            rows = payload
            while rows and batch < len(batch_totals) - 1 and in_batch == batch_totals[batch]:
                batch_base += batch_lens[batch]
                batch += 1
                in_batch = 0
            k, base = 1, batch_base + in_batch
            in_batch += rows
        elif kind == "values":
            rows = len(payload)
            k, base = 0, plain_base
            plain_base += rows
        else:
            continue
        if rows and not (kinds and kinds[-1] == k and src[-1] + rowpos - row_starts[-1] == base):
            kinds.append(k)
            row_starts.append(rowpos)
            src.append(base)
        rowpos += rows
    S = len(kinds)
    S_pad = _bucket(max(S, 1), 4)
    seg_kind = np.zeros(S_pad, dtype=np.int32)
    seg_kind[:S] = kinds
    seg_row_start = np.full(S_pad + 1, rowpos, dtype=np.int32)
    seg_row_start[:S] = row_starts
    seg_src = np.zeros(S_pad, dtype=np.int32)
    seg_src[:S] = src
    return seg_kind, seg_row_start, seg_src, rowpos


def _skewed_dict_bound(dictionary, dict_rows: int, plain_bytes: int):
    """(padded byte bound, acceptable?) for the ragged byte merge: the output
    pads to the worst-case dictionary entry per row, so a skewed dictionary
    (one huge entry) must keep the host fallback — 4x the expected size or
    64 MB, whichever is larger."""
    dict_lens = np.diff(dictionary.offsets)
    n_dict = len(dictionary.offsets) - 1
    max_len = int(dict_lens.max()) if n_dict and dict_rows else 0
    mean_len = float(dict_lens.mean()) if n_dict else 0.0
    bound = plain_bytes + dict_rows * max_len
    est = plain_bytes + int(dict_rows * mean_len) + 1
    ok = bound < (1 << 31) and bound <= max(64 << 20, 4 * est)
    return bound, ok


def _index_width(page_width: int, n_dict: int) -> int:
    """The ONE bit width a chunk's dictionary indices ship at: the widest of
    its pages, and at least what addresses the dictionary's size plus an
    eighth. A writer packs each page at the width of the dictionary SO FAR,
    and a dictionary's final size varies a little from chunk to chunk; taken
    as written, the static `width` of expand_hybrid_device (a compiled
    program each) would follow where the dictionary crossed a power of two
    (TLC tip_amount: 2,012-2,062 entries a row group, 11 or 12 bits; PERF.md
    section 6, PR 28). The eighth only bites within a ninth of a power of
    two: 3, 7, 265 or 8,900 entries keep their written width."""
    n = n_dict + n_dict // 8
    return min(32, max(page_width, (n - 1).bit_length() if n > 0 else 0))


@dataclass
class TpuDecodeStats:
    pages: int = 0
    device_values: int = 0
    host_fallback_pages: int = 0
    device_batches: int = 0


def _count_host_page(plan: "_ChunkPlan") -> None:
    """One page of a device-bound chunk had its VALUES decoded on the host
    (a shape or size the device route does not cover); reported by
    prepare_chunk_plan once the plan is committed."""
    plan.host_pages += 1
    if plan.stats is not None:
        plan.stats.host_fallback_pages += 1


_NUMERIC_DTYPE = {
    Type.INT32: np.int32,
    Type.INT64: np.int64,
    Type.FLOAT: np.float32,
    Type.DOUBLE: np.float64,
}


# -- dispatch of a frozen upload ------------------------------------------------
#
# What _ChunkPlan.dispatch_device does with each frozen record. What is in
# the buffers is device_ops.py's business (pack_hybrid_upload,
# pack_delta_upload): nothing in this module indexes into them.


def _dispatch_hybrid(frozen: FrozenHybrid, padded: bool = False) -> jnp.ndarray:
    """`padded` keeps the kernel's n_pad output whole (the positions past
    `total` hold 0): the exact-length slice is a program a length."""
    with _trace.stage("dispatch.upload", frozen.buf.nbytes):
        buf = jnp.asarray(frozen.buf)
    with _trace.stage("dispatch.launch"):
        dev = expand_hybrid_device(buf, frozen.width, frozen.n_pad)
        return dev if padded else dev[: frozen.total]


def _dispatch_delta(frozen: FrozenDelta, padded: bool = False) -> jnp.ndarray:
    with _trace.stage("dispatch.upload", frozen.frame.nbytes):
        frame = jnp.asarray(frozen.frame)
    with _trace.stage("dispatch.launch"):
        dev = delta_packed_decode_device(
            frame, frozen.nbits, frozen.width, frozen.n_pad, frozen.p_pad
        )
        return dev if padded else dev[: frozen.total]


def _pad_host(host: np.ndarray, floor: int = 1024) -> np.ndarray:
    """A host array zero-padded to its power-of-two bucket before it uploads
    (the padded delivery: the array's length must not reach a compiled shape)."""
    out = np.zeros(_bucket(max(len(host), 1), floor), dtype=host.dtype)
    out[: len(host)] = host
    return out


# -- the chunk plan ------------------------------------------------------------


@dataclass
class DeviceColumn:
    """Decoded column delivered in device memory (HBM) — the TPU-native
    output of the decode pipeline. Numeric columns carry `values` (real
    dtype; floats bitcast on device from their wire bit patterns; a DOUBLE
    asked for under doubles= arrives in the form `double_form` names).
    Byte-array
    columns carry Arrow-style `data` + `offsets`, or — for dictionary-encoded
    chunks — device `indices` plus the (small) dictionary both host-side and
    as device `dict_data`/`dict_offsets`.

    def/rep levels stay host-side (record assembly is a host concern,
    SURVEY §7.1); under compact_levels they arrive bit-packed
    (ops.packed_levels.PackedLevels)."""

    num_values: int
    values: jnp.ndarray | None = None
    indices: jnp.ndarray | None = None
    dictionary: object | None = None  # host ByteArrayData | np.ndarray
    data: jnp.ndarray | None = None  # uint8 payload (byte arrays)
    offsets: jnp.ndarray | None = None  # int64 offsets, len = n + 1
    dict_data: jnp.ndarray | None = None  # uint8 dictionary payload
    dict_offsets: jnp.ndarray | None = None
    def_levels: "np.ndarray | PackedLevels | None" = None
    rep_levels: "np.ndarray | PackedLevels | None" = None
    # a DOUBLE column delivered under doubles=: "bits" (`values` is uint64
    # IEEE-754 patterns) or "float32" (`values` is the exact narrowing);
    # None for every other column and for the default float64 delivery
    double_form: str | None = None
    # `values` were merged from dictionary and PLAIN pages in HBM
    # (merge_mixed_numeric_device): the chunk passed its writer's dictionary limit
    mixed: bool = False
    # memoized device copies of the level streams (one upload, shared by
    # every list_layout() depth)
    _dev_rep: "jnp.ndarray | None" = None
    _dev_def: "jnp.ndarray | None" = None

    def list_layout(self, parent_rep: int, elem_def: int):
        """Arrow-style offsets/validity of one repeated depth, computed ON
        DEVICE from this column's level streams (device_ops.
        list_layout_device): the levels upload once (memoized) and the
        offsets/first-def arrays stay in HBM, so a JAX consumer building
        ragged batches from a device-decoded column never round-trips
        record-assembly structure through the host.

        Returns (offsets int32[n+1], first_def int32[n], n_slots int32
        scalar device array); entries past n_slots are padding. Feed
        `first_def < node.max_def` for the depth's null mask."""
        from .device_ops import list_layout_device

        if self.rep_levels is None:
            raise ValueError("list_layout: column has no repetition levels")
        if self._dev_rep is None:
            self._dev_rep = jnp.asarray(
                np.asarray(self.rep_levels), dtype=jnp.int32
            )
        if self._dev_def is None:
            dl = self.def_levels
            if dl is None:
                # a missing def stream means every entry FULLY defined (the
                # host engine's convention, assembly_vec._Stream): saturate
                # so any elem_def threshold passes and no slot reads null
                self._dev_def = jnp.full(
                    self.num_values, jnp.iinfo(jnp.int32).max, dtype=jnp.int32
                )
            else:
                self._dev_def = jnp.asarray(np.asarray(dl), dtype=jnp.int32)
        return list_layout_device(
            self._dev_rep, self._dev_def, parent_rep, elem_def
        )


class _ChunkPlan:
    """Host-side record of one chunk's in-flight device decode."""

    def __init__(
        self, column: Column, expected: int, doubles: str | None = None, padded: bool = False
    ):
        self.column = column
        self.expected = expected
        # the padded delivery (prepare_chunk_plan list_lengths=True): every
        # upload at a bucket that the chunk's counts cannot move within the
        # row group's budget, every kernel output left at its padded length
        self.padded = padded
        # the delivered form of a DOUBLE column (DOUBLE_FORMS), else None
        self.doubles = doubles if column.type == Type.DOUBLE else None
        # under doubles=: the dictionary as it uploads (bit patterns, or
        # narrowed on the host; padded to its index width's 2^w entries)
        self.dict_upload: np.ndarray | None = None
        self.page_infos: list[tuple] = []  # (n, def, rep, kind, payload)
        # whole-chunk level arrays from the native walk (page slices view
        # them); when set, finalize/device_column skip the per-page concat
        self.native_def: np.ndarray | None = None
        self.native_rep: np.ndarray | None = None
        self.dictionary = None
        self.dict_dev = None
        self.dev_hybrid: list[jnp.ndarray] = []  # per batch, page order
        self.dev_delta: list[jnp.ndarray] = []  # per batch, page order
        self.stats: TpuDecodeStats | None = None
        # frozen upload buffers (built at the END of prepare, host-only, so
        # the dispatch thread does nothing but transfers + kernel launches)
        self.frozen_hybrid: list[FrozenHybrid] = []
        self.frozen_delta: list[FrozenDelta] = []
        self.plain_host = None
        self.dev_plain: jnp.ndarray | None = None
        # BYTE_STREAM_SPLIT pages shipped raw: [( (4, n_pad) u8 host staging,
        # num_values )] -> device transpose (kernels/device_ops
        # bss_transpose_device); page order matches the "bss" page_infos
        self.bss_host: list[tuple] = []
        self.dev_bss: list[tuple] = []  # [(device streams, num_values)]
        self.host_pages = 0  # pages whose values decoded on the host
        # the padded delivery of a single-level LIST leaf (prepare_chunk_plan
        # list_lengths=True, for lists="pack"): the record structure as
        # per-document element counts, O(documents), uploaded at a bucketed
        # length in place of the level streams; values stay at their kernels'
        # padded lengths (device_values_padded) with the counts as host ints
        self.list_lengths: np.ndarray | None = None
        self.list_elements = 0
        self.dev_lengths: jnp.ndarray | None = None
        # true counts of the device arrays a padded dispatch left unsliced:
        # [plain, [hybrid batches], [delta batches]]
        self.padded_totals: list = [0, [], []]
        # dictionary and PLAIN pages of a numeric column that device_column
        # merges in HBM (merge_mixed_numeric_device). The one predicate,
        # decided by dispatch_device, which pads every upload to its bucket
        # for it whatever `padded` says
        self.mixed_numeric = False
        self._dispatched = False

    # -- device dispatch (async; nothing synchronizes here) --------------------
    #
    # The only phase that touches jax: keep it on the dispatching thread so
    # the jax-free prepare phase can run on worker threads. Each host->device
    # copy runs under a dispatch.upload stage that carries the numpy buffers'
    # bytes, each jitted call under dispatch.launch: both nest in the
    # reader's dispatch stage, so a trace says which of the two holds the
    # dispatch thread.

    def dispatch_device(self) -> "_ChunkPlan":
        if self._dispatched:
            return self
        self._dispatched = True
        padded = self.padded
        d = self.dictionary if self.dict_upload is None else self.dict_upload
        # a mixed chunk's dictionary and PLAIN row counts are its own: were
        # they the lengths of what uploads, every program that touches the
        # arrays would compile once a chunk. They pad here, on the host, and
        # the expansion stays whole; the merge takes the counts as data
        numeric_dict = isinstance(d, np.ndarray) and d.ndim == 1
        kinds = {k for _, _, _, k, _ in self.page_infos if k != "empty"}
        self.mixed_numeric = (
            self.column.type in _NUMERIC_DTYPE
            # a DOUBLE delivered as float64 is excluded: the merge needs the
            # f64<->u64 bitcast, which XLA's x64 rewriter does not implement
            # on TPU; it takes device_column's host-merge fallback. Under
            # doubles= the merge never leaves the unsigned domain (FLOAT is
            # fine either way: u32 bitcasts are native)
            and (self.column.type != Type.DOUBLE or self.doubles is not None)
            and "dict" in kinds
            and kinds <= {"dict", "values"}
            and bool(self.frozen_hybrid)
            and numeric_dict
            and self.plain_host is not None
        )
        whole = padded or self.mixed_numeric
        if whole and self.dict_upload is None and numeric_dict:
            d = _pad_host(d)
        if padded:
            lengths = _pad_host(self.list_lengths, _LENGTHS_FLOOR)
            with _trace.stage("dispatch.upload", lengths.nbytes):
                self.dev_lengths = jnp.asarray(lengths)
            _metrics.event("list_structure_upload_bytes", lengths.nbytes)
            _trace.count("list_structure_upload_bytes", lengths.nbytes)
        if self.frozen_hybrid and numeric_dict:
            # Upload the dictionary only when device-decoded indices will
            # gather against it (device_column); host reassembly gathers on
            # host. Floats travel as bit patterns: a gather is dtype-
            # agnostic and the mixed-page merge works in the uint domain
            # (f64 itself is not native on TPU — see DeviceDoubleError).
            if d.dtype.kind == "f":
                d = d.view(np.uint32 if d.dtype.itemsize == 4 else np.uint64)
            with _trace.stage("dispatch.upload", d.nbytes):
                self.dict_dev = jnp.asarray(d)
        # Homogeneous PLAIN numeric chunks are pure uploads (buffer already
        # concatenated at prepare time).
        if self.plain_host is not None:
            host = _pad_host(self.plain_host) if whole else self.plain_host
            if self.mixed_numeric and host.dtype.kind == "f":
                # the merge works on bit patterns: they upload as they are
                host = host.view(np.uint32 if host.dtype.itemsize == 4 else np.uint64)
            with _trace.stage("dispatch.upload", host.nbytes):
                self.dev_plain = self._upload(host)
            self.padded_totals[0] = len(self.plain_host)
            self.plain_host = None
        for streams, nv in self.bss_host:
            with _trace.stage("dispatch.upload", streams.nbytes):
                self.dev_bss.append((jnp.asarray(streams), nv))
            if self.stats is not None:
                self.stats.device_values += nv
                self.stats.device_batches += 1
        self.bss_host = []
        stats = self.stats
        for frozen in self.frozen_hybrid:
            self.dev_hybrid.append(_dispatch_hybrid(frozen, whole))
            self.padded_totals[1].append(frozen.total)
            if stats is not None:
                stats.device_values += frozen.total
                stats.device_batches += 1
        for frozen in self.frozen_delta:
            self.dev_delta.append(_dispatch_delta(frozen, padded))
            self.padded_totals[2].append(frozen.total)
            if stats is not None:
                stats.device_values += frozen.total
                stats.device_batches += 1
        self.frozen_hybrid = []
        self.frozen_delta = []
        return self

    # -- fetch + host reassembly (byte-identical to core.chunk.read_chunk) ----

    def finalize(self, keep_dict_indices: bool = False) -> ChunkData:
        column = self.column
        hybrid_flat = None
        if self.dev_hybrid:
            # a batch left whole by the dispatch is cut to its true count here
            fetched = [
                np.asarray(d)[:n] for d, n in zip(self.dev_hybrid, self.padded_totals[1])
            ]
            hybrid_flat = fetched[0] if len(fetched) == 1 else np.concatenate(fetched)
        if keep_dict_indices and self.dictionary is not None:
            kinds = {k for _, _, _, k, _ in self.page_infos if k != "empty"}
            if kinds and kinds <= {"dict", "indices"}:
                # dictionary-preserving delivery: the (device- or host-)
                # decoded indices pass through unmaterialized
                parts = []
                hpos = 0
                all_def, all_rep = [], []
                total = 0
                for n, dfl, rep, kind, payload in self.page_infos:
                    total += n
                    if dfl is not None:
                        all_def.append(dfl)
                    if rep is not None:
                        all_rep.append(rep)
                    if kind == "dict":
                        parts.append(hybrid_flat[hpos : hpos + payload])
                        hpos += payload
                    elif kind == "indices":
                        parts.append(np.asarray(payload))
                if total != self.expected:
                    raise ChunkError(
                        f"chunk: pages hold {total} values, "
                        f"metadata says {self.expected}"
                    )
                idx = (
                    np.concatenate(parts)
                    if len(parts) != 1
                    else parts[0]
                ) if parts else np.empty(0, np.int32)
                if self.native_def is not None or self.native_rep is not None:
                    dl, rl = self.native_def, self.native_rep
                else:
                    dl = np.concatenate(all_def) if all_def else None
                    rl = np.concatenate(all_rep) if all_rep else None
                return ChunkData(
                    column=column,
                    num_values=total,
                    values=None,
                    def_levels=dl,
                    rep_levels=rl,
                    dictionary=self.dictionary,
                    indices=idx.astype(np.int32, copy=False),
                )
        delta_flat = None
        if self.dev_delta:
            fetched = [np.asarray(d) for d in self.dev_delta]
            delta_flat = fetched[0] if len(fetched) == 1 else np.concatenate(fetched)
        bss_pages = None
        if self.dev_bss or self.bss_host:
            # fetch the device transposes (dispatched), or transpose the
            # staged streams host-side (plan finalized without dispatch)
            from .device_ops import bss_transpose_device

            np_dt = _NUMERIC_DTYPE.get(self.column.type)
            if self.dev_bss:
                bss_pages = [
                    np.asarray(bss_transpose_device(d, nv)).view(np_dt)
                    for d, nv in self.dev_bss
                ]
            else:
                bss_pages = [
                    np.ascontiguousarray(s[:, :nv].T).view(np_dt).reshape(nv)
                    for s, nv in self.bss_host
                ]
            bss_pages = list(reversed(bss_pages))  # pop from the front
        pages_values = []
        all_def: list[np.ndarray] = []
        all_rep: list[np.ndarray] = []
        hpos = 0
        dpos = 0
        num_values_total = 0
        for n, dfl, rep, kind, payload in self.page_infos:
            num_values_total += n
            if dfl is not None:
                all_def.append(dfl)
            if rep is not None:
                all_rep.append(rep)
            if kind == "dict":
                take = payload
                idx = hybrid_flat[hpos : hpos + take]
                hpos += take
                pages_values.append(_materialize(self.dictionary, idx))
            elif kind == "indices":
                pages_values.append(
                    _materialize(self.dictionary, payload)
                )
            elif kind == "delta":
                if payload:
                    vals = delta_flat[dpos : dpos + payload]
                    dpos += payload
                    pages_values.append(vals)
            elif kind == "bss":
                pages_values.append(bss_pages.pop())
            elif kind == "values":
                pages_values.append(payload)
            elif kind == "empty":
                pass
        if num_values_total != self.expected:
            raise ChunkError(
                f"chunk: pages hold {num_values_total} values, "
                f"metadata says {self.expected}"
            )
        values = _concat_values(pages_values, column)
        if self.native_def is not None or self.native_rep is not None:
            def_levels, rep_levels = self.native_def, self.native_rep
        else:
            def_levels = np.concatenate(all_def) if all_def else None
            rep_levels = np.concatenate(all_rep) if all_rep else None
        return ChunkData(
            column=column,
            num_values=num_values_total,
            values=values,
            def_levels=def_levels,
            rep_levels=rep_levels,
            dictionary=self.dictionary,
        )

    # -- decode-to-device ------------------------------------------------------

    def device_column(self) -> DeviceColumn:
        """Deliver the chunk's decoded values in HBM (no device->host fetch of
        the value data). Falls back to host decode + upload for shapes the
        device path doesn't cover (byte-array delta pages, booleans, ...).
        A DOUBLE column arrives in the form the plan was prepared for
        (doubles=, DOUBLE_FORMS); with none asked it is float64, and raises
        DeviceDoubleError on a device that cannot hold float64 bit-exactly
        (any TPU)."""
        column = self.column
        if column.type == Type.DOUBLE:
            if self.doubles is None:
                check_double_delivery([column.path_str])
            else:
                _trace.bump(f"device_double_chunks_{self.doubles}")
        kinds = {k for _, _, _, k, _ in self.page_infos if k != "empty"}
        if self.native_def is not None or self.native_rep is not None:
            def_levels, rep_levels = self.native_def, self.native_rep
        else:
            all_def = [d for _, d, _, _, _ in self.page_infos if d is not None]
            all_rep = [r for _, _, r, _, _ in self.page_infos if r is not None]
            def_levels = np.concatenate(all_def) if all_def else None
            rep_levels = np.concatenate(all_rep) if all_rep else None
        n_total = sum(n for n, *_ in self.page_infos)
        out = DeviceColumn(
            num_values=n_total, def_levels=def_levels, rep_levels=rep_levels,
            double_form=self.doubles,
        )

        if (
            kinds <= {"dict", "empty"}
            and self.dev_hybrid
            and (
                isinstance(self.dictionary, ByteArrayData)
                # dict_dev is only uploaded for 1-D numeric dictionaries;
                # 2-D FLBA dictionaries fall through to host decode + upload
                or self.dict_dev is not None
            )
        ):
            idx = self._dev_indices()
            if isinstance(self.dictionary, ByteArrayData):
                out.indices = idx
                out.dictionary = self.dictionary
                out.dict_data = jnp.asarray(
                    np.frombuffer(self.dictionary.data, dtype=np.uint8)
                )
                out.dict_offsets = jnp.asarray(self.dictionary.offsets)
            else:
                out.values = self._typed(self._lookup(idx))
            return out

        if kinds <= {"delta", "empty"} and self.dev_delta:
            out.values = (
                self.dev_delta[0]
                if len(self.dev_delta) == 1
                else jnp.concatenate(self.dev_delta)
            )
            return out

        if kinds <= {"bss", "empty"} and self.dev_bss:
            from .device_ops import bss_transpose_device

            parts = [bss_transpose_device(d, nv) for d, nv in self.dev_bss]
            u = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            if column.type == Type.INT32:
                u = jax.lax.bitcast_convert_type(u, jnp.int32)
            out.values = _device_bitcast(u, column)
            return out

        if "values" in kinds and kinds <= {"values", "empty"} and column.type in _NUMERIC_DTYPE:
            if self.dev_plain is not None:
                out.values = self._typed(self.dev_plain)
            else:
                parts = [p for _, _, _, k, p in self.page_infos if k == "values"]
                host = parts[0] if len(parts) == 1 else np.concatenate(parts)
                out.values = self._typed(self._upload(host))
            return out

        # Mixed dict+PLAIN numeric chunk (pyarrow's default 1MB dictionary
        # ceiling makes this the common large-dictionary case): dict pages
        # keep their device expansion, PLAIN pages ride the raw upload, and
        # one kernel merges both by segments — no value ever round-trips to
        # the host. Every array arrives at a bucket length: dispatch_device
        # decided that this chunk merges (mixed_numeric) and padded for it,
        # so nothing here is cut or padded to the chunk's own counts.
        if self.mixed_numeric:
            from .device_ops import merge_mixed_numeric_device

            seg_kind, seg_row_start, seg_src, n_rows = _mixed_segments(
                self.page_infos,
                self.padded_totals[1],
                [int(b.shape[0]) for b in self.dev_hybrid],
            )
            _metrics.event("mixed_chunks_by_segments")
            _trace.count("mixed_chunks_by_segments")
            plain_u = self.dev_plain  # bit patterns (dispatch_device)
            if self.doubles == "float32":
                # each side narrows, then they merge at 32 bits: the
                # dictionary on the host (dict_upload), the PLAIN pages here
                plain_u = self._narrow(plain_u)
            merged = merge_mixed_numeric_device(
                self.dev_hybrid[0]
                if len(self.dev_hybrid) == 1
                else jnp.concatenate(self.dev_hybrid),
                self.dict_dev,
                plain_u,
                jnp.asarray(seg_kind),
                jnp.asarray(seg_row_start),
                jnp.asarray(seg_src),
                _bucket(max(n_rows, 1)),
            )[:n_rows]
            out.values = self._typed(merged)
            out.mixed = True
            return out

        # Mixed dict+PLAIN byte-array chunk (config-3 shape under pyarrow's
        # default dictionary ceiling): dict pages ship indices + the (small)
        # dictionary, PLAIN pages ship their raw bytes, and one ragged device
        # gather materializes the merged (data, offsets) column in HBM.
        if (
            kinds <= {"dict", "values", "empty"}
            and "dict" in kinds
            and self.dev_hybrid
            and isinstance(self.dictionary, ByteArrayData)
            and self._merge_ragged_bytes(out)
        ):
            return out

        # Mixed, unsupported, or fully empty shapes: host decode, then upload.
        data = self.finalize()
        if isinstance(data.values, ByteArrayData):
            out.data = jnp.asarray(np.frombuffer(data.values.data, dtype=np.uint8))
            out.offsets = jnp.asarray(data.values.offsets)
        else:
            out.values = self._typed(self._upload(np.asarray(data.values)))
        return out

    def device_values_padded(self) -> tuple:
        """The padded delivery (prepare_chunk_plan list_lengths=True): (values,
        count) with `values` the chunk's non-null values in HBM at a
        power-of-two length and only the first `count` of them real. What
        device_column does to hand over an exact-length array — the slice
        after the decode kernel, and every program downstream of it — is a
        compiled program for every count, and a chunk's count is data; here
        the decode kernel's own padded output goes on whole (index widening
        and dictionary gather included, at the bucket), and the consumer
        takes the count as a runtime value. A chunk shape with more than one
        device array (several batches, mixed dictionary and PLAIN pages, host
        decoded pages) is delivered exactly first and padded after: correct,
        and a program a count — so it is counted, as the event
        padded_delivery_exact_chunks, for a cell or a test to pin at 0."""
        kinds = {k for _, _, _, k, _ in self.page_infos if k != "empty"}
        count = self.list_elements
        if kinds == {"dict"} and len(self.dev_hybrid) == 1 and self.dict_dev is not None:
            return self._typed(self._lookup(self._dev_indices())), count
        if kinds == {"delta"} and len(self.dev_delta) == 1:
            return self.dev_delta[0], count
        if kinds == {"values"} and self.dev_plain is not None:
            return self._typed(self.dev_plain), count
        _metrics.event("padded_delivery_exact_chunks")
        _trace.count("padded_delivery_exact_chunks")
        if not self.mixed_numeric:  # whose merge takes its arrays whole
            plain, hybrid, delta = self.padded_totals
            if self.dev_plain is not None:
                self.dev_plain = self.dev_plain[:plain]
            self.dev_hybrid = [d[:n] for d, n in zip(self.dev_hybrid, hybrid)]
            self.dev_delta = [d[:n] for d, n in zip(self.dev_delta, delta)]
        return _pad_device(self.device_column().values), count

    # -- the delivered form of a value array -----------------------------------
    #
    # Unsigned bit patterns become typed values in exactly two places,
    # _device_bitcast and _upload_typed. Under doubles= neither runs for a
    # DOUBLE: the patterns upload as uint64 (_upload), stay unsigned through
    # gather and merge, and _typed hands them over as they are ("bits") or
    # narrowed ("float32") — no float64 value exists in any program.

    def _upload(self, host: np.ndarray) -> jnp.ndarray:
        if self.doubles is not None and host.dtype == np.float64:
            return jnp.asarray(host.view(np.uint64))
        return _upload_typed(host)

    def _narrow(self, bits: jnp.ndarray) -> jnp.ndarray:
        """uint64 patterns of this chunk's PLAIN / BYTE_STREAM_SPLIT pages ->
        float32 patterns, on the device (bucket-padded: one program a
        bucket; an array that came padded — a mixed chunk's PLAIN pool, the
        padded delivery — goes in as it is)."""
        pages = sum(1 for _, _, _, k, _ in self.page_infos if k == "values")
        _metrics.event("double_pages_narrowed_device", pages)
        _trace.count("double_pages_narrowed_device", pages)
        n = int(bits.shape[0])
        if n == _bucket(n):
            return double_narrow_device(bits)
        return double_narrow_device(_pad_device(bits))[:n]

    def _typed(self, vals: jnp.ndarray) -> jnp.ndarray:
        if self.doubles is None:
            # already typed (a PLAIN upload) or bit patterns (gather, merge)
            return vals if vals.dtype.kind == "f" else _device_bitcast(vals, self.column)
        if self.doubles == "bits":
            return vals
        if vals.dtype == jnp.uint64:
            vals = self._narrow(vals)
        return jax.lax.bitcast_convert_type(vals, jnp.float32)

    def _lookup(self, idx: jnp.ndarray) -> jnp.ndarray:
        """The chunk's numeric dictionary looked up at `idx`, counted by the
        formulation dict_gather_device takes for this table (its own static
        rule, dict_lookup_tier): dict_lookup_dense_chunks — compared with and
        contracted, no gather — or dict_lookup_gather_chunks."""
        name = f"dict_lookup_{dict_lookup_tier(self.dict_dev.shape[0], self.dict_dev.dtype)}_chunks"
        _metrics.event(name)
        _trace.count(name)
        return dict_gather_device(self.dict_dev, idx)

    def _dev_indices(self) -> jnp.ndarray:
        """All dispatched dict-index batches as one int32 device array."""
        return (
            self.dev_hybrid[0]
            if len(self.dev_hybrid) == 1
            else jnp.concatenate(self.dev_hybrid)
        ).astype(jnp.int32)

    def _merge_ragged_bytes(self, out: DeviceColumn) -> bool:
        """Device merge of a mixed dict/PLAIN byte-array chunk. Returns False
        (leaving `out` untouched) when the shape is unsuitable — a skewed
        dictionary whose max-length padding bound would blow HBM, or PLAIN
        pages that did not decode to ByteArrayData.

        Only raw page bytes, int32 plain-offset arrays and tiny per-page
        tables cross the link; merge_mixed_bytes_device derives everything
        else on device (the host baseline ships the fully-expanded column
        plus int64 offsets — roughly 40%% more bytes for string data)."""
        from .device_ops import merge_mixed_bytes_device

        d = self.dictionary
        dict_rows = plain_rows = plain_bytes = 0
        for _n, _d, _r, kind, payload in self.page_infos:
            if kind == "dict":
                dict_rows += payload
            elif kind == "values":
                if not isinstance(payload, ByteArrayData):
                    return False
                plain_rows += len(payload.offsets) - 1
                plain_bytes += len(payload.data)
        bound, ok = _skewed_dict_bound(d, dict_rows, plain_bytes)
        n_rows = dict_rows + plain_rows
        if n_rows == 0 or not ok:
            return False
        if len(d.data) + plain_bytes >= (1 << 31):
            return False  # int32 plain offsets would overflow
        # -- compact host tables ----------------------------------------------
        page_kind, prs, aux_np, _nr = _page_merge_tables(
            self.page_infos, lambda p: (len(p.offsets), len(p.offsets) - 1)
        )
        P_pad = len(page_kind)
        pools = [np.frombuffer(d.data, dtype=np.uint8)]
        base = len(d.data)
        po_parts: list[np.ndarray] = []
        src_base: list[int] = []
        for _n, _dl, _rl, kind, payload in self.page_infos:
            if kind == "dict":
                src_base.append(0)
            elif kind == "values":
                src_base.append(base)
                po_parts.append(payload.offsets.astype(np.int32))
                pools.append(np.frombuffer(payload.data, dtype=np.uint8))
                base += len(payload.data)
        srcb = np.zeros(P_pad, dtype=np.int64)
        srcb[: len(src_base)] = src_base
        po32 = np.concatenate(po_parts) if po_parts else np.zeros(2, dtype=np.int32)
        E_pad = _bucket(len(po32), 1024)
        po32p = np.zeros(E_pad, dtype=np.int32)
        po32p[: len(po32)] = po32
        pool = pools[0] if len(pools) == 1 else np.concatenate(pools)
        S_pad = _bucket(max(len(pool), 1), 1024)
        poolp = np.empty(S_pad, dtype=np.uint8)  # tail garbage is masked out
        poolp[: len(pool)] = pool
        doff_pad = _bucket(len(d.offsets), 1024)
        doffp = np.empty(doff_pad, dtype=np.int64)
        doffp[: len(d.offsets)] = d.offsets
        doffp[len(d.offsets) :] = d.offsets[-1] if len(d.offsets) else 0
        # -- device inputs -----------------------------------------------------
        idx_all = _pad_device(self._dev_indices())
        rows_pad = _bucket(n_rows, 1024)
        data, off = merge_mixed_bytes_device(
            idx_all,
            jnp.asarray(doffp),
            jnp.asarray(poolp),
            jnp.asarray(po32p),
            jnp.asarray(page_kind),
            jnp.asarray(prs),
            jnp.asarray(aux_np),
            jnp.asarray(srcb),
            jnp.int32(n_rows),
            rows_pad,
            _bucket(max(bound, 1)),
        )
        out.data = data
        out.offsets = off[: n_rows + 1]
        out.dictionary = d
        return True

# -- the chunk decoder ---------------------------------------------------------


def plan_chunk_tpu(
    f,
    chunk,
    column: Column,
    validate_crc: bool = False,
    alloc=None,
    stats: TpuDecodeStats | None = None,
) -> _ChunkPlan:
    """Phase 1: host prescan + async device dispatch for one chunk.

    Returns a _ChunkPlan whose device arrays are in flight; call .finalize()
    for a host ChunkData (byte-identical to core.chunk.read_chunk) or
    .device_column() to keep the decoded values in HBM.
    """
    return prepare_chunk_plan(
        f, chunk, column, validate_crc=validate_crc, alloc=alloc, stats=stats
    ).dispatch_device()


# Page-table column indices of the native whole-chunk walk (layout defined in
# native/parquet_tpu_native.cc ptq_chunk_prepare).
_PC_KIND, _PC_N, _PC_NONNULL, _PC_ENC, _PC_ROUTE = 0, 1, 2, 3, 4
_PC_VOFF, _PC_VLEN, _PC_LVLBASE = 5, 6, 7
_PC_RUNS, _PC_RUNE, _PC_PACKS, _PC_PACKE = 8, 9, 10, 11
_PC_MINIS, _PC_MINIE, _PC_DSTART, _PC_DCONS = 12, 13, 14, 15
_PC_EXTRA, _PC_DFIRST = 16, 17
_PC_COLS = 18


def _native_prepare(f, chunk, column, validate_crc, alloc, stats, doubles=None, padded=False):
    """Whole-chunk native prepare: ONE GIL-free C call walks every page
    (header parse, CRC verify when validate_crc, decompress, level decode,
    value prescan) and returns packed tables; batch assembly is then a
    handful of vectorized NumPy ops instead of a per-page Python loop (the
    dominant host cost — reference page walk: chunk_reader.go:182-263).

    Returns (plan, fault): a ready _ChunkPlan and None, or None and an
    optional PrepareFault. fault is set when the native walk RAN and aborted
    (corrupt/unsupported/capacity, with stage + page + byte offset); it is
    None when the walk was never attempted (memory ceiling, non-builtin
    codec, library absent). Either way the caller falls back to the staged
    per-page Python walk — the error-semantics reference — which raises the
    exact typed error if the chunk is genuinely corrupt (the fused -> staged
    -> raise fallback ladder; prepare_fallback_recovered counts chunks the
    staged walk salvaged after a native abort). Under an active
    decode_trace the outcome is pinned by the prepare_fused_engaged /
    prepare_fused_declined counters and the walk's internal stage split
    lands in prepare.* stages."""
    plan, fault = _native_prepare_impl(
        f, chunk, column, validate_crc, alloc, stats, doubles, padded
    )
    if plan is None:
        _trace.bump("prepare_fused_declined")
        if fault is not None:
            _trace.bump(f"prepare_fused_fault_{fault.stage}")
    else:
        _trace.bump("prepare_fused_engaged")
    return plan, fault


def _native_prepare_impl(
    f, chunk, column, validate_crc, alloc, stats, doubles=None, padded=False
):
    if alloc is not None:
        # a memory ceiling needs the per-page accounting only the staged
        # walk performs (validate_crc, by contrast, is fused natively)
        return None, None
    from ..utils.native import PrepareFault, get_native

    lib = get_native()
    if lib is None or not lib.has_chunk_prepare:
        return None, None
    md = chunk.meta_data
    codec = int(md.codec or 0)
    from ..core.compress import is_builtin_codec

    if codec not in (0, 1, 2, 5, 7) or not is_builtin_codec(codec):
        return None, None
    if codec == 1 and not lib.has_snappy:
        return None, None
    if codec in (5, 7) and not lib.has_lz4:
        return None, None
    from ..core.chunk import chunk_byte_range

    try:
        offset, total = chunk_byte_range(chunk)
    except Exception:
        return None, None
    f.seek(offset)
    buf = f.read(total)
    if len(buf) != total:
        return None, None  # truncated: Python walk raises the exact error
    ptype = column.type
    np_dt = _NUMERIC_DTYPE.get(ptype)
    type_size = np.dtype(np_dt).itemsize if np_dt is not None else 0
    delta_nbits = 32 if ptype == Type.INT32 else (64 if ptype == Type.INT64 else 0)
    expected = int(md.num_values or 0)
    if expected < 0:
        return None, None
    import time as _time

    t_walk = _time.perf_counter()
    res = lib.chunk_prepare(
        buf,
        codec,
        column.max_def,
        column.max_rep,
        type_size,
        delta_nbits,
        expected,
        int(md.total_uncompressed_size or 0),
        collect_stages=_trace.active(),
        validate_crc=validate_crc,
    )
    if isinstance(res, PrepareFault):
        return None, res
    t_walk = _time.perf_counter() - t_walk
    stage_ns = res.get("stage_ns")
    if stage_ns is not None:
        # one batch: the sub-stage spans lay back-to-back ending now, so
        # they nest inside the enclosing chunk.prepare span
        _trace.add_seconds_batch(
            [
                (name, int(stage_ns[slot]) / 1e9)
                for slot, name in enumerate(
                    (
                        "prepare.decompress",
                        "prepare.levels",
                        "prepare.prescan",
                        "prepare.copy",
                        "prepare.crc",
                    )
                )
                if stage_ns[slot]
            ]
        )
    try:
        plan = _plan_from_tables(
            column, expected, res, stats, np_dt, delta_nbits, doubles, padded
        )
    except (PageError, ChunkError):
        raise
    except Exception:
        return None, None  # unexpected table shape: let the Python walk decide
    # Always-on process counters, recorded ONLY once the plan is committed —
    # a chunk that falls back to the staged walk is counted by that walk
    # instead (never both; _plan_from_tables decodes the dict page with
    # count_metrics=False for the same reason). The fused walk bypasses
    # decompress_block's byte choke point and the per-page value decoders,
    # so it reports its own totals. Semantics vs the staged lane, by
    # necessity approximate: io_bytes covers the whole chunk window /
    # metadata uncompressed size (page headers included, where the staged
    # lane counts payload-only), and page_bytes uses each page's
    # value-stream length (levels excluded).
    _metrics.observe("chunk_decode_seconds", t_walk)
    _metrics.io_bytes(len(buf), int(md.total_uncompressed_size or 0), codec)
    # mirror decompress_block's per-trace decoded-byte account (the fused
    # walk bypasses that choke point), so cost attribution stays exact on
    # the native lane too
    _trace.add_bytes("decode.bytes", int(md.total_uncompressed_size or 0))
    pages_arr = res["pages"]
    if len(pages_arr):
        for e in np.unique(pages_arr[:, _PC_ENC]):
            sel = pages_arr[pages_arr[:, _PC_ENC] == e]
            _metrics.page_decoded(
                _metrics.encoding_name(int(e)),
                n=len(sel),
                nbytes=int(sel[:, _PC_VLEN].sum()),
            )
    return plan, None


def _plan_from_tables(
    column, expected, res, stats, np_dt, delta_nbits, doubles=None, padded=False
):
    plan = _ChunkPlan(column, expected, doubles, padded)
    plan.stats = stats
    pages = res["pages"].tolist()
    values_buf = res["values"]
    def_all = res["def"]
    rep_all = res["rep"]
    n_data = sum(1 for P in pages if P[_PC_KIND] == 0)
    if stats is not None:
        stats.pages += n_data
    data_pages = []
    for P in pages:
        if P[_PC_KIND] == 1:  # dictionary page
            from ..meta.parquet_types import DictionaryPageHeader, PageHeader

            header = PageHeader(
                type=int(PageType.DICTIONARY_PAGE),
                dictionary_page_header=DictionaryPageHeader(
                    num_values=P[_PC_N], encoding=P[_PC_ENC]
                ),
            )
            block = memoryview(values_buf)[P[_PC_VOFF] : P[_PC_VOFF] + P[_PC_VLEN]]
            # count_metrics=False: the native lane's counters commit only
            # once the whole plan succeeds (see _native_prepare_impl) — a
            # later fallback to the staged walk must not leave this page
            # already counted
            plan.dictionary = decode_dict_page(
                header, block, column, count_metrics=False
            )
        elif P[_PC_KIND] == 0:
            data_pages.append(P)
    if column.max_def > 0 and data_pages:
        plan.native_def = def_all
    if column.max_rep > 0 and data_pages:
        plan.native_rep = rep_all

    def _levels(P):
        base, n = P[_PC_LVLBASE], P[_PC_N]
        dfl = def_all[base : base + n] if column.max_def > 0 else None
        rep = rep_all[base : base + n] if column.max_rep > 0 else None
        return dfl, rep

    routes = {P[_PC_ROUTE] for P in data_pages if P[_PC_ROUTE] != 4}

    if routes == {3} or not routes:  # PLAIN numeric (and/or empty pages)
        first = None
        nbytes = 0
        for P in data_pages:
            if P[_PC_ROUTE] == 4:
                continue
            if first is None:
                first = P[_PC_VOFF]
            nbytes += P[_PC_VLEN]
        whole = None
        if first is not None and np_dt is not None:
            # routes wrote values_out sequentially: one zero-copy view is the
            # whole chunk's upload buffer (no per-page concatenation)
            whole = np.frombuffer(
                values_buf, dtype=np_dt, count=nbytes // np.dtype(np_dt).itemsize,
                offset=first,
            )
        repacked = (
            whole is not None
            and delta_nbits != 0
            and _repack_plain_as_delta(plan, whole, delta_nbits)
        )
        for P in data_pages:
            dfl, rep = _levels(P)
            if P[_PC_ROUTE] == 4:
                plan.page_infos.append((P[_PC_N], dfl, rep, "empty", None))
            elif repacked:
                plan.page_infos.append(
                    (P[_PC_N], dfl, rep, "delta", P[_PC_NONNULL])
                )
            else:
                vals = np.frombuffer(
                    values_buf, dtype=np_dt, count=P[_PC_NONNULL],
                    offset=P[_PC_VOFF],
                )
                plan.page_infos.append((P[_PC_N], dfl, rep, "values", vals))
        if not repacked:
            plan.plain_host = whole
        # PLAIN routes never touch the packed/delta staging buffers, and a
        # repacked chunk's upload is a FRESH delta stream — whatever leaked
        # no view into the plan goes back to the thread pool so the next
        # chunk skips the first-touch page-fault storm on multi-MB buffers.
        # A decoded dictionary page (dict-write fallback to PLAIN pages)
        # can alias values_buf zero-copy, so 'values' is only released when
        # no dictionary rides the plan.
        from ..utils.native import get_native

        _lib = get_native()
        if _lib is not None and "_bases" in res:
            whole = None
            names = (
                ("values", "packed", "delta")
                if repacked and plan.dictionary is None
                else ("packed", "delta")
            )
            _lib.release_buffers(res, names)
        return plan

    if routes == {5} and np_dt is not None:
        # BYTE_STREAM_SPLIT 4-byte pages shipped RAW: each page's streams
        # stage into a (4, bucket) array (4 contiguous memcpys — the host
        # never strides byte-by-byte) and the DEVICE does the transpose
        # (kernels/device_ops.bss_transpose_device)
        for P in data_pages:
            dfl, rep = _levels(P)
            if P[_PC_ROUTE] == 4:
                plan.page_infos.append((P[_PC_N], dfl, rep, "empty", None))
                continue
            nv = P[_PC_NONNULL]
            raw = np.frombuffer(
                values_buf, dtype=np.uint8, count=P[_PC_VLEN], offset=P[_PC_VOFF]
            )
            staged = np.zeros((4, _bucket(max(nv, 1))), dtype=np.uint8)
            staged[:, :nv] = raw.reshape(4, nv)
            plan.bss_host.append((staged, nv))
            plan.page_infos.append((P[_PC_N], dfl, rep, "bss", nv))
        # staging copied out of values_buf: the bases can recycle (same
        # dictionary-aliasing caveat as the PLAIN branch)
        from ..utils.native import get_native

        _lib = get_native()
        if _lib is not None and "_bases" in res:
            names = (
                ("values", "packed", "delta")
                if plan.dictionary is None
                else ("packed", "delta")
            )
            _lib.release_buffers(res, names)
        return plan

    if routes == {1} or (
        routes == {1, 3}
        and np_dt is not None
        and (column.type != Type.DOUBLE or plan.doubles is not None)
        # mixed chunks of a DOUBLE delivered as float64 can't merge on
        # device (no f64<->u64 bitcast in the TPU x64 emulation); freezing
        # their batches would only upload indices that finalize() fetches
        # straight back — demote instead. Under doubles= the merge stays in
        # the unsigned domain and the chunk keeps its device batches
    ):
        # Dictionary-encoded chunk, possibly with a mid-chunk fall-back to
        # PLAIN pages (pyarrow's 1MB dictionary ceiling): dict pages build
        # device run batches, PLAIN pages ride the contiguous raw upload,
        # and device_column merges in page order.
        frozen = _freeze_hybrid_from_tables(
            data_pages, res, len(plan.dictionary) if plan.dictionary is not None else 0
        )
        if frozen is not None:
            plan.frozen_hybrid = frozen
            first = None
            nbytes = 0
            for P in data_pages:
                dfl, rep = _levels(P)
                if P[_PC_ROUTE] == 4:
                    plan.page_infos.append((P[_PC_N], dfl, rep, "empty", None))
                elif P[_PC_ROUTE] == 3:
                    vals = np.frombuffer(
                        values_buf, dtype=np_dt, count=P[_PC_NONNULL],
                        offset=P[_PC_VOFF],
                    )
                    plan.page_infos.append((P[_PC_N], dfl, rep, "values", vals))
                    if first is None:
                        first = P[_PC_VOFF]
                    nbytes += P[_PC_VLEN]
                else:
                    plan.page_infos.append(
                        (P[_PC_N], dfl, rep, "dict", P[_PC_NONNULL])
                    )
            if first is not None:
                plan.plain_host = np.frombuffer(
                    values_buf, dtype=np_dt,
                    count=nbytes // np.dtype(np_dt).itemsize, offset=first,
                )
            return plan
        # oversized page: fall through to the demote path below

    if routes == {2} and all(
        P[_PC_DCONS] * 8 <= _BATCH_BITS_CAP
        for P in data_pages
        if P[_PC_ROUTE] == 2
    ):  # delta-bp chunk (an oversized page demotes the whole chunk, as below)
        frozen = _freeze_delta_from_tables(data_pages, res, delta_nbits)
        if frozen is not None:
            plan.frozen_delta = frozen
            for P in data_pages:
                dfl, rep = _levels(P)
                if P[_PC_ROUTE] == 4:
                    plan.page_infos.append((P[_PC_N], dfl, rep, "empty", None))
                else:
                    plan.page_infos.append(
                        (P[_PC_N], dfl, rep, "delta", P[_PC_EXTRA])
                    )
            return plan

    if (
        column.type == Type.BYTE_ARRAY
        and routes <= {0, 1}
        and 1 in routes
        and all(
            P[_PC_ENC] == int(Encoding.PLAIN)
            for P in data_pages
            if P[_PC_ROUTE] == 0
        )
        and plan.dictionary is not None
        and _skewed_dict_bound(
            plan.dictionary,
            sum(P[_PC_NONNULL] for P in data_pages if P[_PC_ROUTE] == 1),
            # PLAIN stream length bounds the page's data bytes; close enough
            # for the skew gate (the merge re-checks exactly)
            sum(P[_PC_VLEN] for P in data_pages if P[_PC_ROUTE] == 0),
        )[1]
    ):
        # Dict pages with a mid-chunk PLAIN byte-array fallback: dict index
        # batches stay device-bound; PLAIN pages host-scan their offsets
        # (native byte_array_gather) and device_column's ragged merge joins
        # both in output-index space.
        frozen = _freeze_hybrid_from_tables(
            data_pages, res, len(plan.dictionary) if plan.dictionary is not None else 0
        )
        if frozen is not None:
            from ..core.page import _decode_values

            plan.frozen_hybrid = frozen
            dict_size = (
                len(plan.dictionary) if plan.dictionary is not None else None
            )
            for P in data_pages:
                dfl, rep = _levels(P)
                if P[_PC_ROUTE] == 4:
                    plan.page_infos.append((P[_PC_N], dfl, rep, "empty", None))
                elif P[_PC_ROUTE] == 1:
                    plan.page_infos.append(
                        (P[_PC_N], dfl, rep, "dict", P[_PC_NONNULL])
                    )
                else:
                    stream = memoryview(values_buf)[
                        P[_PC_VOFF] : P[_PC_VOFF] + P[_PC_VLEN]
                    ]
                    values, _idx = _decode_values(
                        stream, P[_PC_NONNULL], P[_PC_ENC], column, dict_size
                    )
                    plan.page_infos.append((P[_PC_N], dfl, rep, "values", values))
                    _count_host_page(plan)
            return plan

    # Mixed-route chunk (or an oversized device page): host-decode in place,
    # same policy as _commit_routes — device decode only pays when the whole
    # chunk stays on device.
    from ..core.page import _decode_values

    dict_size = len(plan.dictionary) if plan.dictionary is not None else None
    for P in data_pages:
        dfl, rep = _levels(P)
        route = P[_PC_ROUTE]
        if route == 4:
            plan.page_infos.append((P[_PC_N], dfl, rep, "empty", None))
            continue
        if route == 1:
            idx = _expand_dict_from_tables(P, res)
            plan.page_infos.append((P[_PC_N], dfl, rep, "indices", idx))
            _count_host_page(plan)
        elif route == 2:
            from ..ops.delta import decode_delta

            stream = res["delta_stream"][
                P[_PC_DSTART] : P[_PC_DSTART] + P[_PC_DCONS]
            ]
            vals, _ = decode_delta(
                memoryview(stream), delta_nbits, max_total=P[_PC_NONNULL]
            )
            plan.page_infos.append(
                (P[_PC_N], dfl, rep, "values", vals[: P[_PC_NONNULL]])
            )
            _count_host_page(plan)
        elif route == 3:
            vals = np.frombuffer(
                values_buf, dtype=np_dt, count=P[_PC_NONNULL], offset=P[_PC_VOFF]
            )
            plan.page_infos.append((P[_PC_N], dfl, rep, "values", vals))
        elif route == 5:
            # raw BSS page in a mixed chunk: de-interleave host-side
            nv = P[_PC_NONNULL]
            raw = np.frombuffer(
                values_buf, dtype=np.uint8, count=P[_PC_VLEN], offset=P[_PC_VOFF]
            )
            vals = (
                np.ascontiguousarray(raw.reshape(4, nv).T)
                .view(np_dt)
                .reshape(nv)
            )
            plan.page_infos.append((P[_PC_N], dfl, rep, "values", vals))
        else:  # route 0: host decoder on the raw stream
            stream = memoryview(values_buf)[P[_PC_VOFF] : P[_PC_VOFF] + P[_PC_VLEN]]
            values, indices = _decode_values(
                stream, P[_PC_NONNULL], P[_PC_ENC], column, dict_size
            )
            if indices is not None:
                plan.page_infos.append((P[_PC_N], dfl, rep, "indices", indices))
            else:
                plan.page_infos.append((P[_PC_N], dfl, rep, "values", values))
            _count_host_page(plan)
    kinds_after = {k for _, _, _, k, _ in plan.page_infos}
    kinds_after.discard("empty")
    if kinds_after == {"values"} and column.type in _NUMERIC_DTYPE:
        parts = [p for _, _, _, k, p in plan.page_infos if k == "values"]
        if parts:
            plan.plain_host = parts[0] if len(parts) == 1 else np.concatenate(parts)
    return plan


def _repack_pages_to_width(pages: list, res: dict, width: int):
    """The route-1 `pages` of one chunk with every page narrower than `width`
    re-packed to it: (pages with their packed ranges rebased, is_rle,
    byteoff, packed) replacing the native walk's tables. A page's packed
    region is whole groups of 8 values, w bytes each, so it widens as one
    unit and a run's byte offset scales by width / w. A width-0 page has no
    payload to widen: its bit-packed runs become RLE runs of index 0. The
    bytes move in ONE native call a chunk (GIL-free; a call a page would
    wait for the GIL fifty times on a busy pool), the tables in NumPy."""
    import time as _time

    from ..utils.native import get_native

    t0 = _time.perf_counter()
    rs, re, ps, pe, w = (
        np.array([P[k] for P in pages], dtype=np.int64)
        for k in (_PC_RUNS, _PC_RUNE, _PC_PACKS, _PC_PACKE, _PC_EXTRA)
    )
    narrow = w != width
    size = np.where(narrow, (pe - ps) // np.maximum(w, 1) * width, pe - ps)
    size[w == 0] = 0
    new_pe = np.cumsum(size)
    new_ps = new_pe - size
    packed_all = np.ascontiguousarray(res["packed"])
    lib = get_native()
    if lib is not None and lib.has_repack_pages:
        # clocked inside the call, as the walk's other prepare.* clocks are:
        # a clock out here would also count this thread's waits for the GIL
        packed, seconds = lib.repack_pages(
            packed_all, ps, pe, w.astype(np.int32), width, int(new_pe[-1])
        )
    else:  # ops/bitpack.py: the reference
        from ..ops.bitpack import pack_bits, unpack_bits

        packed = np.concatenate([
            packed_all[a:b] if wp == width else np.frombuffer(
                pack_bits(unpack_bits(packed_all[a:b], (b - a) // wp * 8, wp), width),
                dtype=np.uint8,
            )
            for a, b, wp in zip(ps.tolist(), pe.tolist(), w.tolist()) if wp
        ] or [packed_all[:0]])
        seconds = _time.perf_counter() - t0
    # the run tables: runs of consecutive route-1 pages are consecutive
    page_of = np.repeat(np.arange(len(pages)), re - rs)
    lo, hi = int(rs[0]), int(re[-1])
    is_rle = res["h_is_rle"].copy()
    byteoff = res["h_byteoff"].copy()
    rel = byteoff[lo:hi] - ps[page_of]
    widened = np.where(narrow[page_of], rel // np.maximum(w[page_of], 1) * width, rel)
    byteoff[lo:hi] = widened + new_ps[page_of]
    is_rle[lo:hi] |= (w[page_of] == 0).astype(is_rle.dtype)
    out = []
    for P, a, b in zip(pages, new_ps.tolist(), new_pe.tolist()):
        Q = list(P)
        Q[_PC_PACKS], Q[_PC_PACKE], Q[_PC_EXTRA] = a, b, width
        out.append(Q)
    # the counter, and the prepare.repack_width sub-clock (seconds + bytes
    # written, back-dated like the native walk's prepare.* clocks so that it
    # nests in chunk.prepare)
    _metrics.event("hybrid_pages_repacked", int(narrow.sum()))
    _trace.count("hybrid_pages_repacked", int(narrow.sum()))
    _trace.add_seconds("prepare.repack_width", seconds, int(size[narrow].sum()))
    return out, is_rle, byteoff, packed


def _freeze_hybrid_from_tables(data_pages, res, n_dict: int = 0) -> list | None:
    """THE freeze of a dictionary chunk's index pages, from the whole-chunk
    run tables of the native walk (the staged walk lays its prescans out the
    same way: _hybrid_tables_of). A chunk ships at ONE index width
    (_index_width): pages written narrower are re-packed to it first, so the
    compiled shapes do not follow where the dictionary crossed a power of
    two. Pages group sequentially under the bit cap, one upload a group: the
    group's runs re-framed position by position at one static width
    (device_ops.pack_hybrid_upload: the hybrid frame; the one pass in which
    the host reads an index stream's payload, and no dictionary value is
    formed); returns None when a single page exceeds the cap (the caller
    demotes the chunk)."""
    cap = _BATCH_BITS_CAP
    pages = [P for P in data_pages if P[_PC_ROUTE] == 1]
    h_is_rle = res["h_is_rle"]
    h_byteoff = res["h_byteoff"]
    packed_all = res["packed"]
    if pages:
        width = _index_width(max(P[_PC_EXTRA] for P in pages), n_dict)
        if any(P[_PC_EXTRA] != width for P in pages):
            pages, h_is_rle, h_byteoff, packed_all = _repack_pages_to_width(
                pages, res, width
            )
    groups: list[list] = []  # [rs, re, ps, pe, bits]
    cur = None
    for P in pages:
        bits = (P[_PC_PACKE] - P[_PC_PACKS]) * 8
        if bits > cap:
            return None
        if cur is None or cur[4] + bits > cap:
            cur = [P[_PC_RUNS], P[_PC_RUNE], P[_PC_PACKS], P[_PC_PACKE], bits]
            groups.append(cur)
        else:
            cur[1] = P[_PC_RUNE]
            cur[3] = P[_PC_PACKE]
            cur[4] += bits
    # payload bits are addressed from the group's first packed byte
    return [
        _count_frame(
            "hybrid",
            *pack_hybrid_upload(
                h_is_rle[rs:re], res["h_counts"][rs:re], res["h_values"][rs:re],
                (h_byteoff[rs:re] - ps) * 8, packed_all[ps:pe], width,
            ),
            _hybrid_wire_bytes(h_is_rle[rs:re], res["h_counts"][rs:re], pe - ps, width),
        )
        for rs, re, ps, pe, _bits in groups
    ]


def _hybrid_wire_bytes(is_rle, counts, packed_bytes: int, width: int) -> int:
    """What the runs a hybrid frame replaced take on the wire at `width`
    bits: the bit-packed groups, an RLE run's value, and a varint header a
    run (a bit-packed run's holds its count of groups, an RLE run's its
    count of values; both shifted by the flag bit)."""
    rle = np.asarray(is_rle) != 0
    counts = np.asarray(counts, dtype=np.int64)
    header = np.where(rle, counts, (counts + 7) // 8) << 1
    header_bytes = len(header) + sum(int((header >> s != 0).sum()) for s in (7, 14, 21, 28, 35))
    return int(packed_bytes + header_bytes + rle.sum() * ((width + 7) // 8))


def _repack_plain_as_delta(plan: _ChunkPlan, whole: np.ndarray, nbits: int) -> bool:
    """Transfer-side re-encoding of a PLAIN int chunk: host deltas+bitpacks
    the decoded values (native DELTA_BINARY_PACKED encoder), the freeze
    re-frames that stream like any delta chunk (device_ops.pack_delta_upload)
    and the device delta kernel reconstructs the values bit-exactly in HBM —
    the link then carries the deltas' width (0, 8 or 16 bits a value for
    ids, timestamps, counters), not the column's. Incompressible chunks are
    detected by a sampled width estimate, by the encoded size and last by the
    frame's own size, and ship raw (returns False, caller keeps the PLAIN
    upload). One whole-chunk stream (not per-page) keeps the device kernel's
    shape buckets stable. Mirrors the byte-minimizing intent of the
    reference's encoded column chunks (chunk_writer.go) but applied to the
    transfer link, not the file."""
    from ..utils.trace import bump

    n = len(whole)
    raw_bytes = n * whole.dtype.itemsize
    if n < 1 << 16 or raw_bytes < 1 << 19:
        return False  # small chunk: upload latency, not bandwidth, dominates
    from ..utils.native import get_native

    lib = get_native()
    if lib is None or not (lib.has_delta_encode and lib.has_prescan_delta):
        return False
    # profitability estimate from 4 contiguous sample windows: max zigzag
    # delta width ~ the packed width the encoder will pick
    est_bits = 0
    win = 1024
    for lo in (0, n // 3, (2 * n) // 3, n - win):
        w = whole[max(lo, 0) : max(lo, 0) + win]
        if len(w) < 2:
            continue
        d = np.diff(w.astype(np.int64, copy=False))
        if len(d):
            zz = int(np.abs(d).max()) << 1
            est_bits = max(est_bits, zz.bit_length())
    if est_bits * n >= 4 * raw_bytes:  # est packed size >= raw/2: not worth it
        bump("repack_declined", raw_bytes)
        return False
    try:
        stream = lib.delta_encode(whole, nbits, 1024, 4)
    except (ValueError, OverflowError):
        bump("repack_declined", raw_bytes)
        return False
    if len(stream) * 8 > _BATCH_BITS_CAP or len(stream) * 2 > raw_bytes:
        # sampled estimate missed: ship raw rather than inflate
        bump("repack_declined", raw_bytes)
        return False
    try:
        widths, byte_starts, out_starts, mins, first, total, consumed = (
            lib.prescan_delta_packed(stream, nbits, n)
        )
    except (ValueError, OverflowError):
        bump("repack_declined", raw_bytes)
        return False
    if int(total) != n:
        bump("repack_declined", raw_bytes)
        return False
    frozen, seconds = pack_delta_upload(
        widths, np.asarray(byte_starts, dtype=np.int64) * 8,
        np.asarray(out_starts, dtype=np.int64) + 1, mins,
        [0], [int(first) & ((1 << 64) - 1)],
        np.frombuffer(stream, dtype=np.uint8)[: int(consumed)], nbits, n,
    )
    if frozen.frame.nbytes * 2 > raw_bytes:
        # what ships is the frame, one quantised width over a power-of-two
        # pad: an outlier delta can make it as large as the raw values
        bump("repack_declined", raw_bytes)
        return False
    plan.frozen_delta = [_count_frame("delta", frozen, seconds, int(consumed))]
    bump("repack_engaged", frozen.frame.nbytes)
    return True


def _count_frame(kind: str, frozen, seconds: float, wire_bytes: int):
    """What device_ops.pack_hybrid_upload or pack_delta_upload returned
    (`kind` "hybrid" or "delta"), counted on its way into a plan: the slots
    the frame covers, the wire bytes it read and the plane bytes it wrote (so
    a trace says how much the frame grew or shrank the upload), and the
    prepare.<kind>_frame sub-clock (clocked inside the native call;
    back-dated like the native walk's prepare.* clocks so that it nests in
    chunk.prepare)."""
    frame_bytes = frozen.n_pad * frozen.width // 8
    for name, n in (
        (f"{kind}_values_framed", frozen.total),
        (f"{kind}_wire_bytes", wire_bytes),
        (f"{kind}_frame_bytes", frame_bytes),
    ):
        _metrics.event(name, n)
        _trace.count(name, n)
    _trace.add_seconds(f"prepare.{kind}_frame", seconds, frame_bytes)
    return frozen


def _freeze_delta_from_tables(data_pages, res, nbits: int) -> list:
    """THE freeze of a DELTA_BINARY_PACKED chunk, from the whole-chunk
    miniblock tables of the native walk (the staged walk lays its prescans
    out the same way: _delta_tables_of). Pages group sequentially under the
    bit cap, one upload a group: the group's deltas re-framed position by
    position at one static width (device_ops.pack_delta_upload; the one
    pass in which the host reads a delta chunk's payload); a page without
    values contributes nothing."""
    cap = _BATCH_BITS_CAP
    groups: list[list] = []  # [pages, ms, me, lo, hi, bits]
    cur = None
    for P in data_pages:
        if P[_PC_ROUTE] != 2 or P[_PC_EXTRA] == 0:
            continue
        bits = P[_PC_DCONS] * 8
        if cur is None or cur[5] + bits > cap:
            cur = [[P], P[_PC_MINIS], P[_PC_MINIE], P[_PC_DSTART],
                   P[_PC_DSTART] + P[_PC_DCONS], bits]
            groups.append(cur)
        else:
            cur[0].append(P)
            cur[2] = P[_PC_MINIE]
            cur[4] = P[_PC_DSTART] + P[_PC_DCONS]
            cur[5] += bits
    frozen = []
    for plist, ms, me, lo, hi, _bits in groups:
        totals = np.array([P[_PC_EXTRA] for P in plist], dtype=np.int64)
        bases = np.zeros(len(plist), dtype=np.int64)  # each page's first output position
        np.cumsum(totals[:-1], out=bases[1:])
        minis_per_page = [P[_PC_MINIE] - P[_PC_MINIS] for P in plist]
        frozen.append(_count_frame(
            "delta",
            *pack_delta_upload(
                res["d_widths"][ms:me],
                # payload bits are addressed from the group's first wire byte
                (res["d_bytestart"][ms:me] - lo) * 8,
                # a page's k-th delta lands one past its first value
                res["d_outstart"][ms:me].astype(np.int64) + np.repeat(bases + 1, minis_per_page),
                res["d_mins"][ms:me],
                bases,
                np.array([P[_PC_DFIRST] for P in plist], dtype=np.int64),
                res["delta_stream"][lo:hi],
                nbits,
                int(totals.sum()),
            ),
            hi - lo,
        ))
    return frozen


def _expand_dict_from_tables(P, res) -> np.ndarray:
    """Host expansion of one dict page straight from the global run tables
    (mirrors _host_decode_dict_page without re-prescanning the stream)."""
    from ..ops.rle_hybrid import RunTable, expand_runs

    rs, re, ps = P[_PC_RUNS], P[_PC_RUNE], P[_PC_PACKS]
    width = P[_PC_EXTRA]
    is_rle = res["h_is_rle"][rs:re].astype(bool)
    counts = res["h_counts"][rs:re]
    if len(counts) and not is_rle[-1] and width > 0:
        # the native walk clamps the final run's count to the page's value
        # count; expand_runs wants the FULL bit-packed count (its dense-unpack
        # math needs multiples of 8) and clamps via `takes` itself
        counts = counts.copy()
        counts[-1] = ((P[_PC_PACKE] - int(res["h_byteoff"][re - 1])) // width) * 8
    table = RunTable(
        is_rle=is_rle,
        counts=counts,
        rle_values=res["h_values"][rs:re],
        bp_offsets=res["h_byteoff"][rs:re] - ps,
        packed=bytes(res["packed"][ps : P[_PC_PACKE]]),
        consumed=0,
    )
    return expand_runs(table, P[_PC_NONNULL], width, np.uint32)


def prepare_chunk_plan(
    f,
    chunk,
    column: Column,
    validate_crc: bool = False,
    alloc=None,
    stats: TpuDecodeStats | None = None,
    doubles: str | None = None,
    list_lengths: bool = False,
) -> _ChunkPlan:
    """Host-only prepare: page walk, decompress, level decode, prescan.
    `doubles` (DOUBLE_FORMS) is the form a DOUBLE column is bound for.
    `list_lengths` prepares the padded delivery of a single-level LIST leaf
    (_ChunkPlan.device_values_padded): its record structure is derived here,
    once, as per-document element counts.

    Touches no jax state, so it is safe to run on worker threads; the
    returned plan's batches go to the device via plan.dispatch_device() on
    the dispatching thread. The whole-chunk native walk handles the common
    shapes in one C call; anything it declines takes the per-page Python
    walk below (the error-semantics reference) — the decode fallback
    ladder's middle rung. A chunk the native walk ABORTED on (fault set)
    that the staged walk then decodes cleanly counts as
    prepare_fallback_recovered; a genuinely corrupt chunk raises the staged
    walk's typed error (the ladder's final rung).
    """
    import time as _time


    plan, fault = _native_prepare(
        f, chunk, column, validate_crc, alloc, stats, doubles, list_lengths
    )
    if plan is None:
        t0 = _time.perf_counter()
        plan = _staged_prepare(
            f, chunk, column, validate_crc, alloc, stats, doubles, list_lengths
        )
        _metrics.observe("chunk_decode_seconds", _time.perf_counter() - t0)
        if fault is not None:
            # the native walk aborted but the staged walk decoded cleanly
            _trace.bump("prepare_fallback_recovered")
            from ..obs.log import log_event as _log_event

            _log_event(
                "prepare_fallback_recovered", level="warning",
                column=".".join(column.path), fault=str(fault),
            )
    if plan.host_pages:
        # always on, beside prepare_fused_*: a "device" read may decode any
        # share of its pages on the host, and only this says so — process-
        # wide as events_total{event="host_decoded_pages"}, per column
        # under an active decode_trace
        _metrics.event("host_decoded_pages", plan.host_pages)
        _trace.count("host_decoded_pages", plan.host_pages)
        _trace.count(f"host_decoded_pages.{column.path_str}", plan.host_pages)
    if plan.doubles is not None:
        _shape_double_dictionary(plan)
    if list_lengths:
        _shape_list_lengths(plan)
    return plan


def _shape_list_lengths(plan: _ChunkPlan) -> None:
    """The record structure of a single-level LIST leaf as it uploads under
    the padded delivery: one int32 element count a document (ops/levels.
    list_lengths), O(documents) where the two level streams are O(elements).
    Clocked as prepare.levels.lengths, beside the native walk's
    prepare.levels: together the host's share of the Dremel half."""
    import time as _time

    from ..ops.levels import LevelError, list_lengths

    column = plan.column
    t0 = _time.perf_counter()
    if plan.native_rep is not None or plan.native_def is not None:
        rep, dfl = plan.native_rep, plan.native_def
    else:
        reps = [r for _, _, r, _, _ in plan.page_infos if r is not None]
        defs = [d for _, d, _, _, _ in plan.page_infos if d is not None]
        rep = np.concatenate(reps) if reps else np.zeros(0, dtype=np.uint16)
        dfl = np.concatenate(defs) if defs else None
    try:
        plan.list_lengths, plan.list_elements = list_lengths(
            rep, dfl, column.max_def,
            column.repetition == FieldRepetitionType.OPTIONAL,
        )
    except LevelError as e:
        raise ParquetFileError(
            f"parquet: column {column.path_str} has {e}; packing would shift "
            "positions (fill nulls upstream)"
        ) from e
    decoded = sum(
        (len(p) if k in ("values", "indices") else p)
        for _, _, _, k, p in plan.page_infos
        if k != "empty"
    )
    if decoded != plan.list_elements:
        raise ParquetFileError(
            f"parquet: column {column.path_str} level/value mismatch"
        )
    _trace.add_seconds("prepare.levels.lengths", _time.perf_counter() - t0)


def _shape_double_dictionary(plan: _ChunkPlan) -> None:
    """Under doubles=, the dictionary of a device-decoded DOUBLE chunk as it
    uploads: uint64 bit patterns ("bits") or narrowed here, on the host, to
    float32 patterns ("float32": numpy's astype is IEEE round-to-nearest-
    even, at most 2^20 entries, and the gather then moves 32-bit entries).
    Padded to the 2^w entries its index width addresses (_index_width), so
    dict_gather_device compiles once per width and not once per dictionary
    length. Prepare phase: host only, on the pool's threads."""
    d = plan.dictionary
    if not (plan.frozen_hybrid and isinstance(d, np.ndarray) and d.ndim == 1):
        return
    if plan.doubles == "float32":
        # overflow to +-inf is the stated result; a signalling NaN quiets
        with np.errstate(over="ignore", invalid="ignore"):
            bits = d.astype(np.float32).view(np.uint32)
        _trace.bump("double_dict_narrowed_host", d.nbytes)
    else:
        bits = d.view(np.uint64)
    up = np.zeros(1 << _index_width(0, len(d)), dtype=bits.dtype)
    up[: len(d)] = bits
    plan.dict_upload = up


def _staged_prepare(
    f,
    chunk,
    column: Column,
    validate_crc: bool = False,
    alloc=None,
    stats: TpuDecodeStats | None = None,
    doubles: str | None = None,
    padded: bool = False,
) -> _ChunkPlan:
    """The per-page Python prepare walk (the error-semantics reference)."""
    md = chunk.meta_data
    codec = md.codec or 0
    expected = md.num_values or 0
    plan = _ChunkPlan(column, expected, doubles, padded)
    plan.stats = stats
    ptype = column.type

    # Device-routable pages stage here until the whole chunk is walked; batch
    # building (or demotion to host decode) happens in _commit_routes.
    pending: list[tuple] = []

    for raw in iter_chunk_pages(f, chunk):
        header = raw.header
        if alloc is not None:
            alloc.check(header.uncompressed_page_size or 0)
        pt = header.type
        if pt == int(PageType.DICTIONARY_PAGE):
            if plan.dictionary is not None:
                raise ChunkError("chunk: more than one dictionary page")
            if validate_crc:
                _check_crc(header, raw.payload)
            block = decompress_block(raw.payload, codec, header.uncompressed_page_size or 0)
            plan.dictionary = decode_dict_page(header, block, column)
            if alloc is not None:
                alloc.register_buffers(plan.dictionary)
            continue
        if pt == int(PageType.INDEX_PAGE):
            continue
        if pt not in (int(PageType.DATA_PAGE), int(PageType.DATA_PAGE_V2)):
            raise ChunkError(f"chunk: unknown page type {pt}")
        if validate_crc:
            _check_crc(header, raw.payload)

        n, dfl, rep, non_null, enc, values_buf = _split_page(
            raw, header, pt, codec, column
        )
        # byte volumes ride decompress_block's choke point; pages-per-encoding
        # is counted here because this walk prescans value streams without
        # going through the core.page decoders
        _metrics.page_decoded(
            _metrics.encoding_name(enc), nbytes=header.uncompressed_page_size or 0
        )
        if stats is not None:
            stats.pages += 1
        if alloc is not None:
            # actual levels + the eventual decoded value footprint (a lying
            # header cannot understate these: non_null comes from the real
            # level stream, dict indices decode at 4 B/value, delta totals
            # are plausibility-bounded by the prescan)
            alloc.register(
                decoded_nbytes(dfl)
                + decoded_nbytes(rep)
                + len(values_buf)
                + non_null * 8
            )

        # -- route the value stream --------------------------------------------
        if enc in (int(Encoding.RLE_DICTIONARY), int(Encoding.PLAIN_DICTIONARY)):
            if plan.dictionary is None:
                from ..core.page import MissingDictionaryError

                raise MissingDictionaryError(
                    "page: dictionary encoding without dictionary"
                )
            if non_null == 0:
                plan.page_infos.append((n, dfl, rep, "empty", None))
                continue
            width = values_buf[0] if values_buf else 0
            if width > 32:
                raise PageError(f"page: invalid dict index width {width}")
            from ..core.page import typed_page_errors

            with typed_page_errors("dict index stream"):
                table = prescan_hybrid(values_buf[1:], non_null, width)
            if len(table.packed) * 8 > _BATCH_BITS_CAP:
                # One page alone exceeds the int32 bit-offset range of the
                # device kernel: decode it on host (adversarially large pages;
                # real writers page at ~1 MiB, data_store.go:149-154).
                plan.page_infos.append(
                    (n, dfl, rep, *_host_decode_dict_page(plan, table, width, non_null))
                )
                continue
            pending.append(("dict", len(plan.page_infos), table, width, non_null, None))
            plan.page_infos.append((n, dfl, rep, "dict", non_null))
        elif enc == int(Encoding.DELTA_BINARY_PACKED) and ptype in (
            Type.INT32,
            Type.INT64,
        ):
            nbits = 32 if ptype == Type.INT32 else 64
            from ..core.page import typed_page_errors

            with typed_page_errors("delta stream"):
                table = prescan_delta_packed(values_buf, nbits, max_total=non_null)
            if table.consumed * 8 > _BATCH_BITS_CAP:
                # Same int32-range guard as the hybrid path: host decode.
                plan.page_infos.append(
                    (n, dfl, rep, *_host_decode_delta_page(plan, values_buf, nbits, non_null))
                )
                continue
            pending.append(("delta", len(plan.page_infos), table, nbits, non_null, values_buf))
            plan.page_infos.append((n, dfl, rep, "delta", table.total))
        elif enc == int(Encoding.PLAIN) and ptype in _NUMERIC_DTYPE:
            dt = _NUMERIC_DTYPE[ptype]
            need = non_null * np.dtype(dt).itemsize
            if len(values_buf) < need:
                raise PageError("page: plain payload too short")
            vals = np.frombuffer(values_buf, dtype=dt, count=non_null)
            plan.page_infos.append((n, dfl, rep, "values", vals))
        else:
            # Anything else (byte arrays, boolean, deltas on other types):
            # host decode for this page.
            from ..core.page import _decode_values

            dict_size = len(plan.dictionary) if plan.dictionary is not None else None
            values, indices = _decode_values(
                values_buf, non_null, enc, column, dict_size
            )
            if indices is not None:
                plan.page_infos.append((n, dfl, rep, "indices", indices))
            else:
                plan.page_infos.append((n, dfl, rep, "values", values))
            _count_host_page(plan)

    _commit_routes(plan, pending)
    return plan


def _commit_routes(plan: _ChunkPlan, pending: list) -> None:
    """Freeze the chunk's device uploads — or demote to host decode if its
    pages are not homogeneous.

    Device decode only pays when the whole chunk's values stay on device; a
    chunk that mixes device-kinds with host-kinds (e.g. pyarrow's mid-chunk
    dictionary->PLAIN fallback once the dict page overflows) would need its
    device-decoded pages FETCHED back during reassembly — the exact
    round-trip regression backend="tpu" routing exists to avoid. Deciding
    after the full page walk keeps the cliff out: mixed chunks decode
    entirely on host and device_column does one typed upload.
    """
    kinds = {k for _, _, _, k, _ in plan.page_infos}
    kinds.discard("empty")
    pending_kinds = {p[0] for p in pending}
    # Homogeneous PLAIN numeric chunks: pre-concatenate the upload buffer
    # here (host-only) so dispatch is a single transfer.
    if kinds == {"values"} and not pending and plan.column.type in _NUMERIC_DTYPE:
        parts = [p for _, _, _, k, p in plan.page_infos if k == "values"]
        plan.plain_host = parts[0] if len(parts) == 1 else np.concatenate(parts)
        return
    if kinds == pending_kinds == {"delta"}:
        nbits = pending[0][3]  # the column's: the same in every entry
        plan.frozen_delta = _freeze_delta_from_tables(*_delta_tables_of(pending), nbits)
        return
    if kinds == pending_kinds == {"dict"}:
        frozen = _freeze_hybrid_from_tables(
            *_hybrid_tables_of(pending),
            len(plan.dictionary) if plan.dictionary is not None else 0,
        )
        if frozen is not None:
            plan.frozen_hybrid = frozen
            return
    # Demote: host-decode the would-be device pages in place.
    for kind, idx, table, arg, non_null, buf in pending:
        n, dfl, rep, _k, _p = plan.page_infos[idx]
        if kind == "dict":
            plan.page_infos[idx] = (
                n, dfl, rep, *_host_decode_dict_page(plan, table, arg, non_null)
            )
        else:
            plan.page_infos[idx] = (
                n, dfl, rep, *_host_decode_delta_page(plan, buf, arg, non_null)
            )
    # a demotion can leave the chunk all-'values' numeric: pre-concat so its
    # upload still happens on the dispatch thread, not in device_column
    kinds_after = {k for _, _, _, k, _ in plan.page_infos}
    kinds_after.discard("empty")
    if kinds_after == {"values"} and plan.column.type in _NUMERIC_DTYPE:
        parts = [p for _, _, _, k, p in plan.page_infos if k == "values"]
        if parts:
            plan.plain_host = parts[0] if len(parts) == 1 else np.concatenate(parts)


# The staged walk's prescans as the whole-chunk tables the native walk returns
# (native/parquet_tpu_native.cc ptq_chunk_prepare; one page row each), so
# that both walks feed ONE freeze a kernel. `pending` entries are
# (kind, page index, table, width | nbits, non-null count, value stream).


def _hybrid_tables_of(pending: list) -> tuple[list, dict]:
    """(page rows, run tables) of a dictionary chunk's pages. A page's run
    counts are clamped so that it contributes exactly its real value count
    (its final bit-packed group may encode up to 7 padding values; clamping
    the last run's count drops them without touching bit offsets). A
    bit-packed run's byte offset is into the chunk's `packed`; an RLE run's
    is 0, as the native walk leaves it."""
    rows, is_rle, counts, values, byteoff, packed = [], [], [], [], [], []
    n_runs = n_bytes = 0
    for _kind, _idx, table, width, take, _buf in pending:
        c = table.counts.astype(np.int64)
        cum = np.cumsum(c)
        if take > (int(cum[-1]) if len(cum) else 0):
            raise PageError("page: hybrid run table shorter than value count")
        k = int(np.searchsorted(cum, take, side="left")) + 1
        c = c[:k]
        c[-1] = take - (int(cum[k - 2]) if k > 1 else 0)
        P = [0] * _PC_COLS
        P[_PC_ROUTE], P[_PC_EXTRA] = 1, width
        P[_PC_RUNS], P[_PC_RUNE] = n_runs, n_runs + k
        P[_PC_PACKS], P[_PC_PACKE] = n_bytes, n_bytes + len(table.packed)
        rows.append(P)
        is_rle.append(table.is_rle[:k])
        counts.append(c)
        values.append(table.rle_values[:k])
        byteoff.append(np.where(table.is_rle[:k], 0, table.bp_offsets[:k] + n_bytes))
        packed.append(np.frombuffer(table.packed, dtype=np.uint8))
        n_runs += k
        n_bytes += len(table.packed)
    return rows, {
        "h_is_rle": np.concatenate(is_rle).astype(np.uint8),
        "h_counts": np.concatenate(counts),
        "h_values": np.concatenate(values),
        "h_byteoff": np.concatenate(byteoff),
        "packed": np.concatenate(packed),
    }


def _delta_tables_of(pending: list) -> tuple[list, dict]:
    """(page rows, miniblock tables) of a DELTA_BINARY_PACKED chunk's pages;
    a page without values contributes nothing."""
    rows, widths, bytestart, outstart, mins, streams = [], [], [], [], [], []
    n_minis = n_bytes = 0
    for _kind, _idx, table, _nbits, _non_null, buf in pending:
        if table.total == 0:
            continue
        P = [0] * _PC_COLS
        P[_PC_ROUTE], P[_PC_EXTRA] = 2, table.total
        P[_PC_MINIS], P[_PC_MINIE] = n_minis, n_minis + len(table.widths)
        P[_PC_DSTART], P[_PC_DCONS] = n_bytes, table.consumed
        # the table holds the first value unsigned; the page row is int64
        P[_PC_DFIRST] = int(np.uint64(table.first_value).astype(np.int64))
        rows.append(P)
        widths.append(table.widths)
        bytestart.append(table.byte_starts + n_bytes)
        outstart.append(table.out_starts)
        mins.append(table.mins)
        streams.append(np.frombuffer(buf, dtype=np.uint8)[: table.consumed])
        n_minis += len(table.widths)
        n_bytes += table.consumed
    if not rows:
        return [], {}  # every page empty: nothing to freeze
    return rows, {
        "d_widths": np.concatenate(widths),
        "d_bytestart": np.concatenate(bytestart),
        "d_outstart": np.concatenate(outstart),
        "d_mins": np.concatenate(mins),
        "delta_stream": np.concatenate(streams),
    }


def _host_decode_dict_page(plan, table, width: int, non_null: int):
    """Host fallback for a dict-coded page: ('indices', expanded indices)."""
    from ..ops.rle_hybrid import expand_runs

    _count_host_page(plan)
    return "indices", expand_runs(table, non_null, width, np.uint32)


def _host_decode_delta_page(plan, values_buf, nbits: int, non_null: int):
    """Host fallback for a delta page: ('values', decoded values)."""
    from ..core.page import typed_page_errors
    from ..ops.delta import decode_delta

    _count_host_page(plan)
    with typed_page_errors("delta stream"):
        vals, _ = decode_delta(values_buf, nbits, max_total=non_null)
    return "values", vals[:non_null]


def _split_page(raw, header, pt, codec, column: Column):
    """Split a data page into levels (host-decoded) and the value stream."""
    from ..core.page import typed_page_errors
    from ..ops.levels import decode_levels_v1, decode_levels_v2

    if pt == int(PageType.DATA_PAGE):
        h = header.data_page_header
        if h is None:
            raise PageError("page: DATA_PAGE without data_page_header")
        n = h.num_values or 0
        block = decompress_block(raw.payload, codec, header.uncompressed_page_size or 0)
        buf = memoryview(block)
        pos = 0
        rep = None
        with typed_page_errors("v1 level stream"):
            if column.max_rep > 0:
                rep, used = decode_levels_v1(buf, n, column.max_rep)
                pos += used
            dfl = None
            non_null = n
            if column.max_def > 0:
                dfl, used, cv = decode_levels_v1(
                    buf[pos:], n, column.max_def, want_const=True
                )
                pos += used
                if cv is not None:
                    non_null = n if cv == column.max_def else 0
                else:
                    non_null = int((dfl == column.max_def).sum())
        return n, dfl, rep, non_null, h.encoding, buf[pos:]

    h = header.data_page_header_v2
    if h is None:
        raise PageError("page: DATA_PAGE_V2 without data_page_header_v2")
    n = h.num_values or 0
    rep_len = h.repetition_levels_byte_length or 0
    def_len = h.definition_levels_byte_length or 0
    buf = memoryview(raw.payload)
    if rep_len < 0 or def_len < 0 or rep_len + def_len > len(buf):
        raise ChunkError("chunk: v2 level sizes exceed page")
    with typed_page_errors("v2 level stream"):
        rep = (
            decode_levels_v2(buf[:rep_len], n, column.max_rep)
            if column.max_rep > 0
            else None
        )
        dfl = None
        non_null = n
        if column.max_def > 0:
            dfl, cv = decode_levels_v2(
                buf[rep_len : rep_len + def_len], n, column.max_def, want_const=True
            )
            if cv is not None:
                non_null = n if cv == column.max_def else 0
            else:
                non_null = int((dfl == column.max_def).sum())
    values_buf = buf[rep_len + def_len :]
    if h.is_compressed is None or h.is_compressed:
        un = (header.uncompressed_page_size or 0) - rep_len - def_len
        values_buf = decompress_block(values_buf, codec, max(un, 0))
    return n, dfl, rep, non_null, h.encoding, values_buf


def read_chunk_tpu(
    f,
    chunk,
    column: Column,
    validate_crc: bool = False,
    alloc=None,
    stats: TpuDecodeStats | None = None,
) -> ChunkData:
    """TPU-backend chunk decode: levels on host, values on device.

    Byte-identical to core.chunk.read_chunk (the M1 oracle) — enforced by
    tests/test_tpu_backend.py on every supported shape.
    """
    return plan_chunk_tpu(
        f, chunk, column, validate_crc=validate_crc, alloc=alloc, stats=stats
    ).finalize()


def _device_bitcast(vals: jnp.ndarray, column: Column) -> jnp.ndarray:
    """Bitcast gathered uint patterns back to the column's real dtype."""
    if column.type == Type.FLOAT:
        return jax.lax.bitcast_convert_type(vals, jnp.float32)
    if column.type == Type.DOUBLE:
        return jax.lax.bitcast_convert_type(vals, jnp.float64)
    return vals


def _upload_typed(host: np.ndarray) -> jnp.ndarray:
    """Upload a host array; floats travel as bit patterns and are bitcast
    back on device (exact for f32 everywhere and for f64 wherever the
    device holds f64 natively; device_column refuses DOUBLE where it does
    not — see DeviceDoubleError)."""
    if host.dtype.kind == "f":
        u = np.uint32 if host.dtype.itemsize == 4 else np.uint64
        return jax.lax.bitcast_convert_type(
            jnp.asarray(host.view(u)),
            jnp.float32 if host.dtype.itemsize == 4 else jnp.float64,
        )
    return jnp.asarray(host)


def _materialize(dictionary, indices):
    """Expand dictionary indices for HOST delivery.

    Always gathers on the host: by the time finalize() runs, the indices are
    host arrays (device batches are fetched in one batched transfer up
    front), and bouncing them through the device for the gather costs an
    upload + a fetch per page — measured ~100ms/page on the transfer link —
    for work NumPy does in microseconds. The device dictionary (dict_dev)
    exists solely for device-resident delivery (device_column).

    An index past the dictionary is corrupt input (a rotted bit in the index
    stream), not a programming error: surface it typed, never as a raw
    IndexError (fault-harness contract — the staged walk validates indices
    at decode time, this is the fused walk's equivalent boundary)."""
    try:
        if isinstance(dictionary, ByteArrayData):
            return dictionary.take(np.asarray(indices, dtype=np.int64))
        return np.asarray(dictionary)[np.asarray(indices)]
    except (IndexError, ValueError) as e:
        raise PageError(f"page: dictionary index out of range: {e}") from e


def _concat_values(parts, column: Column):
    parts = [p for p in parts if p is not None]
    if any(isinstance(p, ByteArrayData) for p in parts):
        from ..core.chunk import _concat_byte_arrays

        return _concat_byte_arrays(parts)
    arrs = [np.asarray(p) for p in parts if len(p)]
    if arrs:
        return np.concatenate(arrs) if len(arrs) > 1 else arrs[0]
    from ..core.chunk import _empty_dtype

    if column.type == Type.BYTE_ARRAY:
        return ByteArrayData(offsets=np.zeros(1, dtype=np.int64), data=b"")
    return np.empty(0, dtype=_empty_dtype(column))


# -- write path: DeviceColumn -> encoded pages ---------------------------------
#
# The batch-materialization inverse of read_chunk_tpu: a device-resident
# numeric column (a training batch, a checkpoint shard, a DeviceColumn's
# `values`) encodes into parquet pages WITHOUT first round-tripping the raw
# column through host encode loops. The expensive transforms — the
# dictionary probe and the hybrid bit-pack — run as the jittable inverses in
# device_ops (dict_indices_device / rle_hybrid_encode_device /
# bitpack_encode_device); the host's remaining share is run-header emission
# over the (few) segments plus page framing/compression, and the bytes are
# pinned identical to sink.encoder.encode_chunk for the same values.


def assemble_hybrid_device_stream(
    in_rle: np.ndarray, rle_break: np.ndarray, packed: np.ndarray,
    width: int, value_at
) -> bytes:
    """Turn rle_hybrid_encode_device's run plan into the exact
    ops/rle_hybrid.encode_hybrid byte stream. `in_rle`/`rle_break` are the
    device masks (fetched; one byte per value — rle_break splits ADJACENT
    RLE windows of different runs, which a flat mask would fuse), `packed`
    the device-packed payload words, `value_at(pos)` resolves an RLE
    window's repeated value (a tiny device gather per segment — segments
    are few by construction)."""
    from ..ops.varint import emit_uvarint as _emit_uvarint

    n = len(in_rle)
    out = bytearray()
    if n == 0:
        return b""
    if width == 0:
        _emit_uvarint(out, n << 1)
        return bytes(out)
    vbytes = (width + 7) // 8
    packed_bytes = memoryview(np.ascontiguousarray(packed)).cast("B")
    mask = np.asarray(in_rle, dtype=bool)
    breaks = np.asarray(rle_break, dtype=bool)
    seg_start = breaks.copy()
    seg_start[0] = True
    seg_start[1:] |= mask[1:] != mask[:-1]
    starts = np.flatnonzero(seg_start)
    bounds = np.append(starts, n)
    bp_done = 0  # bit-packed values consumed (tracks the payload cursor)
    for a, b in zip(bounds[:-1], bounds[1:]):
        a, b = int(a), int(b)
        if mask[a]:
            _emit_uvarint(out, (b - a) << 1)
            out += int(value_at(a)).to_bytes(vbytes, "little")
        else:
            groups = (b - a + 7) // 8
            _emit_uvarint(out, (groups << 1) | 1)
            byte0 = (bp_done // 8) * width
            out += packed_bytes[byte0 : byte0 + groups * width]
            bp_done += groups * 8
    return bytes(out)


def assemble_delta_device_stream(
    nbits: int,
    n: int,
    first: int,  # values[0] in the UNSIGNED nbits domain (0 when n == 0)
    mins: np.ndarray,  # int32/int64[>= n_blocks]: per-block min delta, signed
    widths: np.ndarray,  # int32[>= n_blocks * 4]: per-miniblock bit widths
    payload: bytes,  # packed payloads at cumsum(4 * width) byte offsets
) -> bytes:
    """Frame delta_block_encode_device's tables into the exact
    ops/delta.encode_delta byte stream (block_size=128, mini_count=4):
    uvarint header, then per block `<zigzag min> <4 width bytes> <payloads>`.
    mini_len=32 keeps every payload 4*width bytes, so the device stream
    slices out by a running byte cursor — the only sequential work left is
    header emission over the (few) blocks, the write-side twin of the
    prescan/expand split on the read side."""
    from ..ops.delta import _to_signed
    from ..ops.varint import emit_uvarint, emit_zigzag

    out = bytearray()
    emit_uvarint(out, 128)
    emit_uvarint(out, 4)
    emit_uvarint(out, n)
    emit_zigzag(out, _to_signed(int(first), nbits))
    if n <= 1:
        return bytes(out)
    n_deltas = n - 1
    pay = 0
    for blk in range((n_deltas + 127) // 128):
        emit_zigzag(out, int(mins[blk]))
        ws = [int(widths[blk * 4 + k]) for k in range(4)]
        out += bytes(ws)
        for k, w in enumerate(ws):
            if blk * 128 + k * 32 < n_deltas:  # mini has values: full payload
                out += payload[pay : pay + 4 * w]
            pay += 4 * w
    return bytes(out)


class _DevicePageFramer:
    """Host framing of device-produced page payloads — compress + Thrift
    header + optional CRC, shared by every encode_device_column route (the
    exact mirror of core/page.encode_data_page_v1/v2 for flat REQUIRED
    columns: no levels, no nulls)."""

    def __init__(self, cfg, value_encoding):
        from ..core.compress import compress_block
        from ..core.page import _crc32_signed
        from ..meta.parquet_types import (
            DataPageHeader,
            DataPageHeaderV2,
            PageHeader,
        )

        self._cfg = cfg
        self._value_encoding = value_encoding
        self._compress = compress_block
        self._crc = _crc32_signed
        self._PageHeader = PageHeader
        self._DataPageHeader = DataPageHeader
        self._DataPageHeaderV2 = DataPageHeaderV2
        self.parts: list = []
        self.pos = 0
        self.uncompressed_total = 0
        self.n_pages = 0

    def frame(self, raw: bytes, n_values: int) -> None:
        cfg = self._cfg
        block = self._compress(raw, cfg.codec)
        if cfg.data_page_version == 1:
            header = self._PageHeader(
                type=0,
                uncompressed_page_size=len(raw),
                compressed_page_size=len(block),
                data_page_header=self._DataPageHeader(
                    num_values=n_values,
                    encoding=int(self._value_encoding),
                    definition_level_encoding=int(Encoding.RLE),
                    repetition_level_encoding=int(Encoding.RLE),
                ),
            )
        else:
            header = self._PageHeader(
                type=3,
                uncompressed_page_size=len(raw),
                compressed_page_size=len(block),
                data_page_header_v2=self._DataPageHeaderV2(
                    num_values=n_values,
                    num_nulls=0,
                    num_rows=n_values,
                    encoding=int(self._value_encoding),
                    definition_levels_byte_length=0,
                    repetition_levels_byte_length=0,
                    is_compressed=True,
                ),
            )
        if cfg.with_crc:
            header.crc = self._crc(block)
        hdr = header.dumps()
        self.parts.append(hdr)
        self.parts.append(block)
        self.pos += len(hdr) + len(block)
        self.uncompressed_total += len(hdr) + len(raw)
        self.n_pages += 1


def encode_device_column(
    column: Column,
    values,
    cfg,
    kv: dict | None = None,
    *,
    enable_dict: bool = True,
):
    """Encode one device-resident numeric column into an EncodedChunk whose
    bytes are IDENTICAL to the host encoder's for the same values — drop-in
    for sink.encoder's assemble_group/commit_group, so a device training
    batch materializes to parquet through the same sink seam.

    `values` is a 1-D int32/int64/float32/float64 jax array (or anything
    jnp.asarray accepts) — or, for a BYTE_ARRAY column, a `(data, offsets)`
    pair of device arrays (uint8 payload + n+1 value offsets, the same
    layout the device read path delivers). The column must be flat REQUIRED
    (the dense batch shape device pipelines produce — levels stay a host
    concern). The dictionary decision, index hybrid-encode, bit-pack,
    DELTA block scans and byte-array framing all run on device; the host
    frames pages and compresses blocks."""
    import jax.numpy as _jnp

    from ..core.column_store import DICT_MAX_UNIQUES
    from ..core.page import encode_dict_page
    from ..core.stats import column_is_unsigned
    from ..sink.encoder import (
        EncodedChunk,
        _ChunkEncodePlan,
        _chunk_meta,
        _split_starts,
    )

    if column.max_rep > 0 or column.max_def > 0:
        raise ValueError(
            "encode_device_column: only flat REQUIRED columns encode "
            "device-side (nested/optional batches go through the host writer)"
        )
    if cfg.write_page_index:
        # per-page stat collection lives in the host encoder's
        # _PageIndexBuilder; silently dropping a requested page index would
        # break the drop-in identity this function promises
        raise ValueError(
            "encode_device_column: write_page_index is host-encoder-only "
            "(use sink.encoder.encode_chunk for indexed chunks)"
        )
    if column.type == Type.BYTE_ARRAY:
        if enable_dict:
            # The host encoder would run its dictionary probe (and dict-
            # encode when it pays); the device route has no byte-array
            # uniqueness kernel, so declining here keeps the byte-identity
            # contract — the writer's typed fallback re-encodes on host.
            raise ValueError(
                "encode_device_column: dictionary-eligible BYTE_ARRAY "
                "columns encode host-side (disable the dictionary for "
                "this column to engage the device PLAIN route)"
            )
        return _encode_device_bytearray(column, values, cfg, kv)
    dev = _jnp.asarray(values)
    if dev.ndim != 1 or dev.dtype.itemsize not in (4, 8):
        raise ValueError(
            "encode_device_column: expected a 1-D 4/8-byte numeric column"
        )
    want = {Type.INT32: 4, Type.INT64: 8, Type.FLOAT: 4, Type.DOUBLE: 8}.get(
        column.type
    )
    if want is None or dev.dtype.itemsize != want:
        # An int64 batch built before jax x64 was enabled arrives as int32:
        # encoding its 4-byte values into an INT64 chunk would write a
        # corrupt file. The typed decline routes through the host encoder,
        # which widens correctly.
        raise ValueError(
            f"encode_device_column: {column.path_str} is {column.type!s} "
            f"but the device array is {dev.dtype} — width mismatch "
            "(was the array built before jax x64 was enabled?)"
        )
    n = int(dev.shape[0])
    np_dt = np.dtype(dev.dtype.name)
    # uniqueness domain: bit patterns, so NaN payloads dedup like the host
    bits = jax.lax.bitcast_convert_type(
        dev, _jnp.uint32 if np_dt.itemsize == 4 else _jnp.uint64
    )
    dict_result = None
    indices = None
    if enable_dict and n:
        idx_dev, firsts_dev, nu_dev = dict_indices_device(bits)
        nu = int(nu_dev)
        if nu <= DICT_MAX_UNIQUES:
            width = max(int(nu - 1).bit_length(), 1)
            dict_nbytes = nu * np_dt.itemsize
            if dict_nbytes + (n * width) // 8 < n * np_dt.itemsize:
                dict_values = np.asarray(dev[firsts_dev[:nu]]).astype(
                    np_dt, copy=False
                )
                dict_result = (dict_values, None)
                indices = idx_dev.astype(_jnp.uint32)
    value_encoding = (
        Encoding.RLE_DICTIONARY
        if dict_result is not None
        else cfg.column_encodings.get(column.path, Encoding.PLAIN)
    )
    nbits = np_dt.itemsize * 8
    delta_route = (
        dict_result is None
        and value_encoding == Encoding.DELTA_BINARY_PACKED
        and column.type in (Type.INT32, Type.INT64)
        and np_dt.kind in "iu"
    )
    host_typed = None
    stats_src = None
    if dict_result is None and not delta_route:
        host_typed = np.asarray(dev).astype(np_dt, copy=False)
        stats_src = host_typed
    elif delta_route:
        # DELTA never round-trips the raw column: min/max reduce on device
        # (in the column's defined order) and a 2-element stats_src yields
        # the identical Statistics bytes. Bloom is the one consumer that
        # needs every value — download only when a spec asks for it.
        udt = _jnp.uint32 if nbits == 32 else _jnp.uint64
        view = (
            jax.lax.bitcast_convert_type(dev, udt)
            if column_is_unsigned(column) and np_dt.kind == "i"
            else dev
        )
        if n:
            stats_src = np.array(
                [int(view.min()), int(view.max())],
                dtype=np.dtype(view.dtype.name),
            ).view(np_dt)
        else:
            stats_src = np.zeros(0, dtype=np_dt)
        if cfg.bloom_specs.get(column.path) is not None:
            host_typed = np.asarray(dev).astype(np_dt, copy=False)

    framer = _DevicePageFramer(cfg, value_encoding)
    dict_offset = None
    if dict_result is not None:
        header, block = encode_dict_page(
            column, dict_result[0], cfg.codec, cfg.with_crc
        )
        hdr = header.dumps()
        dict_offset = framer.pos
        framer.parts.append(hdr)
        framer.parts.append(block)
        framer.pos += len(hdr) + len(block)
        framer.uncompressed_total += len(hdr) + (
            header.uncompressed_page_size or 0
        )
        _metrics.inc("pages_written_total", encoding="PLAIN")
        data_offset = framer.pos
        width = max(int(len(dict_result[0]) - 1).bit_length(), 1)
        for a, b in _split_starts(n, max(int(cfg.max_page_size // 4), 1)):
            page_idx = indices[a:b]
            in_rle, rle_break, packed, _n_bp = rle_hybrid_encode_device(
                page_idx, width
            )
            stream = assemble_hybrid_device_stream(
                np.asarray(in_rle),
                np.asarray(rle_break),
                np.asarray(packed),
                width,
                lambda p, _pi=page_idx: int(_pi[p]),
            )
            framer.frame(bytes([width]) + stream, b - a)
    elif delta_route:
        data_offset = framer.pos
        per_page = max(int(cfg.max_page_size // np_dt.itemsize), 1)
        udt = _jnp.uint32 if nbits == 32 else _jnp.uint64
        for a, b in _split_starts(n, per_page):
            page = dev[a:b]
            pad = _bucket(max(b - a, 1))
            if pad > b - a:
                page = _jnp.concatenate(
                    [page, _jnp.zeros(pad - (b - a), dtype=dev.dtype)]
                )
            mins, widths, words = delta_block_encode_device(page, b - a, nbits)
            first = (
                int(jax.lax.bitcast_convert_type(dev[a], udt)) if b > a else 0
            )
            stream = assemble_delta_device_stream(
                nbits,
                b - a,
                first,
                np.asarray(mins),
                np.asarray(widths),
                memoryview(np.ascontiguousarray(words)).cast("B"),
            )
            framer.frame(stream, b - a)
    else:
        if value_encoding != Encoding.PLAIN:
            raise ValueError(
                "encode_device_column: only PLAIN/dictionary/"
                "DELTA_BINARY_PACKED device encodes are supported for "
                f"numeric columns (column asks for {value_encoding})"
            )
        data_offset = framer.pos
        per_page = max(int(cfg.max_page_size // np_dt.itemsize), 1)
        for a, b in _split_starts(n, per_page):
            framer.frame(host_typed[a:b].tobytes(), b - a)
    _metrics.inc(
        "pages_written_total", framer.n_pages,
        encoding=_metrics.encoding_name(value_encoding),
    )
    plan = _ChunkEncodePlan(
        nv=n,
        num_entries=n,
        null_count=0,
        def_levels=None,
        rep_levels=None,
        typed=host_typed,
        dict_result=dict_result,
        value_encoding=value_encoding,
        page_values=None,
        dict_size=len(dict_result[0]) if dict_result is not None else None,
        stats_src=dict_result[0] if dict_result is not None else stats_src,
    )
    cc, bloom = _chunk_meta(
        cfg,
        _DeviceBuilderShim(column),
        kv,
        plan,
        uncompressed_total=framer.uncompressed_total,
        pos=framer.pos,
        data_offset=data_offset,
        dict_offset=dict_offset,
        n_pages=framer.n_pages,
    )
    return EncodedChunk(
        parts=framer.parts, nbytes=framer.pos, chunk=cc, index=None, bloom=bloom
    )


def _encode_device_bytearray(column: Column, values, cfg, kv: dict | None):
    """BYTE_ARRAY half of encode_device_column: `values` is a
    `(data, offsets)` device pair; the PLAIN framing — `<4-byte LE length>
    <bytes>` per value — materializes on device as ONE fused program
    (plain_bytearray_encode_device), and PLAIN streams concatenate, so the
    host slices page sub-ranges out of the single framed download instead
    of looping values. Statistics still scan host-side (lexicographic
    byte-string min/max has no device formulation worth its dispatch), off
    the same offsets the page split already needs."""
    import jax.numpy as _jnp

    from ..sink.encoder import (
        EncodedChunk,
        _ChunkEncodePlan,
        _chunk_meta,
        _split_starts,
        _value_width,
    )

    try:
        data, offsets = values
    except (TypeError, ValueError):
        raise ValueError(
            "encode_device_column: BYTE_ARRAY columns take a "
            "(data, offsets) device pair"
        ) from None
    value_encoding = cfg.column_encodings.get(column.path, Encoding.PLAIN)
    if value_encoding != Encoding.PLAIN:
        raise ValueError(
            "encode_device_column: only PLAIN device encodes are supported "
            f"for BYTE_ARRAY columns (column asks for {value_encoding})"
        )
    data = _jnp.asarray(data)
    offsets = _jnp.asarray(offsets)
    if data.dtype != _jnp.uint8 or data.ndim != 1 or offsets.ndim != 1:
        raise ValueError(
            "encode_device_column: BYTE_ARRAY expects 1-D uint8 data and "
            "1-D integer offsets"
        )
    host_off = np.asarray(offsets).astype(np.int64, copy=False)
    n = int(host_off.shape[0] - 1)
    total = int(host_off[-1]) if n >= 0 else 0
    out_pad = _bucket(max(4 * n + total, 1))
    framed = np.asarray(
        plain_bytearray_encode_device(
            _pad_device(data), _pad_device(offsets), n, out_pad
        )
    )
    bad = ByteArrayData(offsets=host_off, data=np.asarray(data))
    framer = _DevicePageFramer(cfg, value_encoding)
    data_offset = framer.pos
    for a, b in _split_starts(n, max(int(cfg.max_page_size // _value_width(bad)), 1)):
        lo = 4 * a + int(host_off[a])
        hi = 4 * b + int(host_off[b])
        framer.frame(framed[lo:hi].tobytes(), b - a)
    _metrics.inc(
        "pages_written_total", framer.n_pages,
        encoding=_metrics.encoding_name(value_encoding),
    )
    plan = _ChunkEncodePlan(
        nv=n,
        num_entries=n,
        null_count=0,
        def_levels=None,
        rep_levels=None,
        typed=bad,
        dict_result=None,
        value_encoding=value_encoding,
        page_values=None,
        dict_size=None,
        stats_src=bad,
    )
    cc, bloom = _chunk_meta(
        cfg,
        _DeviceBuilderShim(column),
        kv,
        plan,
        uncompressed_total=framer.uncompressed_total,
        pos=framer.pos,
        data_offset=data_offset,
        dict_offset=None,
        n_pages=framer.n_pages,
    )
    return EncodedChunk(
        parts=framer.parts, nbytes=framer.pos, chunk=cc, index=None, bloom=bloom
    )


class _DeviceBuilderShim:
    """The slice of ColumnChunkBuilder _chunk_meta actually reads."""

    def __init__(self, column: Column):
        self.column = column
