"""Device-side (JAX/XLA) batched decode primitives.

These are the TPU formulations of the ops/ host codecs, written as jittable
functions over fixed-shape tensors (XLA: traced once, no data-dependent
shapes). The sequential run/block structure of the wire format is dissolved on
the host into flat tables (ops/rle_hybrid.py prescan, ops/delta.py prescan);
everything here is gathers, shifts, segment-broadcasts and scans — the shapes
TPU executes well (SURVEY §7.2 M3).

Key formulation — bit-unpack without byte loops: value i of width W occupies
bits [i*W, (i+1)*W) of the LSB-first stream. Load the stream as uint32 words;
then val = (words[b>>5] >> (b&31)) | (words[b>>5+1] << (32-(b&31))), masked to
W bits: two gathers + two shifts per value, fully vectorized. 64-bit widths use
the same two-gather trick on uint64 words. That is the delta kernel's read,
whose width is data. Where the width is static and the payload one dense
stream (the hybrid kernel), W words hold exactly 32 values at fixed bit
positions: the words are first re-packed by constant shifts of strided slices
so that no value crosses a word (_align_words), and a value is ONE gather.

All index arithmetic is int32: TPU v5e has no native 64-bit integer ALU path
(XLA emulates i64 as i32 pairs, ~10-100x slower for gather/scan-heavy code),
and every batch this framework builds is < 2^31 bits (buckets are capped by
MAX_DEVICE_BATCH_BITS; the host drivers in pipeline.py split larger chunks).
64-bit *values* (delta int64 payloads) still use uint64 lanes — only the
positions/indices stay 32-bit.

int64 value support requires jax_enable_x64; enabled at import (documented in
the package README).

Why XLA formulations and not hand-written Pallas kernels: a fused Pallas
hybrid-expansion kernel (kept through round 1 as kernels/pallas_ops.py) could
not lower on the Mosaic TPU backend of its day — its essential dynamic 1-D
gather (words[bitpos >> 5]) trips Mosaic's gather lowering rule, which only
supports take_along_axis-shaped indices. What the XLA formulations cost on
a v5e is measured, not assumed (PERF.md sections 5 and 6; jax 0.9.0, libtpu
0.0.34): the reader is device-bound, the two decode kernels here are most of
its window, and each is a count of full-length gather passes — one
table[idx] over 2^20 indices takes 9.3-9.9 ms whatever the table's length
above a hundred entries (15-16 ms for 64-bit entries), against 0.3 ms for
prefix_sum and 0.2-0.6 ms for a scatter-add of up to 65,536 32-bit updates.
That is why nothing below looks a run, miniblock or page up per value: the
position -> segment index is a scatter and a scan (_segment_of; searchsorted
was 13-17 dependent gather passes, 65 % of the device's busy time in PR 26's
trace), and a segment's fields reach its positions the same way (_spread:
scatter the differences at the starts, scan; four of expand_hybrid_device's
six passes and five of delta_packed_decode_device's seven went with it in
PR 29). What is left of each kernel is its reads of the packed words. The
hybrid kernel read two words a value through PR 30, 16.5 of the 17-18 ms a
stream cost per 2^20 values at any width and run count; since PR 31 it aligns
the payload first (under 0.2 ms) and reads one: 8.1-8.4 ms a stream, 9.2
with 65,536 runs, 6.2 from a 1,024-word payload (PERF.md section 6, PR 31;
the gather is 7.5 ms with the kernel's near-sequential indices). A
64-bit delta stream still reads two: 36 of its 38 ms (four 32-bit passes:
the emulated halves) while XLA keeps all four word tables in fast memory, 49
of 51 once the wire words pass 2^18 — the fourth table is then read from HBM,
a 22 ms pass (PERF.md section 6, PR 29).
"""

from __future__ import annotations

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache, shared by every process of a run. Where
# JAX_COMPILATION_CACHE_DIR is set (jax reads it into its own config) or the
# embedder configured a directory, no directory is set here; otherwise ONE
# fixed, git-ignored path inside the checkout — the path is part of the
# cache key, so a directory that moves never hits. The thresholds are set
# either way: most of these programs compile in well under a second, and
# jax's defaults would leave them out of an externally placed cache.
# What a cold cache costs on a v5e (libtpu 0.0.34; chip_smoke.py at 1M-row
# groups, PERF.md PR 21): ~190 programs in ~70 s — most under a second, delta
# decode 5-9 s per shape, and the one 1M-element sort in dict_indices_device
# 22 s. Nothing is near the O(100 s) per shape bucket this comment once
# warned of, as long as row-group-sized scans go through prefix_sum.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)
if jax.config.jax_compilation_cache_dir is None:
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
# The cache key covers op metadata (jax leaves it out by default). The named
# scopes below ARE metadata, and the profiler reads them out of the loaded
# executable: with the default key a program compiled before a scope was
# added or renamed is served from the cache as it was, and the device trace
# shows the old names (or none) until the cache is emptied. The price is a
# recompile when a line moves in a kernel's call stack: seconds, once per
# checkout.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

import jax.numpy as jnp
import numpy as np
from functools import partial
from typing import NamedTuple

__all__ = [
    "MAX_DEVICE_BATCH_BITS",
    "bytes_to_words32",
    "bytes_to_words64",
    "expand_hybrid_device",
    "pack_hybrid_upload",
    "delta_packed_decode_device",
    "pack_delta_upload",
    "dict_gather_device",
    "double_narrow_device",
    "list_layout_device",
    "record_starts_device",
    "pack_append_device",
    "pack_emit_device",
    "pack_carry_device",
    "predicate_mask_device",
    "list_contains_mask_device",
    "mask_take_device",
    "bitpack_encode_device",
    "rle_hybrid_encode_device",
    "dict_indices_device",
    "delta_block_encode_device",
    "plain_bytearray_encode_device",
    "masked_agg_device",
]


def device_facts(device=None) -> dict:
    """What jax reports for `device` (None = the process default, jax's
    devices()[0]): the identity every entry point that serves or measures
    the device path prints, so a run never has to be guessed at."""
    devs = jax.devices()
    d = devs[0] if device is None else device
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "id": d.id,
        "count": len(devs),
    }


def require_chip(device=None) -> dict:
    """device_facts() for entry points whose point IS the accelerator
    (`serve --device`, bench.py's device phases): a device that is not a TPU
    is refused unless JAX_PLATFORMS names cpu outright — the way tests and
    rehearsals ask for the CPU — so a missing chip fails loudly instead of
    quietly timing XLA:CPU."""
    facts = device_facts(device)
    asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if facts["platform"] != "tpu" and not asked_cpu:
        raise RuntimeError(
            f"parquet_tpu: the device path needs a TPU, jax found "
            f"{facts['platform']} ({facts['kind']}); set JAX_PLATFORMS=cpu "
            "to run it on the CPU on purpose"
        )
    return facts


# Largest bit offset representable in the int32 position math (host drivers
# assert batches stay under this; 2^31 bits = 256 MiB of packed payload).
MAX_DEVICE_BATCH_BITS = 1 << 31


# Every jitted kernel below traces under one jax.named_scope "pqt.<kernel>"
# (the two that hold the reader's device time also under inner scopes:
# pqt.hybrid_expand/{find_run,unpack,select}, pqt.delta_decode/{find_block,
# unpack,prefix_sum,rebase}). The scope path lands in each HLO op's op_name
# metadata, which the profiler's trace carries per device op: those names
# are what benchmark/lib/xspans.py reads, so a refactor may rename or fuse
# the Python functions and must keep them. Scopes act while a program is
# traced for compilation, cost nothing when it runs and change no compiled
# code (which is why the persistent cache's key is told to cover them, above).

# Row length of the two-level prefix sum below.
_SCAN_BLOCK = 1024


@jax.jit
@jax.named_scope("pqt.prefix_sum")
def prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of a 1-D integer array in its own dtype — the
    values of jnp.cumsum, wrapping included — computed in two levels: rows of
    _SCAN_BLOCK scan independently, the row totals scan recursively, and the
    carries add back. The flat form is what XLA:TPU cannot compile quickly:
    one jnp.cumsum over 2^20 elements takes 33 s (int32) to 54 s (uint64) to
    compile on a v5e, this form 1-3 s, and both run in ~1 ms (PERF.md,
    PR 21) — every kernel here that scans a row group goes through it."""
    n = x.shape[0]
    if n <= _SCAN_BLOCK:
        return jnp.cumsum(x)
    pad = (-n) % _SCAN_BLOCK
    if pad:
        x = jnp.concatenate([x, jnp.zeros(pad, x.dtype)])
    inner = jnp.cumsum(x.reshape(-1, _SCAN_BLOCK), axis=1)
    totals = inner[:, -1]
    carry = prefix_sum(totals) - totals
    return (inner + carry[:, None]).reshape(-1)[:n]


def _segment_of(starts: jnp.ndarray, num_values: int) -> jnp.ndarray:
    """For every position i in [0, num_values): the index of the last entry
    of the sorted int32 table `starts` that is <= i, -1 where there is none
    — the values of searchsorted(starts, arange(num_values), 'right') - 1.
    The queries are every position in order, so the answer is a running
    count of segment starts: one scatter-add of a 1 at each start, one
    prefix_sum, minus one. Entries at or past num_values (the tables'
    n_pad + 1 padding) fall outside the array and are dropped, not clipped;
    equal entries (zero-length segments) add up, so the last of them wins."""
    marks = jnp.zeros(num_values, dtype=jnp.int32).at[starts].add(
        1, mode="drop", indices_are_sorted=True
    )
    return prefix_sum(marks) - 1


def _spread(starts: jnp.ndarray, field: jnp.ndarray, num_values: int) -> jnp.ndarray:
    """A per-segment quantity brought to every position of its segment:
    field[_segment_of(starts, num_values)] wherever that index is >= 0, and 0
    before the first start — without the gather. Each entry's difference from
    the entry before it (the first from 0) is scatter-added at its start, and
    one prefix_sum telescopes them: at position i the sum is the field of the
    last segment that starts at or before i. The arithmetic wraps in the
    field's own integer dtype and telescopes exactly all the same. Entries at
    or past num_values (the tables' n_pad + 1 padding) are dropped with their
    differences; they are the table's tail, so nothing after them is missed.
    Equal starts (zero-length segments) add up to the last one's field.

    On a v5e at 2^20 values (PERF.md section 6, PR 29): 0.30 ms from a table
    of up to 4,096 32-bit entries, 0.57 from 32,768, 0.85 from 65,536, where
    field[idx] is a 9.3-9.9 ms pass. A uint64 field goes as its two uint32
    halves, each telescoping on its own: 1.1 ms from 32,768 entries where the
    emulated 64-bit scatter-add alone makes it 2.75 (and 15.7 the gather)."""
    if field.dtype == jnp.uint64:
        lo = _spread(starts, field.astype(jnp.uint32), num_values)
        hi = _spread(starts, (field >> 32).astype(jnp.uint32), num_values)
        return (hi.astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)
    steps = field - jnp.concatenate([jnp.zeros(1, field.dtype), field[:-1]])
    return prefix_sum(
        jnp.zeros(num_values, dtype=field.dtype)
        .at[starts]
        .add(steps, mode="drop", indices_are_sorted=True)
    )


def _bucket(n: int, floor: int = 1024) -> int:
    """Next power-of-two bucket >= n (>= floor): every padded length of an
    upload, so that XLA compiles each kernel a bounded number of times."""
    b = floor
    while b < n:
        b <<= 1
    return b


def bytes_to_words32(data: bytes) -> np.ndarray:
    """Pad bytes to a uint32 LE word array (+1 guard word for the hi gather)."""
    pad = (-len(data)) % 4
    buf = data + b"\x00" * (pad + 4)
    return np.frombuffer(buf, dtype="<u4")


def bytes_to_words64(data: bytes) -> np.ndarray:
    pad = (-len(data)) % 8
    buf = data + b"\x00" * (pad + 8)
    return np.frombuffer(buf, dtype="<u8")


def _align_words(packed_words: jnp.ndarray, width: int):
    """A bit-packed payload (uint32 words, LSB-first values of `width` bits
    end to end from bit 0) as a table in which no value crosses a word:
    (table, bits, row_stride, word_stride). Value v sits in word
    (v >> 5) * row_stride + ((v & 31) * bits >> 5) * word_stride of the
    table, `bits` bits from bit (v * bits) & 31; `bits` is the next power of
    two >= width, so 32 // bits values share a word and the table is under
    twice the payload.

    `width` words hold exactly 32 values, at bit positions that depend on
    the static width alone: column c of the payload viewed as rows of `width`
    words is one strided slice, and each of a row's 32 values is a shift of
    one column or an or of two, by Python constants — no index array, no
    gather (the reference's unpack8Int32FuncByWidth, SURVEY.md L1). The
    `bits` words that re-pack a row come out column by column, so the table
    keeps them that way — word k of every row, then word k + 1: row_stride 1,
    word_stride rows — and nothing is transposed. At a width that is a power
    of two the payload is such a table as it stands (row_stride `width`,
    word_stride 1). The payload's length is a power-of-two bucket: it is
    padded with zeros to whole rows, never cut — to a multiple of 1,024 rows,
    so that every column is whole 1,024-word tiles (a width-17 shape then
    compiles in 1.8 s on a v5e, not 4.4, and runs no slower; PERF.md
    section 6, PR 31)."""
    bits = 1 << (width - 1).bit_length()
    if bits == width:
        return packed_words, bits, width, 1
    pad = (-packed_words.shape[0]) % (width * 1024)
    if pad:
        packed_words = jnp.concatenate([packed_words, jnp.zeros(pad, jnp.uint32)])
    n = packed_words.shape[0]
    columns = [jax.lax.slice(packed_words, (c,), (n,), (width,)) for c in range(width)]
    mask = jnp.uint32((1 << width) - 1)
    per_word = 32 // bits
    words = []
    for k in range(bits):
        word = None
        for t in range(per_word):
            c, s = divmod((k * per_word + t) * width, 32)
            value = columns[c] >> s
            if s + width > 32:
                value = value | (columns[c + 1] << (32 - s))
            value = (value & mask) << (t * bits)
            word = value if word is None else word | value
        words.append(word)
    return jnp.concatenate(words), bits, 1, n // width


@partial(jax.jit, static_argnames=("width", "num_values", "run_pad"))
@jax.named_scope("pqt.hybrid_expand")
def expand_hybrid_device(
    buf: jnp.ndarray,  # uint32: [run_meta (4*run_pad) | packed words]
    width: int,
    num_values: int,
    run_pad: int,
) -> jnp.ndarray:
    """Expand a prescanned hybrid RLE/bit-packed stream on device.

    buf is pack_hybrid_upload's (below): the four per-run vectors and the
    packed payload words in ONE upload; that function and the five slices
    here are the only statements of its layout.

    No position looks its run up. A run hands its positions two words by
    _spread (a scatter of differences at out_start and one prefix sum each,
    0.3-0.9 ms where a table[r] pass over 2^20 positions is 9): its is_rle flag,
    and one payload — the value to broadcast if it is an RLE run, else
    value_off = bit_start // width - out_start. The payload holds bit-packed
    groups only, so a run's bit_start is a multiple of `width` and the payload
    is ONE dense stream of `width`-bit values: position i of a bit-packed run
    is value i + value_off of it. Padding entries of out_start hold n_pad + 1
    and are dropped; a zero-length run repeats the next run's start and loses
    to it.

    What is left is ONE gather a value: the payload is first aligned so that
    no value crosses a word (_align_words: fixed shifts of strided slices,
    under unpack/align), then position i reads the one word that holds its
    value (unpack/gather) and shifts it out. Per 2^20 values on a v5e
    (PERF.md section 6, PR 31): the alignment 0.02-0.19 ms, the gather 7.5
    (5.6 from a 1,024-word table) whatever the width, the whole call 8.1-8.4
    (9.2 with 65,536 runs); through PR 30 a value was read as the two words
    it might straddle, 7.5 + 9.0 ms and 17.1-18.2 the call. At positions of
    RLE runs the value index means nothing, so the word index is clipped
    into the table and the result discarded. Positions past the table's
    total belong to the last run and carry garbage: the caller slices them
    off.
    """
    if width == 0:
        return jnp.zeros(num_values, dtype=jnp.uint32)
    run_is_rle = buf[:run_pad]
    run_out_start = jax.lax.bitcast_convert_type(buf[run_pad : 2 * run_pad], jnp.int32)
    run_rle_value = buf[2 * run_pad : 3 * run_pad]
    run_bp_bit_start = jax.lax.bitcast_convert_type(
        buf[3 * run_pad : 4 * run_pad], jnp.int32
    )
    packed_words = buf[4 * run_pad :]
    i = jnp.arange(num_values, dtype=jnp.int32)
    with jax.named_scope("find_run"):
        run_value_off = run_bp_bit_start // width - run_out_start
        run_payload = jnp.where(
            run_is_rle != 0,
            run_rle_value,
            jax.lax.bitcast_convert_type(run_value_off, jnp.uint32),
        )
        payload = _spread(run_out_start, run_payload, num_values)
        is_rle = _spread(run_out_start, run_is_rle, num_values) != 0
    with jax.named_scope("unpack"):
        with jax.named_scope("align"):
            table, bits, row_stride, word_stride = _align_words(packed_words, width)
        with jax.named_scope("gather"):
            v = jax.lax.bitcast_convert_type(payload, jnp.int32) + i
            bit = (v & 31) * bits
            w = (v >> 5) * row_stride + (bit >> 5) * word_stride
            bp_vals = table[jnp.clip(w, 0, table.shape[0] - 1)]
            if width < 32:
                bp_vals = (bp_vals >> (bit & 31).astype(jnp.uint32)) & jnp.uint32(
                    (1 << width) - 1
                )
    with jax.named_scope("select"):
        return jnp.where(is_rle, payload, bp_vals)


class FrozenHybrid(NamedTuple):
    """One upload of expand_hybrid_device (built in prepare, dispatched by
    transfer) with the kernel's static arguments; `total` values are real."""

    buf: np.ndarray
    width: int
    n_pad: int
    run_pad: int
    total: int


def pack_hybrid_upload(
    is_rle, counts, rle_values, bit_starts, packed, width: int, dense: bool = False
) -> FrozenHybrid:
    """The upload expand_hybrid_device reads, from one row per run: `counts`
    values each (already clamped: the runs produce exactly the values wanted,
    a zero-length run is fine), the value an RLE run repeats, the bit offset
    into `packed` (uint8 array or bytes, LSB-first groups at `width` bits) at
    which a bit-packed run's payload starts. `packed` holds bit-packed groups
    only (no headers, no RLE values), each run whole groups of 8 values, so
    every bit-packed run's bit_start % (8 * width) == 0 — both walks, re-packed
    pages included (tests/test_fused_prepare.py) — and the payload is one dense
    stream of `width`-bit values: the kernel divides bit_start by `width` and
    reads value, not bit, positions. Nobody reads a bit-packed run's value or
    an RLE run's bit offset. ONE uint32 buffer, because the
    host<->device link pays a fixed latency per transfer that dwarfs these
    tables; with run_pad = _bucket(runs, 64), n_pad = _bucket(values):
      buf[0*run_pad:1*run_pad]  is_rle      0/1
      buf[1*run_pad:2*run_pad]  out_start   exclusive cumsum of counts (int32);
                                            padding entries hold n_pad + 1
      buf[2*run_pad:3*run_pad]  rle_value
      buf[3*run_pad:4*run_pad]  bit_start   (int32)
      buf[4*run_pad:]           payload words + 1 guard word, padded to
                                _bucket(words, 1024)
    `dense` (the padded delivery, kernels/pipeline.py: a chunk whose counts
    are data and must not reach a compiled shape) floors both buckets at
    what n_pad values can need, so that the shape is a function of (width,
    n_pad) for every stream that is mostly bit-packed: run_pad at n_pad / 256
    (pyarrow writes bit-packed runs of 504 values; only a stream whose runs
    average under 256 values leaves the floor) and the payload at n_pad
    values of `width` bits plus 1,024 words (the guard word and the up to
    7 values by which each page's last group overshoots)."""
    k = len(counts)
    total = int(np.sum(counts))
    n_pad = _bucket(max(total, 1))
    words = bytes_to_words32(bytes(packed))
    run_floor, words_floor = (n_pad >> 8, n_pad * width // 32 + 1024) if dense else (0, 0)
    run_pad = _bucket(k, max(64, run_floor))
    buf = np.zeros(4 * run_pad + _bucket(len(words), max(1024, words_floor)), dtype=np.uint32)
    buf[run_pad : 2 * run_pad] = np.int32(n_pad + 1).view(np.uint32)
    out_start = np.zeros(k, dtype=np.int64)
    np.cumsum(counts[:-1], out=out_start[1:])
    # every field modulo 2^32: an int32 (a bit offset may be negative) travels
    # as its bit pattern
    for row, field in enumerate((is_rle, out_start, rle_values, bit_starts)):
        buf[row * run_pad : row * run_pad + k] = np.asarray(field).astype(np.uint32)
    buf[4 * run_pad : 4 * run_pad + len(words)] = words
    return FrozenHybrid(buf, width, n_pad, run_pad, total)


@partial(jax.jit, static_argnames=("nbits", "num_values", "m_pad", "p_pad"))
@jax.named_scope("pqt.delta_decode")
def delta_packed_decode_device(
    meta32: jnp.ndarray,  # uint32 — packed 32-bit tables (+ words when nbits=32)
    wide: jnp.ndarray,  # uint32/uint64 — packed wide tables (+ words when nbits=64)
    nbits: int,
    num_values: int,
    m_pad: int,
    p_pad: int,
) -> jnp.ndarray:
    """Fused DELTA_BINARY_PACKED decode of a whole chunk from *wire* bytes.

    The host ships the encoded stream (plus tiny per-miniblock/per-page
    tables); the device does everything: dynamic-width bit-unpack of every
    miniblock (two-word gather; the width is data, not a static — TPU vector
    shifts take vector amounts), + block min_delta, then one wrapping
    prefix-sum segmented per page:

        value[i] = first[p(i)] + C[i] - C[page_start[p(i)]]

    with C = cumsum of the per-position deltas (positions at page starts
    contribute 0). No position looks its miniblock or page up: a miniblock
    hands its positions its width, its min_delta and base = bit_start -
    out_start * width (a delta's bits sit at base + i * width) by _spread
    over out_starts, a scatter of differences and one prefix sum each; the
    page starts are a scatter of ones; and the page's offset first - C[
    page_start], a gather of p_pad entries, reaches its values by _spread
    over page_start. Padding entries hold n_pad + 1 and are dropped. A
    page's miniblocks start one past its first value, so a page start still
    carries the previous page's last miniblock (zeros at i = 0) and a bit
    position that means nothing: its word index is clipped into the payload
    and what is unpacked there is masked by is_start. What is left is the two
    gathers out of the wire words: at nbits 64 two 32-bit passes each, 9 ms a
    pass per 2^20 values on a v5e, 22 for a table XLA leaves in HBM (PERF.md
    section 6). This is
    the SURVEY §7.2 M3c shape — headers prescanned,
    payload never expanded host-side — and the upload is the wire size, ~5-10x
    smaller than the decoded column (the reason device decode beats
    host-decode-plus-upload on the host<->device link).

    meta32 and wide are pack_delta_upload's (below): that function and the
    slices here are the only statements of their layout.
    """
    mb_width = meta32[:m_pad]
    mb_bit_start = jax.lax.bitcast_convert_type(meta32[m_pad : 2 * m_pad], jnp.int32)
    mb_out_start = jax.lax.bitcast_convert_type(
        meta32[2 * m_pad : 3 * m_pad], jnp.int32
    )
    page_start = jax.lax.bitcast_convert_type(
        meta32[3 * m_pad : 3 * m_pad + p_pad], jnp.int32
    )
    if nbits == 32:
        mb_min = meta32[3 * m_pad + p_pad : 4 * m_pad + p_pad]
        page_first = meta32[4 * m_pad + p_pad : 4 * m_pad + 2 * p_pad]
        words = meta32[4 * m_pad + 2 * p_pad :]
    else:
        mb_min = wide[:m_pad]
        page_first = wide[m_pad : m_pad + p_pad]
        words = wide[m_pad + p_pad :]
    ut = jnp.uint32 if nbits == 32 else jnp.uint64
    i = jnp.arange(num_values, dtype=jnp.int32)
    with jax.named_scope("find_block"):
        mb_base = mb_bit_start - mb_out_start * mb_width.astype(jnp.int32)
        w = _spread(mb_out_start, mb_width, num_values)
        base = _spread(mb_out_start, mb_base, num_values)
        min_delta = _spread(mb_out_start, mb_min, num_values)
        is_start = (
            jnp.zeros(num_values, dtype=jnp.int32)
            .at[page_start]
            .add(1, mode="drop", indices_are_sorted=True)
        ) != 0
    with jax.named_scope("unpack"):
        bitpos = base + i * w.astype(jnp.int32)
        w0 = jnp.clip(bitpos >> (nbits.bit_length() - 1), 0, words.shape[0] - 2)
        s = (bitpos & (nbits - 1)).astype(ut)
        lo = words[w0] >> s
        hi = jnp.where(
            s == 0, ut(0), words[w0 + 1] << ((nbits - s) & (nbits - 1))
        )
        mask = jnp.where(
            w >= nbits, ~ut(0), (ut(1) << (w & (nbits - 1)).astype(ut)) - 1
        )
        d = ((lo | hi) & mask) + min_delta
        d = jnp.where(is_start, ut(0), d)
    with jax.named_scope("prefix_sum"):
        c = prefix_sum(d)
    with jax.named_scope("rebase"):
        at_start = c[jnp.minimum(page_start, num_values - 1)]
        vals = c + _spread(page_start, page_first - at_start, num_values)
    return jax.lax.bitcast_convert_type(vals, jnp.int32 if nbits == 32 else jnp.int64)


class FrozenDelta(NamedTuple):
    """One upload of delta_packed_decode_device (built in prepare, dispatched
    by transfer) with the kernel's static arguments; `total` values are real."""

    meta32: np.ndarray
    wide: np.ndarray
    nbits: int
    n_pad: int
    m_pad: int
    p_pad: int
    total: int


def pack_delta_upload(
    widths, bit_starts, out_starts, mins, page_starts, page_firsts, stream,
    nbits: int, total: int,
) -> FrozenDelta:
    """The uploads delta_packed_decode_device reads. One row per miniblock:
    its bit width, the bit offset of its payload in `stream` (the pages' wire
    bytes end to end; uint8 array or bytes), the output position of its first
    delta (one past its page's first value), its block's min_delta; one row
    per page, none of them empty: the output position of its first value and
    that value; `total` values in all. At most TWO uploads — one when nbits
    is 32 — because per-transfer latency on the link dwarfs their size; with
    m_pad = _bucket(miniblocks, 64), p_pad = _bucket(pages, 64), n_pad =
    _bucket(total):
      meta32  [widths(m_pad) | bit_starts(m_pad) | out_starts(m_pad) |
              page_start(p_pad)], int32 fields as bit patterns, padding
              entries of the two start tables n_pad + 1
      wide    [mins(m_pad) | page_first(p_pad) | wire words + 1 guard word,
              padded to _bucket(words, 1024)] in the value's width
    For nbits 32, `wide` is the tail of meta32 (followed by m_pad + p_pad
    words of slack that nothing reads: the compiled shapes are keyed by the
    length) and the second array is empty."""
    ud = np.uint32 if nbits == 32 else np.uint64
    m, p = len(widths), len(page_starts)
    n_pad = _bucket(total)
    m_pad = _bucket(max(m, 1), 64)
    p_pad = _bucket(p, 64)
    words = (bytes_to_words32 if nbits == 32 else bytes_to_words64)(bytes(stream))
    head = 3 * m_pad + p_pad
    tail = m_pad + p_pad + _bucket(len(words), 1024)
    meta32 = np.zeros(head + (tail + m_pad + p_pad if nbits == 32 else 0), dtype=np.uint32)
    meta32[2 * m_pad : head] = np.int32(n_pad + 1).view(np.uint32)
    for row, field in enumerate((widths, bit_starts, out_starts)):
        meta32[row * m_pad : row * m_pad + m] = np.asarray(field).astype(np.uint32)
    meta32[3 * m_pad : 3 * m_pad + p] = np.asarray(page_starts).astype(np.uint32)
    wide = meta32[head:] if nbits == 32 else np.zeros(tail, dtype=np.uint64)
    wide[:m] = np.asarray(mins).astype(ud)
    wide[m_pad : m_pad + p] = np.asarray(page_firsts).astype(ud)
    wide[m_pad + p_pad : m_pad + p_pad + len(words)] = words
    return FrozenDelta(
        meta32, wide if nbits == 64 else np.zeros(0, dtype=np.uint32),
        nbits, n_pad, m_pad, p_pad, total,
    )


@jax.jit
@jax.named_scope("pqt.bss_transpose")
def _bss_transpose_padded(streams: jnp.ndarray) -> jnp.ndarray:
    m = streams.transpose()  # (n_pad, 4) uint8, one value per row
    return jax.lax.bitcast_convert_type(m, jnp.uint32)


def bss_transpose_device(streams: jnp.ndarray, num_values: int) -> jnp.ndarray:
    """BYTE_STREAM_SPLIT de-interleave ON DEVICE for 4-byte types: the
    page's 4 byte streams arrive as a (4, n_pad) uint8 array (each row one
    stream, bucket-padded); a transpose + one bitcast yields uint32 bit
    patterns (parquet-format Encodings.md BYTE_STREAM_SPLIT; host
    analogue: ops/byte_stream_split.decode). The jitted part sees ONLY the
    padded shape — pages with different non-null counts in the same bucket
    share one compilation; the slice below is a device-side view."""
    return _bss_transpose_padded(streams)[:num_values]


@jax.jit
@jax.named_scope("pqt.dict_gather")
def dict_gather_device(dictionary: jnp.ndarray, indices: jnp.ndarray) -> jnp.ndarray:
    """Dictionary expansion: one gather (reference: type_dict.go lookup loop)."""
    return dictionary[indices]


@jax.jit
@jax.named_scope("pqt.double_narrow")
def double_narrow_device(bits: jnp.ndarray) -> jnp.ndarray:
    """IEEE-754 binary64 bit patterns (uint64) -> the binary32 patterns
    (uint32) of their round-to-nearest-even narrowing: bit for bit numpy's
    astype(float32) — +-inf on overflow, f32 subnormals, -0.0 kept; a NaN
    stays a NaN (quiet, the payload's top 22 bits kept).

    Integer arithmetic only, and that is the point: a TPU has no f64 (XLA
    emulates it as an f32 pair, an ulp off — pipeline.DeviceDoubleError),
    so `astype` on the device would narrow a value that is already wrong.
    The 53-bit significand (implicit one included) shifts right by 29 for a
    normal result, by 30 - e32 when the result is an f32 subnormal (e32 =
    biased exponent - 896 <= 0), and rounds on what fell off: up when it is
    over half, or exactly half with the kept part odd. Adding the kept
    significand — implicit one at bit 23 — to (e32 - 1) << 23 lets a
    rounding carry walk into the exponent by itself: the largest subnormal
    rounds to the smallest normal, the largest normal to infinity. A shift
    of 54 or more keeps nothing and rounds to zero (the value is under half
    the smallest subnormal), so the shift is clamped to 63."""
    bits = bits.astype(jnp.uint64)
    sign = ((bits >> 63) << 31).astype(jnp.uint32)
    e = ((bits >> 52) & jnp.uint64(0x7FF)).astype(jnp.int32)
    mant = bits & jnp.uint64((1 << 52) - 1)
    e32 = e - 896
    sig = jnp.where(e == 0, mant, mant | jnp.uint64(1 << 52))
    sh = jnp.where(e32 >= 1, 29, jnp.minimum(30 - e32, 63)).astype(jnp.uint64)
    kept = (sig >> sh).astype(jnp.uint32)
    rem = sig & ((jnp.uint64(1) << sh) - 1)
    half = jnp.uint64(1) << (sh - 1)
    up = (rem > half) | ((rem == half) & ((kept & 1) == 1))
    base = jnp.where(e32 >= 1, (e32 - 1) << 23, 0).astype(jnp.uint32)
    out = base + kept + up.astype(jnp.uint32)
    out = jnp.where(e32 >= 255, jnp.uint32(0x7F800000), out)  # overflow, inf
    nan = jnp.uint32(0x7FC00000) | (mant >> 29).astype(jnp.uint32)
    return sign | jnp.where((e == 2047) & (mant != 0), nan, out)


@jax.jit
@jax.named_scope("pqt.record_starts")
def record_starts_device(rep: jnp.ndarray):
    """Record assembly scan 1: which record each level entry belongs to.

    The device formulation of ops/levels.rows_from_rep / slot_ids at the
    root: an entry starts a record iff rep == 0, so row_of = inclusive
    prefix count of starts, minus one. Returns (row_of int32[n], n_rows
    int32 scalar) — both stay on device for downstream ragged-batch math."""
    starts = (rep == 0).astype(jnp.int32)
    row_of = prefix_sum(starts) - 1
    return row_of, jnp.sum(starts)


@jax.jit
@jax.named_scope("pqt.list_layout")
def list_layout_device(
    rep: jnp.ndarray,  # int32[n]: repetition levels of one leaf
    dfl: jnp.ndarray,  # int32[n]: definition levels of the same leaf
    parent_rep,  # int32 scalar: the expanded node's PARENT repetition depth
    elem_def,  # int32 scalar: def threshold at which an element exists
) -> tuple:
    """One nesting depth's offsets/validity from device-resident level
    streams — the jittable twin of ops/levels.list_layout composed with
    slot_ids, so level streams decoded (or delivered) on device assemble
    into an Arrow-style layout WITHOUT a host round-trip (the host analogue
    walks these same arrays in core/assembly_vec.py).

    An entry opens a slot iff rep <= parent_rep; it starts an element of
    this depth iff additionally-or-independently rep <= parent_rep + 1 AND
    dfl >= elem_def (below elem_def the entry is the placeholder of an
    empty or null list). All prefix sums are prefix_sum; the per-slot
    element counts are one scatter-add — the shapes XLA executes well
    (SURVEY §7.2 M3).

    Returns (offsets, first_def, n_slots):
      offsets    int32[n + 1]  element-count prefix sums; entries past
                               n_slots repeat the total (padding)
      first_def  int32[n]      each slot's first entry's def level (feed
                               `first_def < null_def` for the node's null
                               mask); entries past n_slots are 0
      n_slots    int32 scalar  true slot count
    """
    n = rep.shape[0]
    boundary = rep <= parent_rep
    slot_of = prefix_sum(boundary.astype(jnp.int32)) - 1
    exists = dfl >= elem_def
    elem_start = (rep <= parent_rep + 1) & exists
    counts = (
        jnp.zeros(n, dtype=jnp.int32)
        .at[jnp.clip(slot_of, 0, n - 1)]
        .add(elem_start.astype(jnp.int32))
    )
    offsets = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32), prefix_sum(counts)]
    )
    first_def = (
        jnp.zeros(n, dtype=jnp.int32)
        .at[jnp.clip(slot_of, 0, n - 1)]
        .add(jnp.where(boundary, dfl, 0).astype(jnp.int32))
    )
    return offsets, first_def, jnp.sum(boundary.astype(jnp.int32))


# -- sequence packing: a LIST<int> leaf as fixed [sequences, seq_len] batches ----
#
# The stream of a token corpus is every document's elements in row order; a
# training step wants it cut every seq_len tokens, with segment ids and
# positions that restart at document boundaries. The packer's state is a
# carry of fewer than `span` = batch * seq_len tokens and their start flags;
# a row group appends to it (pack_append_device), whole batches are cut out
# of the work buffers it returns (pack_emit_device) and what is left becomes
# the next carry (pack_carry_device). No shape below follows the data: the
# values come at their bucketed length, the lengths at theirs, and every
# count is a runtime scalar. Integer arithmetic only. core/packing.py drives
# the three; benchmark/lib/reference_packed.py states the semantics in numpy.


def _scan(x: jnp.ndarray, op) -> jnp.ndarray:
    """Inclusive scan of a 1-D array of non-negative int32 under `op`
    (jnp.add or jnp.maximum; 0 is the identity of both here), in prefix_sum's
    two levels, but each level by doubling: log2(row) steps of `x = op(x, x
    shifted right by k)`. Every step is an elementwise op on a slice, so the
    whole scan stays under the caller's named scope in the device trace;
    jnp.cumsum / lax.cummax lower to reduce_window, which XLA:TPU rewrites
    into ops that carry no scope at all (PERF.md section 3), and the packer's
    time would be read at a fifth of what it is. Twenty passes over a batch
    of 2^19 slots instead of a windowed reduction's few, and faster on a v5e:
    pack_emit_device 0.053 ms a batch against 0.28 with prefix_sum and a
    two-level lax.cummax (PERF.md section 6, PR 33). A second scan beside
    prefix_sum until one of them is measured in the other's callers and goes
    (ROADMAP 3.19)."""
    n = x.shape[0]
    if n > _SCAN_BLOCK:
        pad = (-n) % _SCAN_BLOCK
        if pad:
            x = jnp.concatenate([x, jnp.zeros(pad, x.dtype)])
        x = x.reshape(-1, _SCAN_BLOCK)
    k = 1
    while k < x.shape[-1]:
        x = op(x, jnp.concatenate([jnp.zeros_like(x[..., :k]), x[..., :-k]], axis=-1))
        k *= 2
    if x.ndim == 1:
        return x
    totals = _scan(x[:, -1], op)
    before = jnp.concatenate([jnp.zeros(1, x.dtype), totals[:-1]])
    return op(x, before[:, None]).reshape(-1)[:n]


@jax.jit
@jax.named_scope("pqt.pack_sequences")
def pack_append_device(
    carry_tokens: jnp.ndarray,  # int32[span]: the stream's tail not yet emitted
    carry_flags: jnp.ndarray,  # int32[span]: 1 where a document starts
    values: jnp.ndarray,  # int32|int64[n_pad]: a row group's elements, padded
    lengths: jnp.ndarray,  # int32[d_pad]: its documents' lengths, zero-padded
    carry_n,  # int32 scalar: valid slots of the carry (< span)
) -> tuple:
    """Append one row group to the packer's carry: (tokens, flags), two
    int32[2 * span + n_pad] work buffers whose first carry_n + sum(lengths)
    slots are the stream so far. The padded values land at carry_n in one
    dynamic_update_slice; what they carry past their true count is garbage
    that the next append overwrites or the last emit masks, so nothing ever
    reads a slot at or past the fill. A document's first token is flagged by
    one scatter-add at carry_n + the exclusive prefix sum of the lengths;
    empty (and null) documents add a 0 at their successor's start. The
    buffers leave `span` slots of room past the furthest fill, so that the
    carry can be sliced out at any whole-batch offset without clamping."""
    span = carry_tokens.shape[0]
    room = span + values.shape[0]
    with jax.named_scope("append"):
        tokens = jnp.concatenate([carry_tokens, jnp.zeros(room, jnp.int32)])
        tokens = jax.lax.dynamic_update_slice(
            tokens, values.astype(jnp.int32), (carry_n,)
        )
    with jax.named_scope("flags"):
        kept = jnp.where(jnp.arange(span, dtype=jnp.int32) < carry_n, carry_flags, 0)
        starts = carry_n + _scan(lengths, jnp.add) - lengths
        flags = (
            jnp.concatenate([kept, jnp.zeros(room, jnp.int32)])
            .at[starts]
            .add((lengths > 0).astype(jnp.int32), mode="drop", indices_are_sorted=True)
        )
    return tokens, flags


@partial(jax.jit, static_argnames=("batch", "seq_len"))
@jax.named_scope("pqt.pack_sequences")
def pack_emit_device(
    tokens: jnp.ndarray, flags: jnp.ndarray, offset, n_valid, batch: int, seq_len: int
) -> tuple:
    """One batch out of pack_append_device's work buffers: (tokens,
    segment_ids, positions), each int32[batch, seq_len], from the
    batch * seq_len slots at `offset`; slots at or past n_valid (the file's
    last, padded sequence) read 0 in all three. A piece starts at every
    sequence's slot 0 and at every flagged slot: segment_ids counts the
    piece starts of its sequence up to the slot (>= 1 on a real token),
    positions is the distance to the latest one. Both are scans over the
    flat batch (_scan) — every sequence opens a piece, so neither leaks
    across sequences: a prefix sum of the starts, rebased per sequence, and
    a running maximum of the starts' own slot numbers."""
    span = batch * seq_len
    with jax.named_scope("emit"):
        t = jax.lax.dynamic_slice(tokens, (offset,), (span,))
        f = jax.lax.dynamic_slice(flags, (offset,), (span,))
        i = jnp.arange(span, dtype=jnp.int32)
        start = (f != 0) | (i % seq_len == 0)
        count = _scan(start.astype(jnp.int32), jnp.add).reshape(batch, seq_len)
        segment = count - count[:, :1] + 1
        position = i - _scan(jnp.where(start, i, 0), jnp.maximum)
        valid = i < n_valid
        return (
            jnp.where(valid, t, 0).reshape(batch, seq_len),
            jnp.where(valid.reshape(batch, seq_len), segment, 0),
            jnp.where(valid, position, 0).reshape(batch, seq_len),
        )


@partial(jax.jit, static_argnames=("span",))
@jax.named_scope("pqt.pack_sequences")
def pack_carry_device(tokens: jnp.ndarray, flags: jnp.ndarray, offset, span: int) -> tuple:
    """What the emitted batches left of the work buffers, as the next
    append's carry: the `span` slots at `offset` of both."""
    with jax.named_scope("carry"):
        return (
            jax.lax.dynamic_slice(tokens, (offset,), (span,)),
            jax.lax.dynamic_slice(flags, (offset,), (span,)),
        )


# -- query push-down: predicate -> mask -> gather, device-resident --------------


@partial(jax.jit, static_argnames=("op", "exact"))
@jax.named_scope("pqt.predicate_mask")
def predicate_mask_device(values: jnp.ndarray, op: str, lo, hi, exact: bool = True):
    """One leaf predicate as a device boolean mask — the jittable twin of
    core/filter_vec's bracket comparison, so residual filtering of
    device-resident columns (read_row_group_device / DeviceColumn values)
    never round-trips the host.

    `lo`/`hi` bracket the filter value in the column's physical domain
    exactly like normalize_filters computes them; `exact` (static) is
    lo == hi — an inexact bracket means the value falls BETWEEN
    representable stored values, so equality is impossible and ordered ops
    use the end that stays exact. Masks combine with & / | (conjunction /
    DNF) and feed mask_take_device for the gather."""
    if op == "==":
        return (values == lo) if exact else jnp.zeros(values.shape, dtype=bool)
    if op == "!=":
        return (values != lo) if exact else jnp.ones(values.shape, dtype=bool)
    if op == "<":
        return (values < lo) if exact else (values <= lo)
    if op == "<=":
        return values <= lo
    if op == ">":
        return (values > hi) if exact else (values >= hi)
    if op == ">=":
        return values >= hi
    raise ValueError(f"predicate_mask_device: unsupported op {op!r}")


@jax.jit
@jax.named_scope("pqt.list_contains_mask")
def list_contains_mask_device(
    rep: jnp.ndarray,  # int32[n]: repetition levels of one LIST leaf
    dfl: jnp.ndarray,  # int32[n]: definition levels of the same leaf
    dense_match: jnp.ndarray,  # bool[nv]: equality mask over the DENSE values
    elem_def,  # int32 scalar: def level at which an element is present
):
    """('tags', 'contains', x) at the list-slot level, on device: the dense
    per-element equality mask scatters through the level streams to row
    membership — the same record-start prefix scan as record_starts_device
    composed with the validity gather of list_layout_device. Returns
    (rows bool[n], n_rows int32): entries past n_rows are padding."""
    n = rep.shape[0]
    valid = dfl == elem_def
    didx = jnp.clip(
        prefix_sum(valid.astype(jnp.int32)) - 1,
        0,
        max(dense_match.shape[0] - 1, 0),
    )
    if dense_match.shape[0]:
        entry_match = valid & dense_match[didx]
    else:
        entry_match = jnp.zeros(n, dtype=bool)
    starts = (rep == 0).astype(jnp.int32)
    row_of = prefix_sum(starts) - 1
    # scattered as int32: the same scatter over bool takes XLA:TPU 17 s to
    # compile at a 1M-row group (PERF.md, PR 21)
    rows = (
        jnp.zeros(n, dtype=jnp.int32)
        .at[jnp.clip(row_of, 0, max(n - 1, 0))]
        .max(entry_match.astype(jnp.int32))
    ) > 0
    return rows, jnp.sum(starts)


@partial(jax.jit, static_argnames=("out_pad",))
@jax.named_scope("pqt.mask_take")
def mask_take_device(values: jnp.ndarray, mask: jnp.ndarray, out_pad: int):
    """Compact `values[mask]` into a static out_pad-sized buffer on device
    (the gather stage of predicate -> mask -> gather; static shapes bound
    the compile count, SURVEY §7.1). Returns (taken, count): positions past
    `count` hold values[0] as padding — callers slice on the host after a
    (tiny) count fetch, or carry (taken, count) into downstream masked
    kernels unsliced."""
    n = values.shape[0]
    pos = prefix_sum(mask.astype(jnp.int32)) - 1
    tgt = jnp.where(mask, pos, out_pad)
    src = (
        jnp.zeros(out_pad + 1, dtype=jnp.int32)
        .at[jnp.clip(tgt, 0, out_pad)]
        .max(jnp.arange(n, dtype=jnp.int32))[:out_pad]
    )
    taken = values[src] if n else jnp.zeros((out_pad,), values.dtype)
    return taken, jnp.sum(mask.astype(jnp.int32))


# -- write path: device ENCODE kernels (inverses of the decode formulations) ----


@partial(jax.jit, static_argnames=("width",))
@jax.named_scope("pqt.bitpack_encode")
def bitpack_encode_device(values: jnp.ndarray, width: int) -> jnp.ndarray:
    """LSB-first bit-pack of uint32 `values` at `width` bits — the jittable
    inverse of the two-gather unpack at the top of this module (and of
    ops/bitpack.pack_bits on host). Value i lands at bits
    [i*width, (i+1)*width): each value splits into a lo/hi uint32 word
    contribution and one scatter-add assembles the stream (contributions
    occupy disjoint bits, so add IS or and no carries can occur).

    Returns uint32 LE words covering ceil(n*width/32) (+1 guard word of
    zeros, mirroring bytes_to_words32); the host trims the byte tail.
    The caller pads `values` to a multiple of 8 where the hybrid format
    requires whole groups (pack_bits has the same contract)."""
    n = values.shape[0]
    if width == 0 or n == 0:
        return jnp.zeros(1, dtype=jnp.uint32)
    n_words = (n * width + 31) // 32 + 1
    i = jnp.arange(n, dtype=jnp.int32)
    bitpos = i * width
    w0 = bitpos >> 5
    s = (bitpos & 31).astype(jnp.uint64)
    v = values.astype(jnp.uint64) << s
    lo = (v & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (v >> jnp.uint64(32)).astype(jnp.uint32)
    words = (
        jnp.zeros(n_words, dtype=jnp.uint32)
        .at[w0]
        .add(lo)
        .at[jnp.minimum(w0 + 1, n_words - 1)]
        .add(hi)
    )
    return words


@partial(jax.jit, static_argnames=("width",))
@jax.named_scope("pqt.rle_hybrid_encode")
def rle_hybrid_encode_device(values: jnp.ndarray, width: int):
    """The device half of hybrid RLE/bit-pack ENCODE — the inverse of
    expand_hybrid_device, mirroring ops/rle_hybrid.encode_hybrid's run
    policy exactly: an 8-aligned window of >= 8 identical values becomes an
    RLE run; everything else bit-packs in groups of 8.

    All the per-value work happens here with static shapes: run discovery
    (one boundary scan + prefix sums), the 8-aligned RLE-window arithmetic
    per position, compaction of the bit-packed positions, and the packed
    payload itself (bitpack_encode_device over the compacted stream — legal
    as ONE pack because every mid-stream segment covers whole groups of 8,
    so concatenating per-segment payloads equals packing the compacted
    sequence, zero-padded only at the very end). What remains on host is
    header emission over the (few) segments — the write-side twin of the
    prescan/expand split on the read side.

    Returns (in_rle bool[n], rle_break bool[n], packed uint32 words,
    n_bp int32 scalar): in_rle marks positions covered by an RLE window;
    rle_break marks the first position of each window (adjacent windows
    from DIFFERENT runs are separate RLE runs on the wire — a flat mask
    alone would fuse them); packed holds the bit-packed payload of the
    remaining positions in order; n_bp counts them.
    kernels/pipeline.assemble_hybrid_device_stream turns this into the
    exact encode_hybrid byte stream."""
    n = values.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)
    if n == 0:
        return (
            jnp.zeros(0, dtype=bool),
            jnp.zeros(0, dtype=bool),
            jnp.zeros(1, dtype=jnp.uint32),
            jnp.int32(0),
        )
    boundary = jnp.concatenate(
        [jnp.ones(1, dtype=bool), values[1:] != values[:-1]]
    )
    run_of = prefix_sum(boundary.astype(jnp.int32)) - 1
    # per-position run extent via segment scatter of starts/ends
    run_start = (
        jnp.full(n, n, dtype=jnp.int32).at[run_of].min(jnp.where(boundary, i, n))
    )[run_of]
    run_end = (
        jnp.zeros(n, dtype=jnp.int32).at[run_of].max(i + 1)
    )[run_of]
    rle_s = (run_start + 7) & ~7
    rle_e = run_end & ~7
    qualifies = (run_end - run_start >= 8) & (rle_e - rle_s >= 8)
    in_rle = qualifies & (i >= rle_s) & (i < rle_e)
    rle_break = in_rle & (i == rle_s)
    n_bp = jnp.sum(~in_rle)
    # compact the bit-packed positions (stable order), pad tail with zeros
    # so the trailing partial group packs its zero padding
    pos = prefix_sum((~in_rle).astype(jnp.int32)) - 1
    tgt = jnp.where(~in_rle, pos, n)
    src = (
        jnp.full(n + 1, -1, dtype=jnp.int32)
        .at[jnp.clip(tgt, 0, n)]
        .max(i)[:n]
    )
    bp_vals = jnp.where(src >= 0, values[jnp.clip(src, 0, n - 1)], 0).astype(
        jnp.uint32
    )
    packed = bitpack_encode_device(bp_vals, width)
    return in_rle, rle_break, packed, n_bp.astype(jnp.int32)


@jax.jit
@jax.named_scope("pqt.dict_indices")
def dict_indices_device(values: jnp.ndarray):
    """First-occurrence dictionary probe on device — the jittable inverse of
    dict_gather_device and the twin of the host u64/bytes probes (same
    first-occurrence unique order, so the dictionary PAGE bytes match).
    `values` must already be the column's uniqueness domain (bit patterns
    for floats, like build_dictionary's view). Static shapes throughout:

      sort -> group boundaries -> group id -> first-occurrence row per
      group (segment min) -> dictionary rank = order of groups by first
      occurrence -> per-row index gather.

    Returns (indices int32[n], firsts int32[n], n_uniques int32): firsts
    holds each unique's first row in dictionary order, padded with n past
    n_uniques; dictionary value k is values[firsts[k]]."""
    n = values.shape[0]
    if n == 0:
        return (
            jnp.zeros(0, dtype=jnp.int32),
            jnp.zeros(0, dtype=jnp.int32),
            jnp.int32(0),
        )
    order = jnp.argsort(values, stable=True).astype(jnp.int32)
    sv = values[order]
    newg = jnp.concatenate([jnp.ones(1, dtype=bool), sv[1:] != sv[:-1]])
    gid_sorted = prefix_sum(newg.astype(jnp.int32)) - 1
    n_uniques = gid_sorted[-1] + 1
    # first occurrence row of each (sorted-domain) group
    first_of_group = (
        jnp.full(n, n, dtype=jnp.int32).at[gid_sorted].min(order)
    )
    # dictionary order = groups sorted by first occurrence; unused group
    # slots carry n and sort last
    perm = jnp.argsort(first_of_group, stable=True).astype(jnp.int32)
    rank = jnp.zeros(n, dtype=jnp.int32).at[perm].set(jnp.arange(n, dtype=jnp.int32))
    gid = jnp.zeros(n, dtype=jnp.int32).at[order].set(gid_sorted)
    indices = rank[gid]
    firsts = first_of_group[perm]
    return indices, firsts, n_uniques.astype(jnp.int32)


@partial(jax.jit, static_argnames=("nbits",))
@jax.named_scope("pqt.delta_block_encode")
def delta_block_encode_device(values: jnp.ndarray, n, nbits: int):
    """DELTA_BINARY_PACKED block scans + payload pack on device — the encode
    inverse of delta_packed_decode_device, mirroring ops/delta.encode_delta's
    block policy exactly (block_size=128, mini_count=4, mini_len=32).

    `values` is one page's int32/int64 (or uint bit-pattern) slice padded to a
    static multiple of 128; `n` (traced) is the true value count, so one
    compilation serves every page in a pad bucket (SURVEY §7.1). The whole
    sequential structure dissolves into segment reductions: wrapping unsigned
    deltas (one shifted subtract), per-block signed min (one reshape min),
    per-miniblock max-of-adjusted -> bit width (one reshape max + clz), and
    the byte-aligned payload itself as one scatter-add of lo/hi word
    contributions (mini_len=32 makes every miniblock payload 4*width bytes,
    so payloads butt together byte-aligned at cumsum(4*width) offsets).

    Returns (mins, widths, words):
      mins    int32/int64[p_pad/128]  per-block min delta, signed; blocks
                                      past the last real delta carry INT_MAX
      widths  int32[p_pad/32]         per-miniblock bit width; minis with no
                                      real deltas carry 0
      words   uint32 LE words         payload stream at cumsum(4*width) byte
                                      offsets (+ guard words)
    kernels/pipeline.assemble_delta_device_stream frames these into the exact
    encode_delta byte stream (uvarint header + per-block min/widths/payload)."""
    p_pad = values.shape[0]
    ut = jnp.uint32 if nbits == 32 else jnp.uint64
    st = jnp.int32 if nbits == 32 else jnp.int64
    u = jax.lax.bitcast_convert_type(values, ut)
    i = jnp.arange(p_pad, dtype=jnp.int32)
    nd = n - 1  # delta count
    valid = i < nd
    d = jnp.where(valid, jnp.roll(u, -1) - u, ut(0))
    sd = jax.lax.bitcast_convert_type(d, st)
    n_blocks = p_pad // 128
    mins = jnp.min(
        jnp.where(valid, sd, jnp.iinfo(st).max).reshape(n_blocks, 128), axis=1
    )
    adj = jnp.where(
        valid, d - jax.lax.bitcast_convert_type(mins, ut)[i >> 7], ut(0)
    )
    n_minis = p_pad // 32
    amax = jnp.max(adj.reshape(n_minis, 32), axis=1)
    widths = jnp.where(
        amax == 0, ut(0), ut(nbits) - jax.lax.clz(amax).astype(ut)
    ).astype(jnp.int32)
    pay_start = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int32), prefix_sum(4 * widths)]
    )
    m = i >> 5
    w = widths[m]
    bitpos = pay_start[m] * 8 + (i & 31) * w
    n_words = n_minis * nbits + 2
    w0 = jnp.clip(bitpos >> 5, 0, n_words - 2)
    s = (bitpos & 31).astype(jnp.uint64)
    vlo = (adj & ut(0xFFFFFFFF)).astype(jnp.uint64) << s
    words = (
        jnp.zeros(n_words, dtype=jnp.uint32)
        .at[w0]
        .add((vlo & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
        .at[w0 + 1]
        .add((vlo >> jnp.uint64(32)).astype(jnp.uint32))
    )
    if nbits == 64:
        # widths past 32 bits: the hi half of each delta lands 32 bits later
        # (disjoint bits again: add is or)
        vhi = (adj >> ut(32)).astype(jnp.uint64) << s
        w1 = jnp.clip(w0 + 1, 0, n_words - 2)
        words = (
            words.at[w1]
            .add((vhi & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
            .at[w1 + 1]
            .add((vhi >> jnp.uint64(32)).astype(jnp.uint32))
        )
    return mins, widths, words


@partial(jax.jit, static_argnames=("out_pad",))
@jax.named_scope("pqt.plain_bytearray_encode")
def plain_bytearray_encode_device(
    data: jnp.ndarray,  # uint8: dense value bytes
    offsets: jnp.ndarray,  # int32/int64[nv + 1]: value byte offsets
    n,  # int32 scalar: true value count (entries past it are padding)
    out_pad: int,  # static bucketed output byte capacity
) -> jnp.ndarray:
    """PLAIN BYTE_ARRAY framing on device: `<4-byte LE length><bytes>` per
    value, the encode inverse of the merge_mixed_bytes_device gather. One
    searchsorted maps every output byte to its value; headers materialize
    from the offset diffs and payload bytes gather straight out of `data` —
    no per-value host loop, and PLAIN streams concatenate, so the host
    slices page sub-ranges out of ONE framed chunk stream at
    4*a + offsets[a]. Bytes past 4*n + offsets[n] are zero padding."""
    nv = offsets.shape[0] - 1
    v_idx = jnp.arange(offsets.shape[0], dtype=jnp.int64)
    off = offsets.astype(jnp.int64)
    off = jnp.where(v_idx <= n, off, off[jnp.int64(n)])
    fout = 4 * jnp.minimum(v_idx, jnp.int64(n)) + off
    total = fout[jnp.int64(n)]
    pos = jnp.arange(out_pad, dtype=jnp.int64)
    v = jnp.clip(
        jnp.searchsorted(fout[1:], pos, side="right"), 0, max(nv - 1, 0)
    )
    rel = pos - fout[v]
    ln = off[v + 1] - off[v]
    hdr = ((ln >> (8 * jnp.clip(rel, 0, 3))) & 0xFF).astype(jnp.uint8)
    db = data[jnp.clip(off[v] + rel - 4, 0, max(data.shape[0] - 1, 0))]
    if data.shape[0] == 0:
        db = jnp.zeros(out_pad, dtype=jnp.uint8)
    return jnp.where(pos < total, jnp.where(rel < 4, hdr, db), jnp.uint8(0))


@partial(jax.jit, static_argnames=("op",))
@jax.named_scope("pqt.masked_agg")
def masked_agg_device(values: jnp.ndarray, mask: jnp.ndarray, op: str):
    """One aggregation unit's partial as ONE jnp reduction over the resident
    row mask (count/sum/min/max) — the device half of serve/aggregate's
    unit_partial; the exact pyarrow-pinned cross-group merge stays on host.
    sum accumulates in the 64-bit domain like pyarrow's sum kernel (the
    caller pre-casts to int64/uint64); min/max mask losers with the dtype's
    identity, so a matched count of zero means the scalar is garbage — the
    caller must gate on count > 0 (serve/aggregate_device does)."""
    if op == "count":
        return jnp.sum(mask.astype(jnp.int64))
    if op == "sum":
        return jnp.sum(jnp.where(mask, values, values.dtype.type(0)))
    if jnp.issubdtype(values.dtype, jnp.integer):
        info = jnp.iinfo(values.dtype)
        lose = info.max if op == "min" else info.min
    else:
        lose = jnp.inf if op == "min" else -jnp.inf
    masked = jnp.where(mask, values, values.dtype.type(lose))
    if op == "min":
        return jnp.min(masked)
    if op == "max":
        return jnp.max(masked)
    raise ValueError(f"masked_agg_device: unsupported op {op!r}")


@partial(jax.jit, static_argnames=("rows_pad",))
@jax.named_scope("pqt.merge_mixed_numeric")
def merge_mixed_numeric_device(
    idx_all: jnp.ndarray,        # int32[D_pad]: dict-row indices, output order
    dictionary: jnp.ndarray,     # dict values (uint bit patterns for floats)
    plain: jnp.ndarray,          # plain values, page pools concatenated
    page_kind: jnp.ndarray,      # int32[P_pad]: 1 dict page, 0 plain page
    page_row_start: jnp.ndarray, # int32[P_pad + 1]: first output row per page
    page_aux: jnp.ndarray,       # int32[P_pad]: base into idx_all / plain
    rows_pad: int,
) -> jnp.ndarray:
    """Merge a mixed dict/PLAIN numeric chunk in output-index space: dict
    rows gather through idx_all -> dictionary, PLAIN rows read their upload
    directly — one fused program, one dispatch (a per-page slice/concat loop
    costs one host->device dispatch per page over the transfer link). Rows
    past the true count carry padding; the caller slices them off."""
    rows = jnp.arange(rows_pad, dtype=jnp.int32)
    pg = jnp.searchsorted(page_row_start[1:], rows, side="right").astype(jnp.int32)
    pg = jnp.minimum(pg, page_kind.shape[0] - 1)
    rel = rows - page_row_start[pg]
    is_dict = page_kind[pg] == 1
    src = jnp.clip(page_aux[pg] + rel, 0, None)
    dv = dictionary[
        jnp.clip(idx_all[jnp.minimum(src, idx_all.shape[0] - 1)], 0,
                 dictionary.shape[0] - 1)
    ]
    pv = plain[jnp.minimum(src, plain.shape[0] - 1)]
    return jnp.where(is_dict, dv, pv)


@partial(jax.jit, static_argnames=("rows_pad", "total_bytes_pad"))
@jax.named_scope("pqt.merge_mixed_bytes")
def merge_mixed_bytes_device(
    idx_all: jnp.ndarray,        # int32[D_pad]: dict-row indices, output order
    doff: jnp.ndarray,           # int64[n_dict + 1]: dictionary offsets
    src_data: jnp.ndarray,       # uint8: [dict payload | plain page pools]
    po32: jnp.ndarray,           # int32[E_pad]: concatenated plain offset arrays
    page_kind: jnp.ndarray,      # int32[P_pad]: 1 dict page, 0 plain page
    page_row_start: jnp.ndarray, # int32[P_pad + 1]: first output row per page
    page_aux: jnp.ndarray,       # int32[P_pad]: dict: base into idx_all;
                                 #              plain: base ENTRY into po32
    page_src_base: jnp.ndarray,  # int64[P_pad]: plain: pool byte base in src_data
    n_rows: jnp.ndarray,         # int32 scalar: true row count (shape-free)
    rows_pad: int,               # static bucketed row capacity
    total_bytes_pad: int,        # static bucketed output byte capacity
):
    """Materialize a mixed dict/PLAIN byte-array chunk on device.

    Dict pages contribute rows via index gather against the dictionary's
    offsets; PLAIN pages contribute rows via their (int32-compressed) offset
    arrays — only raw page bytes, int32 offsets and tiny per-page tables
    ever cross the host->device link; the per-row source map, the offsets
    cumsum and the final byte materialization are one fused device program.
    Returns (data uint8[total_bytes_pad], offsets int64[rows_pad + 1]);
    entries past n_rows and bytes past offsets[n_rows] are padding (static
    shapes bound the compile count, SURVEY §7.1).
    """
    rows = jnp.arange(rows_pad, dtype=jnp.int32)
    pg = jnp.searchsorted(page_row_start[1:], rows, side="right").astype(jnp.int32)
    pg = jnp.minimum(pg, page_kind.shape[0] - 1)
    rel = rows - page_row_start[pg]
    is_dict = page_kind[pg] == 1
    idx = idx_all[
        jnp.clip(jnp.where(is_dict, page_aux[pg] + rel, 0), 0, idx_all.shape[0] - 1)
    ]
    idx = jnp.clip(idx, 0, doff.shape[0] - 2)
    dstart = doff[idx]
    dlen = doff[idx + 1] - doff[idx]
    e = jnp.clip(jnp.where(is_dict, 0, page_aux[pg] + rel), 0, po32.shape[0] - 2)
    p0 = po32[e].astype(jnp.int64)
    p1 = po32[e + 1].astype(jnp.int64)
    pstart = p0 + page_src_base[pg]
    plen = p1 - p0
    starts = jnp.where(is_dict, dstart, pstart)
    lengths = jnp.where(rows < n_rows, jnp.where(is_dict, dlen, plen), 0)
    lengths = jnp.maximum(lengths, 0)
    off = jnp.concatenate(
        [jnp.zeros(1, dtype=jnp.int64), prefix_sum(lengths.astype(jnp.int64))]
    )
    pos = jnp.arange(total_bytes_pad, dtype=jnp.int64)
    row = jnp.searchsorted(off[1:], pos, side="right")
    row = jnp.minimum(row, rows_pad - 1)
    src = starts[row] + (pos - off[row])
    src = jnp.clip(src, 0, src_data.shape[0] - 1)
    data = jnp.where(pos < off[-1], src_data[src], jnp.uint8(0))
    return data, off
