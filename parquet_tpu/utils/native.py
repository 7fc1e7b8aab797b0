"""Builder + ctypes loader for the optional native C++ helper library (native/).

The native library accelerates the host-side scalar hot spots that neither
NumPy nor the TPU can absorb: snappy (de)compression, PLAIN byte_array offset
scans, and hybrid/delta run-header prescans. Both artifacts — the ctypes
library and the `_native_ext` CPython extension — are build outputs, never
tracked: the first use in a checkout compiles them from native/*.cc|.h|.c
(and again whenever a source is newer than an artifact). Everything degrades
gracefully to the pure-Python implementations when no compiler is available;
callers that must not degrade (the chip smoke, bench.py, `serve --device`)
go through require_native().
"""

from __future__ import annotations

import ctypes
import os
import threading
from pathlib import Path
from typing import NamedTuple

_ROOT = Path(__file__).resolve().parent.parent.parent
_NATIVE_DIR = _ROOT / "native"
_SOURCES = ("parquet_tpu_native.cc", "parquet_tpu_native.h", "pyext.c")
_LIB_PATH = _NATIVE_DIR / "build" / "libparquet_tpu_native.so"
_cached = None
_probed = False
_probe_lock = threading.Lock()
_build_error: str | None = None  # why the last build attempt failed

# ptq_chunk_prepare err_info[0] stage codes (parquet_tpu_native.h PTQ_STAGE_*).
PREPARE_STAGES = {
    0: "none",
    1: "header",
    2: "crc",
    3: "decompress",
    4: "levels",
    5: "prescan",
    6: "values",
}

# ptq_chunk_prepare terminal return codes (parquet_tpu_native.h PTQ_E_*).
PREPARE_E_CORRUPT = -1
PREPARE_E_CAPACITY = -5
PREPARE_E_CRC = -6

# ptq_chunk_encode err_info[0] stage codes (parquet_tpu_native.h PTQ_ENC_STAGE_*).
ENCODE_STAGES = {
    0: "none",
    1: "split",
    2: "levels",
    3: "values",
    4: "compress",
    5: "frame",
}


def hybrid_encode_cap(n: int, width: int) -> int:
    """Worst-case hybrid RLE/bit-pack stream size for n values at `width`
    bits — the ONE sizing formula behind hybrid_encode's output buffer and
    the fused encode walk's capacity planning (a drifted copy would turn
    into silent -5 capacity faults and a quiet staged fallback)."""
    vbytes = (width + 7) // 8
    return 64 + (n // 8 + 2) * (5 + vbytes) + ((n + 7) // 8) * max(width, 1)


def delta_encode_cap(
    n: int, nbits: int, block_size: int = 128, mini_count: int = 4
) -> int:
    """Worst-case DELTA_BINARY_PACKED size: header + per-block zigzag +
    widths + payloads at full width (shared by delta_encode and the fused
    encode walk's capacity planning)."""
    blocks = max(n // block_size + 2, 1)
    return (
        64
        + blocks * (10 + mini_count)
        + ((n + block_size) * nbits) // 8
        + block_size
    )


class EncodeFault(NamedTuple):
    """Structured failure report from the fused native chunk encode: the
    negative return code plus the stage/page context. NOT an exception —
    encode_chunk's fallback ladder retries the chunk on the staged Python
    encoder, which raises the exact typed error if the input is genuinely
    unencodable."""

    code: int
    stage: str
    page: int


class PrepareFault(NamedTuple):
    """Structured failure report from the fused native chunk walk: the
    negative return code (PREPARE_E_*) plus the stage/page/byte-offset
    context the walk recorded when it aborted. NOT an exception — the
    pipeline's fallback ladder retries the chunk on the staged Python walk,
    which raises the exact typed error if the input is genuinely corrupt."""

    code: int
    stage: str
    page: int
    offset: int


def _ptr(data):
    """(address, length, keepalive) for any contiguous readable buffer.

    Lets the hot-path wrappers accept bytes, bytearray, memoryview, or numpy
    arrays without the `bytes(data)` copy a c_char_p signature would force
    (decompressed pages are ~1 MiB each; those copies were measurable).
    """
    import numpy as np

    if isinstance(data, bytes):
        # ctypes converts bytes to a char pointer for c_void_p params directly
        return data, len(data), data
    arr = np.frombuffer(data, dtype=np.uint8)
    return arr.ctypes.data, arr.size, arr


class NativeLib:
    def __init__(self, lib: ctypes.CDLL):
        import threading

        self._lib = lib
        self._chunk_tl = threading.local()  # per-thread chunk_prepare scratch
        self.has_snappy = hasattr(lib, "ptq_snappy_compress")
        if self.has_snappy:
            lib.ptq_snappy_max_compressed_length.restype = ctypes.c_size_t
            lib.ptq_snappy_max_compressed_length.argtypes = [ctypes.c_size_t]
            lib.ptq_snappy_compress.restype = ctypes.c_ssize_t
            lib.ptq_snappy_compress.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
            lib.ptq_snappy_decompress.restype = ctypes.c_ssize_t
            lib.ptq_snappy_decompress.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
        self.has_lz4 = hasattr(lib, "ptq_lz4_compress")
        if self.has_lz4:
            lib.ptq_lz4_max_compressed_length.restype = ctypes.c_size_t
            lib.ptq_lz4_max_compressed_length.argtypes = [ctypes.c_size_t]
            for fn in (
                lib.ptq_lz4_compress,
                lib.ptq_lz4_decompress,
                lib.ptq_lz4_hadoop_decompress,
            ):
                fn.restype = ctypes.c_ssize_t
                fn.argtypes = [
                    ctypes.c_void_p,
                    ctypes.c_size_t,
                    ctypes.c_void_p,
                    ctypes.c_size_t,
                ]
        self.has_xxh64 = hasattr(lib, "ptq_xxh64")
        if self.has_xxh64:
            lib.ptq_xxh64.restype = ctypes.c_uint64
            lib.ptq_xxh64.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_uint64,
            ]
            lib.ptq_xxh64_fixed.restype = None
            lib.ptq_xxh64_fixed.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int,
                ctypes.c_void_p,
            ]
            lib.ptq_xxh64_offsets.restype = None
            lib.ptq_xxh64_offsets.argtypes = [ctypes.c_void_p] * 2 + [
                ctypes.c_int64,
                ctypes.c_void_p,
            ]
            lib.ptq_bloom_insert.restype = None
            lib.ptq_bloom_insert.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_int64,
            ]
            lib.ptq_bloom_check.restype = None
            lib.ptq_bloom_check.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
            ]
        self.has_byte_array_scan = hasattr(lib, "ptq_byte_array_gather")
        if self.has_byte_array_scan:
            lib.ptq_byte_array_gather.restype = ctypes.c_ssize_t
            lib.ptq_byte_array_gather.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
        self.has_hybrid_decode = hasattr(lib, "ptq_hybrid_decode")
        if self.has_hybrid_decode:
            lib.ptq_hybrid_decode.restype = ctypes.c_ssize_t
            lib.ptq_hybrid_decode.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_int64,
                ctypes.c_int,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
        self.has_delta_decode = hasattr(lib, "ptq_delta_decode")
        if self.has_delta_decode:
            lib.ptq_delta_decode.restype = ctypes.c_ssize_t
            lib.ptq_delta_decode.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_int,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
            lib.ptq_delta_peek_total.restype = ctypes.c_ssize_t
            lib.ptq_delta_peek_total.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
            ]
        self.has_bytearray_take = hasattr(lib, "ptq_bytearray_take")
        if self.has_bytearray_take:
            lib.ptq_bytearray_take.restype = ctypes.c_ssize_t
            lib.ptq_bytearray_take.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
        self.has_plain_encode_ba = hasattr(lib, "ptq_plain_encode_bytearray")
        if self.has_plain_encode_ba:
            lib.ptq_plain_encode_bytearray.restype = ctypes.c_ssize_t
            lib.ptq_plain_encode_bytearray.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
        self.has_prescan_delta = hasattr(lib, "ptq_prescan_delta_packed")
        if self.has_prescan_delta:
            lib.ptq_prescan_delta_packed.restype = ctypes.c_ssize_t
            lib.ptq_prescan_delta_packed.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_int,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
        self.has_parse_page_header = hasattr(lib, "ptq_parse_page_header")
        if self.has_parse_page_header:
            lib.ptq_parse_page_header.restype = ctypes.c_ssize_t
            lib.ptq_parse_page_header.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
            ]
        self.has_prescan_hybrid = hasattr(lib, "ptq_prescan_hybrid")
        if self.has_prescan_hybrid:
            lib.ptq_prescan_hybrid.restype = ctypes.c_ssize_t
            lib.ptq_prescan_hybrid.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_int64,
                ctypes.c_int,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
            ]
        self.has_hybrid_encode = hasattr(lib, "ptq_hybrid_encode")
        if self.has_hybrid_encode:
            lib.ptq_hybrid_encode.restype = ctypes.c_ssize_t
            lib.ptq_hybrid_encode.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
        self.has_repack_pages = hasattr(lib, "ptq_repack_pages")
        if self.has_repack_pages:
            lib.ptq_repack_pages.restype = ctypes.c_ssize_t
            lib.ptq_repack_pages.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int,
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
            ]
        self.has_delta_frame = hasattr(lib, "ptq_delta_frame")
        if self.has_delta_frame:
            lib.ptq_delta_frame.restype = ctypes.c_ssize_t
            lib.ptq_delta_frame.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int,
                ctypes.c_uint64,
                ctypes.c_int,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
        self.has_hybrid_frame = hasattr(lib, "ptq_hybrid_frame")
        if self.has_hybrid_frame:
            lib.ptq_hybrid_frame.restype = ctypes.c_ssize_t
            lib.ptq_hybrid_frame.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
        self.has_delta_encode = hasattr(lib, "ptq_delta_encode")
        if self.has_delta_encode:
            lib.ptq_delta_encode.restype = ctypes.c_ssize_t
            lib.ptq_delta_encode.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
        self.has_bytes_dict = hasattr(lib, "ptq_bytes_dict_indices")
        if self.has_bytes_dict:
            lib.ptq_bytes_dict_indices.restype = ctypes.c_ssize_t
            lib.ptq_bytes_dict_indices.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
        self.has_bytes_minmax = hasattr(lib, "ptq_bytes_minmax")
        if self.has_bytes_minmax:
            lib.ptq_bytes_minmax.restype = ctypes.c_ssize_t
            lib.ptq_bytes_minmax.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
            ]
        self.has_u64_dict = hasattr(lib, "ptq_u64_dict_indices")
        if self.has_u64_dict:
            lib.ptq_u64_dict_indices.restype = ctypes.c_ssize_t
            lib.ptq_u64_dict_indices.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
        self.has_gzip_encode = hasattr(lib, "ptq_gzip_compress")
        if self.has_gzip_encode:
            lib.ptq_gzip_compress.restype = ctypes.c_ssize_t
            lib.ptq_gzip_compress.argtypes = [
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
        self.has_chunk_encode = hasattr(lib, "ptq_chunk_encode")
        if self.has_chunk_encode:
            lib.ptq_chunk_encode.restype = ctypes.c_ssize_t
            lib.ptq_chunk_encode.argtypes = (
                [ctypes.c_int]  # route
                + [ctypes.c_void_p, ctypes.c_size_t]  # values
                + [ctypes.c_void_p, ctypes.c_int64]  # ba_offsets, nv
                + [ctypes.c_int, ctypes.c_int]  # type_size, dict_width
                + [ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int64]  # dict
                + [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int]  # def levels
                # codec, dpv, with_crc
                + [ctypes.c_int] * 3
                + [ctypes.c_int64]  # per_page
                + [ctypes.c_void_p, ctypes.c_size_t] * 2  # out, scratch
                + [ctypes.c_void_p, ctypes.c_size_t]  # pages
                + [ctypes.c_void_p] * 3  # totals, stage_ns, err_info
            )
        self.has_chunk_prepare = hasattr(lib, "ptq_chunk_prepare")
        if self.has_chunk_prepare:
            lib.ptq_chunk_prepare.restype = ctypes.c_ssize_t
            lib.ptq_chunk_prepare.argtypes = (
                [ctypes.c_void_p, ctypes.c_size_t]  # src
                # codec, validate_crc, max_def, max_rep, type_size, delta_nbits
                + [ctypes.c_int] * 6
                + [ctypes.c_int64]  # expected_values
                + [ctypes.c_void_p, ctypes.c_size_t]  # pages
                + [ctypes.c_void_p, ctypes.c_void_p]  # def_out, rep_out
                + [ctypes.c_void_p, ctypes.c_size_t] * 4  # values/packed/delta/scratch
                + [ctypes.c_void_p] * 4 + [ctypes.c_size_t]  # hybrid tables
                + [ctypes.c_void_p] * 4 + [ctypes.c_size_t]  # delta tables
                + [ctypes.c_void_p]  # totals
                + [ctypes.c_void_p]  # stage_ns (nullable per-stage clock)
                + [ctypes.c_void_p]  # err_info (nullable int64[4])
            )
        # The CPython-extension binding of the same walk: one call, every
        # buffer through the buffer protocol, the whole walk under
        # Py_BEGIN_ALLOW_THREADS. Preferred over ctypes when built — ctypes
        # marshals ~30 arguments under the GIL per call; the extension
        # binds them in C. Falls back transparently when the extension is
        # absent (ctypes also drops the GIL during the foreign call, so
        # multi-thread prepare stays correct either way, just slower).
        _ext = load_ext()
        self._ext_chunk_prepare = getattr(_ext, "chunk_prepare", None)
        self._ext_chunk_encode = getattr(_ext, "chunk_encode", None)
        self.fused_gil_free = self._ext_chunk_prepare is not None

    def snappy_compress(self, data) -> bytes:
        addr, n_in, _keep = _ptr(data)
        cap = self._lib.ptq_snappy_max_compressed_length(n_in)
        out = ctypes.create_string_buffer(cap)
        n = self._lib.ptq_snappy_compress(addr, n_in, out, cap)
        if n < 0:
            raise ValueError("native snappy: compression failed")
        return out.raw[:n]

    def snappy_decompress(self, data, uncompressed_size: int):
        """Returns a memoryview over a freshly decoded buffer (no memset, no
        trailing copy — the hot path of every snappy page)."""
        import numpy as np

        addr, n_in, _keep = _ptr(data)
        # 64 bytes of slack past the logical size switches the decoder into
        # its overshooting-wide-copy fast mode; the view below hides it
        out = np.empty(max(uncompressed_size, 1) + 64, dtype=np.uint8)
        n = self._lib.ptq_snappy_decompress(
            addr, n_in, ctypes.c_void_p(out.ctypes.data), uncompressed_size + 64
        )
        # n > uncompressed_size: the stream's own length claim exceeded the
        # page header's — corrupt (the pre-slack cap check used to catch it)
        if n < 0 or n > uncompressed_size:
            raise ValueError("native snappy: corrupt input")
        return memoryview(out)[:n]

    def lz4_compress(self, data) -> bytes:
        """One raw LZ4 block (no framing, no size prefix)."""
        addr, n_in, _keep = _ptr(data)
        cap = self._lib.ptq_lz4_max_compressed_length(n_in)
        out = ctypes.create_string_buffer(cap)
        n = self._lib.ptq_lz4_compress(addr, n_in, out, cap)
        if n < 0:
            raise ValueError("native lz4: compression failed")
        return out.raw[:n]

    def lz4_decompress(self, data, uncompressed_size: int, hadoop: bool = False):
        """Decode one raw LZ4 block; hadoop=True also accepts the Hadoop
        [BE usize][BE csize] framing parquet's legacy LZ4 codec uses."""
        import numpy as np

        addr, n_in, _keep = _ptr(data)
        out = np.empty(max(uncompressed_size, 1), dtype=np.uint8)
        fn = (
            self._lib.ptq_lz4_hadoop_decompress
            if hadoop
            else self._lib.ptq_lz4_decompress
        )
        n = fn(addr, n_in, ctypes.c_void_p(out.ctypes.data), uncompressed_size)
        if n < 0:
            raise ValueError("native lz4: corrupt input")
        return memoryview(out)[:n]

    def xxh64(self, data, seed: int = 0) -> int:
        addr, n, _keep = _ptr(data)
        return int(self._lib.ptq_xxh64(addr, n, seed))

    def xxh64_fixed(self, data, n: int, stride: int):
        import numpy as np

        addr, _nb, _keep = _ptr(data)
        out = np.empty(n, dtype=np.uint64)
        self._lib.ptq_xxh64_fixed(addr, n, stride, ctypes.c_void_p(out.ctypes.data))
        return out

    def xxh64_offsets(self, data, offsets):
        import numpy as np

        n = len(offsets) - 1
        addr, _nb, _keep = _ptr(data)
        off = np.ascontiguousarray(offsets, dtype=np.int64)
        out = np.empty(n, dtype=np.uint64)
        self._lib.ptq_xxh64_offsets(
            addr,
            ctypes.c_void_p(off.ctypes.data),
            n,
            ctypes.c_void_p(out.ctypes.data),
        )
        return out

    def bloom_insert(self, blocks, hashes) -> None:
        h = hashes if hashes.flags["C_CONTIGUOUS"] else hashes.copy()
        self._lib.ptq_bloom_insert(
            ctypes.c_void_p(blocks.ctypes.data),
            len(blocks) // 8,
            ctypes.c_void_p(h.ctypes.data),
            len(h),
        )

    def bloom_check(self, blocks, hashes):
        import numpy as np

        h = hashes if hashes.flags["C_CONTIGUOUS"] else hashes.copy()
        out = np.empty(len(h), dtype=np.uint8)
        self._lib.ptq_bloom_check(
            ctypes.c_void_p(blocks.ctypes.data),
            len(blocks) // 8,
            ctypes.c_void_p(h.ctypes.data),
            len(h),
            ctypes.c_void_p(out.ctypes.data),
        )
        return out.astype(bool)

    def byte_array_gather(self, data, num_values: int):
        """PLAIN byte_array scan: returns (offsets int64[n+1], flat bytes, consumed)."""
        import numpy as np

        addr, n_in, _keep = _ptr(data)
        offsets = np.empty(num_values + 1, dtype=np.int64)
        out = ctypes.create_string_buffer(max(n_in, 1))
        consumed = self._lib.ptq_byte_array_gather(
            addr,
            n_in,
            num_values,
            offsets.ctypes.data_as(ctypes.c_void_p),
            out,
            n_in,
        )
        if consumed < 0:
            raise ValueError("native: corrupt byte_array stream")
        # single copy of exactly the payload (out.raw would copy the whole cap)
        flat = ctypes.string_at(out, int(offsets[-1]))
        return offsets, flat, int(consumed)

    def hybrid_decode(self, data, num_values: int, width: int, nbits: int):
        """One-shot hybrid RLE/bit-pack decode. Returns (values, consumed);
        values is uint32 (nbits==32) or uint64 (nbits==64)."""
        import numpy as np

        addr, n_in, _keep = _ptr(data)
        out = np.empty(num_values, dtype=np.uint32 if nbits == 32 else np.uint64)
        p = out.ctypes.data_as(ctypes.c_void_p)
        consumed = self._lib.ptq_hybrid_decode(
            addr,
            n_in,
            num_values,
            width,
            p if nbits == 32 else None,
            p if nbits == 64 else None,
        )
        if consumed < 0:
            raise ValueError("native: corrupt hybrid stream")
        return out, int(consumed)

    def delta_decode(self, data: bytes, nbits: int, max_total: int | None):
        """Full DELTA_BINARY_PACKED decode. Returns (int32/int64 values, consumed).
        Raises OverflowError when the stream's count exceeds max_total so the
        caller can report the same error as the NumPy path."""
        import numpy as np

        addr, n_in, _keep = _ptr(data)
        total = np.zeros(1, dtype=np.int64)
        if self._lib.ptq_delta_peek_total(addr, n_in, total.ctypes.data_as(ctypes.c_void_p)) < 0:
            raise ValueError("native: corrupt delta header")
        cap = int(total[0])
        if max_total is not None and cap > max(max_total, 0):
            raise OverflowError(
                f"stream claims {cap} values, caller expects at most {max_total}"
            )
        out = np.empty(cap, dtype=np.int32 if nbits == 32 else np.int64)
        # max_total already enforced above on the peeked count; the C-side
        # bound (-3) is unreachable from here, so pass "no bound".
        consumed = self._lib.ptq_delta_decode(
            addr,
            n_in,
            nbits,
            -1,
            out.ctypes.data_as(ctypes.c_void_p),
            total.ctypes.data_as(ctypes.c_void_p),
        )
        if consumed < 0:
            raise ValueError("native: corrupt delta stream")
        return out, int(consumed)

    def plain_encode_bytearray(self, data, offsets) -> bytes:
        """(offsets, data) column -> PLAIN stream ([4B LE len][bytes] per
        value) in one C pass; ~memcpy speed vs the per-item Python loop."""
        import numpy as np

        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        n = len(offsets) - 1
        addr, n_in, _keep = _ptr(data)
        cap = n_in + 4 * max(n, 0)
        out = np.empty(max(cap, 1), dtype=np.uint8)
        rc = self._lib.ptq_plain_encode_bytearray(
            addr, n_in,
            offsets.ctypes.data_as(ctypes.c_void_p), n,
            ctypes.c_void_p(out.ctypes.data), cap,
        )
        if rc < 0:
            raise ValueError("native: corrupt byte-array offsets")
        return out[: int(rc)].tobytes()

    def bytearray_take(self, data: bytes, offsets, indices, new_offsets, total: int) -> bytes:
        """Gather rows of an (offsets, data) byte-array column by index."""
        import numpy as np

        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        indices = np.ascontiguousarray(indices, dtype=np.int64)
        new_offsets = np.ascontiguousarray(new_offsets, dtype=np.int64)
        addr, n_in, _keep = _ptr(data)
        out = ctypes.create_string_buffer(max(total, 1))
        rc = self._lib.ptq_bytearray_take(
            addr,
            n_in,
            offsets.ctypes.data_as(ctypes.c_void_p),
            len(offsets) - 1,
            indices.ctypes.data_as(ctypes.c_void_p),
            len(indices),
            new_offsets.ctypes.data_as(ctypes.c_void_p),
            out,
            total,
        )
        if rc < 0:
            raise ValueError("native: byte-array take index out of range")
        return ctypes.string_at(out, total)

    def prescan_hybrid(self, data: bytes, num_values: int, width: int):
        """Run-header prescan: returns (is_rle, counts, values, bp_offsets, consumed)
        with bp_offsets absolute into `data`, or None if the run table overflows."""
        import numpy as np

        addr, n_in, _keep = _ptr(data)
        max_runs = 4096
        while True:
            is_rle = np.empty(max_runs, dtype=np.uint8)
            counts = np.empty(max_runs, dtype=np.int64)
            values = np.empty(max_runs, dtype=np.uint64)
            offsets = np.empty(max_runs, dtype=np.int64)
            consumed = np.zeros(1, dtype=np.int64)
            n = self._lib.ptq_prescan_hybrid(
                addr,
                n_in,
                num_values,
                width,
                is_rle.ctypes.data_as(ctypes.c_void_p),
                counts.ctypes.data_as(ctypes.c_void_p),
                values.ctypes.data_as(ctypes.c_void_p),
                offsets.ctypes.data_as(ctypes.c_void_p),
                max_runs,
                consumed.ctypes.data_as(ctypes.c_void_p),
            )
            if n == -2:
                max_runs *= 8
                continue
            if n < 0:
                raise ValueError("native: corrupt hybrid stream")
            n = int(n)
            return (
                is_rle[:n].astype(bool),
                counts[:n],
                values[:n],
                offsets[:n],
                int(consumed[0]),
            )


    _POOL_MAX_BUFS = 6
    _POOL_MAX_BYTES = 64 << 20  # don't hold giant one-off chunks
    _POOL_MAX_TOTAL = 192 << 20  # per-thread retention cap (all buffers)

    def _take_buf(self, size: int):
        """A uint8 staging buffer from the per-thread pool (best fit), or a
        fresh np.empty. Pooled buffers have their pages already faulted in,
        which is most of the cost of writing a fresh multi-MB allocation.
        Entries more than 4x the request are left for larger chunks — a
        tiny chunk pinning a pooled multi-MB buffer (its plan keeps views)
        would drain the pool of exactly the buffers worth pooling."""
        import numpy as np

        pool = getattr(self._chunk_tl, "out_pool", None)
        if pool:
            best = -1
            for k in range(len(pool)):
                n = len(pool[k])
                if size <= n <= max(4 * size, 1 << 16) and (
                    best < 0 or n < len(pool[best])
                ):
                    best = k
            if best >= 0:
                return pool.pop(best)
        return np.empty(size, dtype=np.uint8)

    def release_buffers(self, res: dict, names) -> None:
        """Hand chunk_prepare staging buffers back to this thread's pool.

        ONLY legal when the caller proves no view of the named buffers
        escapes into the returned plan (e.g. the PLAIN route releases
        packed/delta always, and values when the transfer repack replaced
        the upload). Must run on the thread that called chunk_prepare."""
        bases = res.get("_bases")
        if not bases:
            return
        tl = self._chunk_tl
        pool = getattr(tl, "out_pool", None)
        if pool is None:
            pool = tl.out_pool = []
        held = sum(len(b) for b in pool)
        for name in names:
            buf = bases.pop(name, None)
            if (
                buf is not None
                and len(buf)
                and len(buf) <= self._POOL_MAX_BYTES
                and len(pool) < self._POOL_MAX_BUFS
                and held + len(buf) <= self._POOL_MAX_TOTAL
            ):
                pool.append(buf)
                held += len(buf)

    def chunk_prepare(
        self,
        data,
        codec: int,
        max_def: int,
        max_rep: int,
        type_size: int,
        delta_nbits: int,
        expected_values: int,
        uncompressed_cap: int,
        collect_stages: bool = False,
        validate_crc: bool = False,
    ):
        """Whole-chunk prepare walk (ptq_chunk_prepare): one native call does
        header parse + (opt-in) CRC verify + decompress + level decode +
        value-stream prescan for every page, GIL-free (the CPython-extension
        binding releases it explicitly via Py_BEGIN_ALLOW_THREADS; the ctypes
        fallback drops it at the foreign-call boundary). Returns a dict of
        packed tables on success, or a PrepareFault naming the failing
        {code, stage, page, offset} when the chunk needs the Python walk
        (corrupt / unsupported / capacity-exceeded — the Python path
        reproduces the exact error semantics; the fault detail feeds the
        fallback-ladder counters and parquet-tool verify).
        collect_stages=True adds a "stage_ns" int64[5] entry (decompress,
        levels, prescan, copy, crc accumulated wall ns)."""
        import numpy as np

        addr, n_in, _keep = _ptr(data)
        cap = max(uncompressed_cap, n_in) + 64
        lv = max(expected_values, 1)
        max_pages, max_runs, max_minis = 1024, 4096, 4096
        # output buffers sized from metadata; np.empty is virtual until
        # touched — but the first WRITE then faults every page in (~0.5 ms
        # per MB), so routes that provably leak no view of a buffer hand it
        # back via release_buffers and the next chunk on this thread skips
        # the fault storm entirely
        def_out = np.empty(lv, dtype=np.uint16) if max_def > 0 else np.empty(0, np.uint16)
        rep_out = np.empty(lv, dtype=np.uint16) if max_rep > 0 else np.empty(0, np.uint16)
        values_out = self._take_buf(cap)
        packed_out = self._take_buf(cap)
        # delta_out slack covers the worst-case PLAIN->delta repack (a page
        # that sampled compressible but encodes at full width: raw size +
        # ~0.5% framing) so the C walk never has to back out mid-chunk
        delta_out = (
            self._take_buf(cap + cap // 64 + 4096)
            if delta_nbits
            else np.empty(0, np.uint8)
        )
        # The decompress scratch never escapes the C call, so it is the one
        # big buffer that can be POOLED per thread: a fresh np.empty faults
        # in every written page on first touch (~1 ms per decompressed MB on
        # this class of host), which a reused buffer pays only once.
        tl = self._chunk_tl
        # +64 bytes of physical slack past the largest page's uncompressed
        # size: decompress_page passes the physical capacity through, which
        # switches snappy into its overshooting fast mode even when a chunk
        # is one exactly-sized page
        scratch = getattr(tl, "scratch", None)
        if scratch is None or len(scratch) < cap + 64:
            scratch = tl.scratch = np.empty(cap + 64, dtype=np.uint8)
        totals = np.zeros(8, dtype=np.int64)
        stage_ns = np.zeros(5, dtype=np.int64) if collect_stages else None
        err_info = np.zeros(4, dtype=np.int64)
        ext = self._ext_chunk_prepare
        p = ctypes.c_void_p
        while True:
            if stage_ns is not None:
                stage_ns[:] = 0  # a table-growth retry re-walks from scratch:
                # keep only the final walk's split, not partial+full summed
            pages = np.empty((max_pages, 18), dtype=np.int64)
            h_is_rle = np.empty(max_runs, dtype=np.uint8)
            h_counts = np.empty(max_runs, dtype=np.int64)
            h_values = np.empty(max_runs, dtype=np.uint64)
            h_byteoff = np.empty(max_runs, dtype=np.int64)
            d_widths = np.empty(max_minis, dtype=np.uint32)
            d_bytestart = np.empty(max_minis, dtype=np.int64)
            d_outstart = np.empty(max_minis, dtype=np.int32)
            d_mins = np.empty(max_minis, dtype=np.uint64)
            if ext is not None:
                # single GIL-free transition: every buffer binds through the
                # buffer protocol and capacities derive from the buffer
                # lengths — values/packed are sliced to exactly `cap` so both
                # bindings enforce the SAME -5 overflow bound (the pool may
                # hand back a larger staging buffer than requested)
                rc = ext(
                    data if isinstance(data, (bytes, memoryview)) else _keep,
                    codec, 1 if validate_crc else 0,
                    max_def, max_rep, type_size, delta_nbits,
                    expected_values,
                    pages, def_out, rep_out,
                    memoryview(values_out)[:cap],
                    memoryview(packed_out)[:cap],
                    delta_out, scratch,
                    h_is_rle, h_counts, h_values, h_byteoff,
                    d_widths, d_bytestart, d_outstart, d_mins,
                    totals, stage_ns, err_info,
                )
            else:
                rc = self._lib.ptq_chunk_prepare(
                    addr, n_in, codec, 1 if validate_crc else 0,
                    max_def, max_rep, type_size, delta_nbits,
                    expected_values,
                    pages.ctypes.data_as(p), max_pages,
                    def_out.ctypes.data_as(p), rep_out.ctypes.data_as(p),
                    values_out.ctypes.data_as(p), cap,
                    packed_out.ctypes.data_as(p), cap,
                    delta_out.ctypes.data_as(p), len(delta_out),
                    scratch.ctypes.data_as(p), len(scratch),
                    h_is_rle.ctypes.data_as(p), h_counts.ctypes.data_as(p),
                    h_values.ctypes.data_as(p), h_byteoff.ctypes.data_as(p), max_runs,
                    d_widths.ctypes.data_as(p), d_bytestart.ctypes.data_as(p),
                    d_outstart.ctypes.data_as(p), d_mins.ctypes.data_as(p), max_minis,
                    totals.ctypes.data_as(p),
                    None if stage_ns is None else stage_ns.ctypes.data_as(p),
                    err_info.ctypes.data_as(p),
                )
            if rc == -2 and max_pages < (1 << 24):
                max_pages *= 8
                continue
            if rc == -3 and max_runs < n_in + 8:
                max_runs = min(max_runs * 8, n_in + 8)
                continue
            if rc == -4 and max_minis < n_in + 8:
                max_minis = min(max_minis * 8, n_in + 8)
                continue
            if rc < 0:
                return PrepareFault(
                    code=int(rc),
                    stage=PREPARE_STAGES.get(int(err_info[0]), "none"),
                    page=int(err_info[1]),
                    offset=int(err_info[2]),
                )
            n = int(rc)
            R = int(totals[4])
            M = int(totals[5])
            return {
                "pages": pages[:n],
                "def": def_out[: int(totals[0])] if max_def > 0 else None,
                "rep": rep_out[: int(totals[0])] if max_rep > 0 else None,
                "values": values_out[: int(totals[1])],
                "packed": packed_out[: int(totals[2])],
                "delta_stream": delta_out[: int(totals[3])],
                "_bases": {
                    "values": values_out,
                    "packed": packed_out,
                    "delta": delta_out if delta_nbits else None,
                },
                "h_is_rle": h_is_rle[:R],
                "h_counts": h_counts[:R],
                "h_values": h_values[:R],
                "h_byteoff": h_byteoff[:R],
                "d_widths": d_widths[:M],
                "d_bytestart": d_bytestart[:M],
                "d_outstart": d_outstart[:M],
                "d_mins": d_mins[:M],
                "has_dict": bool(totals[6]),
                "stage_ns": stage_ns,
            }

    def gzip_compress(self, data) -> bytes:
        """Deflate with the fused encode walk's exact gzip parameters (the
        startup identity probe against CPython's zlib)."""
        addr, n_in, _keep = _ptr(data)
        cap = n_in + n_in // 4 + 128
        out = ctypes.create_string_buffer(cap)
        n = self._lib.ptq_gzip_compress(addr, n_in, out, cap)
        if n < 0:
            raise ValueError("native gzip: compression failed")
        return out.raw[:n]

    def chunk_encode(
        self,
        route: int,
        values,
        ba_offsets,
        nv: int,
        type_size: int,
        dict_width: int,
        dict_raw,
        dict_num: int,
        def_levels,
        num_entries: int,
        max_def: int,
        codec: int,
        dpv: int,
        with_crc: bool,
        per_page: int,
        raw_worst: int,
        collect_stages: bool = False,
    ):
        """Whole-chunk encode walk (ptq_chunk_encode): ONE native call does
        page split + level pack + value encode + compress + Thrift page
        framing, GIL-free via the CPython-extension binding (ctypes
        fallback drops the GIL at the foreign-call boundary). Returns a
        dict {out, pages, totals, stage_ns} on success — `out` is a uint8
        view of exactly the framed chunk bytes — or an EncodeFault naming
        the failing {code, stage, page} when the chunk needs the staged
        Python encoder. `raw_worst` is the caller's worst-case raw
        (uncompressed) page-block bound; output/scratch capacities derive
        from it with compression-expansion slack, and a -5 capacity verdict
        retries once with doubled buffers before reporting the fault."""
        import numpy as np

        ext = self._ext_chunk_encode
        # worst case for an incompressible block: snappy adds ~n/6 + 32,
        # deflate ~n/1000 + 13 — one shared slack covers every codec
        comp_slack = raw_worst // 4 + 1024
        scratch_cap = 2 * (raw_worst + comp_slack)
        out_cap = (
            raw_worst
            + comp_slack
            + int(dict_raw.nbytes if hasattr(dict_raw, "nbytes") else len(dict_raw or b""))
            + 4096
        )
        max_pages = int(num_entries // max(per_page, 1)) + 3
        totals = np.zeros(8, dtype=np.int64)
        stage_ns = np.zeros(5, dtype=np.int64) if collect_stages else None
        err_info = np.zeros(4, dtype=np.int64)
        p = ctypes.c_void_p
        attempts = 0
        while True:
            out = np.empty(out_cap, dtype=np.uint8)
            scratch = self._take_buf(scratch_cap)
            pages = np.empty((max_pages, 8), dtype=np.int64)
            if stage_ns is not None:
                stage_ns[:] = 0
            if ext is not None:
                rc = ext(
                    route,
                    values,
                    ba_offsets,
                    nv,
                    type_size,
                    dict_width,
                    dict_raw,
                    dict_num,
                    def_levels,
                    num_entries,
                    max_def,
                    codec,
                    dpv,
                    1 if with_crc else 0,
                    per_page,
                    out,
                    memoryview(scratch)[:scratch_cap],
                    pages,
                    totals,
                    stage_ns,
                    err_info,
                )
            else:
                va, v_len, _vk = _ptr(values)
                oa = ok = da = dk = fa = fk = None
                if ba_offsets is not None:
                    oa, _n, ok = _ptr(ba_offsets)
                if dict_raw is not None:
                    da, d_len, dk = _ptr(dict_raw)
                else:
                    d_len = 0
                if def_levels is not None:
                    fa, _n, fk = _ptr(def_levels)
                rc = self._lib.ptq_chunk_encode(
                    route, va, v_len, oa, nv, type_size, dict_width,
                    da, d_len, dict_num, fa, num_entries, max_def,
                    codec, dpv, 1 if with_crc else 0, per_page,
                    ctypes.c_void_p(out.ctypes.data), out_cap,
                    ctypes.c_void_p(scratch.ctypes.data), scratch_cap,
                    pages.ctypes.data_as(p), max_pages,
                    totals.ctypes.data_as(p),
                    None if stage_ns is None else stage_ns.ctypes.data_as(p),
                    err_info.ctypes.data_as(p),
                )
                del ok, dk, fk  # keepalives live through the call
            # scratch never escapes the walk: always pool it back
            self.release_buffers({"_bases": {"scratch": scratch}}, ("scratch",))
            if rc == -2 and max_pages < (1 << 24):
                max_pages *= 8
                continue
            if rc == -5 and attempts < 2:
                attempts += 1
                out_cap *= 2
                scratch_cap *= 2
                continue
            if rc < 0:
                return EncodeFault(
                    code=int(rc),
                    stage=ENCODE_STAGES.get(int(err_info[0]), "none"),
                    page=int(err_info[1]),
                )
            return {
                "out": out[: int(totals[0])],
                "pages": pages[: int(rc)],
                "totals": totals,
                "stage_ns": stage_ns,
            }

    def hybrid_encode(self, values, width: int) -> bytes:
        """RLE/bit-pack hybrid encode of a uint64 array (byte-identical to
        ops/rle_hybrid.py encode_hybrid)."""
        import numpy as np

        v = np.ascontiguousarray(values, dtype=np.uint64)
        n = len(v)
        cap = hybrid_encode_cap(n, width)
        out = np.empty(cap, dtype=np.uint8)
        rc = self._lib.ptq_hybrid_encode(
            ctypes.c_void_p(v.ctypes.data), n, width,
            ctypes.c_void_p(out.ctypes.data), cap,
        )
        if rc < 0:
            raise ValueError(
                f"native: hybrid encode failed ({'value too wide' if rc == -1 else 'capacity'})"
            )
        return out[: int(rc)].tobytes()

    def repack_pages(self, packed, ps, pe, widths, w_to: int, out_size: int):
        """The packed regions [ps[p], pe[p]) of one chunk's index pages
        (uint8 array `packed`, int64 arrays, int32 `widths`) laid end to end
        at w_to bits, in one GIL-free call: (a new uint8 array of out_size
        bytes, the seconds the call clocked inside itself)."""
        import numpy as np

        out = np.empty(out_size, dtype=np.uint8)
        ns = ctypes.c_int64(0)
        rc = self._lib.ptq_repack_pages(
            ctypes.c_void_p(packed.ctypes.data), ctypes.c_void_p(ps.ctypes.data),
            ctypes.c_void_p(pe.ctypes.data), ctypes.c_void_p(widths.ctypes.data),
            len(widths), w_to, ctypes.c_void_p(out.ctypes.data), out_size,
            ctypes.byref(ns),
        )
        if rc != out_size:
            raise ValueError("native: repack_pages failed")
        return out, ns.value / 1e9

    def delta_frame(
        self, stream, widths, bit_starts, out_starts, counts, mins,
        nbits: int, ref: int, width: int, n_pad: int, out,
    ) -> float:
        """The delta frame of one upload (kernels/device_ops.py
        pack_delta_upload says what it is) written into `out`, a contiguous
        uint32 array of n_pad * width / 32 words, in one GIL-free call: the
        miniblock tables are contiguous arrays (uint32 widths, int64
        bit_starts / out_starts / counts, uint64 mins), `stream` a uint8
        array, `ref` the frame of reference mod 2^nbits. Returns the seconds
        the call clocked inside itself."""
        assert out.dtype == "uint32" and out.flags.c_contiguous and len(out) == n_pad * width // 32
        ns = ctypes.c_int64(0)
        rc = self._lib.ptq_delta_frame(
            ctypes.c_void_p(stream.ctypes.data), len(stream),
            ctypes.c_void_p(widths.ctypes.data), ctypes.c_void_p(bit_starts.ctypes.data),
            ctypes.c_void_p(out_starts.ctypes.data), ctypes.c_void_p(counts.ctypes.data),
            ctypes.c_void_p(mins.ctypes.data), len(widths), nbits, ref, width, n_pad,
            ctypes.c_void_p(out.ctypes.data), ctypes.byref(ns),
        )
        if rc != int(counts.sum()):
            raise ValueError(f"native: delta_frame failed ({rc})")
        return ns.value / 1e9

    def hybrid_frame(
        self, packed, is_rle, counts, rle_values, bit_starts,
        width: int, lo_bits: int, hi_bits: int, n_pad: int, out,
    ) -> float:
        """The hybrid frame of one upload (kernels/device_ops.py
        pack_hybrid_upload says what it is) written into `out`, a contiguous
        uint32 array of n_pad * (lo_bits + hi_bits) / 32 words, in one
        GIL-free call: the run tables are contiguous arrays (uint8 is_rle,
        int64 counts / bit_starts, uint32 rle_values), `packed` a uint8
        array. Returns the seconds the call clocked inside itself."""
        assert out.dtype == "uint32" and out.flags.c_contiguous
        assert len(out) == n_pad * (lo_bits + hi_bits) // 32
        ns = ctypes.c_int64(0)
        rc = self._lib.ptq_hybrid_frame(
            ctypes.c_void_p(packed.ctypes.data), len(packed),
            ctypes.c_void_p(is_rle.ctypes.data), ctypes.c_void_p(counts.ctypes.data),
            ctypes.c_void_p(rle_values.ctypes.data), ctypes.c_void_p(bit_starts.ctypes.data),
            len(counts), width, lo_bits, hi_bits, n_pad,
            ctypes.c_void_p(out.ctypes.data), ctypes.byref(ns),
        )
        if rc != int(counts.sum()):
            raise ValueError(f"native: hybrid_frame failed ({rc})")
        return ns.value / 1e9

    def delta_encode(self, values, nbits: int, block_size: int, mini_count: int) -> bytes:
        """DELTA_BINARY_PACKED encode (byte-identical to ops/delta.py
        encode_delta)."""
        import numpy as np

        dt = np.int32 if nbits == 32 else np.int64
        v = np.ascontiguousarray(values, dtype=dt)
        n = len(v)
        cap = delta_encode_cap(n, nbits, block_size, mini_count)
        out = np.empty(cap, dtype=np.uint8)
        rc = self._lib.ptq_delta_encode(
            ctypes.c_void_p(v.ctypes.data), n, nbits, block_size, mini_count,
            ctypes.c_void_p(out.ctypes.data), cap,
        )
        if rc < 0:
            raise ValueError("native: delta encode failed")
        return out[: int(rc)].tobytes()

    def bytes_dict_indices(self, data, offsets, max_uniques: int):
        """Dictionary probe over an (offsets, data) byte-array column.
        Returns (first_occurrence_rows uint32[U], indices uint32[n]) or None
        when uniques exceed max_uniques."""
        import numpy as np

        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        n = len(offsets) - 1
        addr, data_len, _keep = _ptr(data)
        indices = np.empty(max(n, 1), dtype=np.uint32)
        firsts = np.empty(max_uniques + 2, dtype=np.uint32)
        rc = self._lib.ptq_bytes_dict_indices(
            addr, data_len,
            ctypes.c_void_p(offsets.ctypes.data), n, max_uniques,
            ctypes.c_void_p(indices.ctypes.data),
            ctypes.c_void_p(firsts.ctypes.data),
        )
        if rc == -2:
            return None
        if rc < 0:
            raise ValueError("native: byte-array dictionary probe failed")
        return firsts[: int(rc)], indices[:n]

    def bytes_minmax(self, data, offsets):
        """(row of lexicographic min, row of max) over a byte-array column."""
        import numpy as np

        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        n = len(offsets) - 1
        addr, data_len, _keep = _ptr(data)
        out = np.empty(2, dtype=np.int64)
        rc = self._lib.ptq_bytes_minmax(
            addr, data_len, ctypes.c_void_p(offsets.ctypes.data), n,
            ctypes.c_void_p(out.ctypes.data),
        )
        if rc < 0:
            raise ValueError("native: byte-array minmax failed")
        return int(out[0]), int(out[1])

    def u64_dict_indices(self, bits, max_uniques: int):
        """Dictionary probe over uint32/uint64 bit patterns (probed in place,
        no widening copy); early-exits past the unique cutoff. Returns
        (first_rows, indices) or None over the cap."""
        import numpy as np

        v = np.ascontiguousarray(bits)
        if v.dtype not in (np.dtype(np.uint32), np.dtype(np.uint64)):
            v = v.astype(np.uint64)
        n = len(v)
        indices = np.empty(max(n, 1), dtype=np.uint32)
        firsts = np.empty(max_uniques + 2, dtype=np.uint32)
        rc = self._lib.ptq_u64_dict_indices(
            ctypes.c_void_p(v.ctypes.data), v.dtype.itemsize, n, max_uniques,
            ctypes.c_void_p(indices.ctypes.data),
            ctypes.c_void_p(firsts.ctypes.data),
        )
        if rc == -2:
            return None
        if rc < 0:
            raise ValueError("native: u64 dictionary probe failed")
        return firsts[: int(rc)], indices[:n]

    def prescan_delta_packed(self, data: bytes, nbits: int, max_total: int):
        """Header-only delta prescan. Returns (widths, byte_starts, out_starts,
        mins, first_value, total, consumed). Raises OverflowError when the
        stream's count exceeds max_total (parity with the Python path)."""
        import numpy as np

        # Negative bounds clamp to 0, matching the Python path's
        # max(max_total, 0); the C side applies the same clamp, and the table
        # is sized from the bound actually enforced.
        max_total = max(max_total, 0)
        # One table entry per miniblock with >=1 real delta; mini_len >= 8, so
        # M <= ceil((total-1)/8) and total <= max_total. Each entry also
        # consumes at least its one width byte from the stream, so M <= len:
        # a lying header with a huge count must not drive the allocation
        # (validation-before-allocation discipline).
        addr, n_in, _keep = _ptr(data)
        max_entries = min(max(max_total, 8) // 8 + 2, n_in + 2)
        widths = np.empty(max_entries, dtype=np.uint32)
        byte_starts = np.empty(max_entries, dtype=np.int64)
        out_starts = np.empty(max_entries, dtype=np.int32)
        mins = np.empty(max_entries, dtype=np.uint64)
        first = np.zeros(1, dtype=np.uint64)
        total = np.zeros(1, dtype=np.int64)
        consumed = np.zeros(1, dtype=np.int64)
        m = self._lib.ptq_prescan_delta_packed(
            addr,
            n_in,
            nbits,
            max_total,
            widths.ctypes.data_as(ctypes.c_void_p),
            byte_starts.ctypes.data_as(ctypes.c_void_p),
            out_starts.ctypes.data_as(ctypes.c_void_p),
            mins.ctypes.data_as(ctypes.c_void_p),
            max_entries,
            first.ctypes.data_as(ctypes.c_void_p),
            total.ctypes.data_as(ctypes.c_void_p),
            consumed.ctypes.data_as(ctypes.c_void_p),
        )
        if m == -3:
            raise OverflowError(
                f"stream claims more than the caller's bound of {max_total} values"
            )
        if m < 0:
            raise ValueError("native: corrupt delta stream")
        m = int(m)
        return (
            widths[:m],
            byte_starts[:m],
            out_starts[:m],
            mins[:m],
            int(first[0]),
            int(total[0]),
            int(consumed[0]),
        )

    def parse_page_header(self, window: bytes):
        """Parse one Thrift compact PageHeader from a peeked window.

        Returns the 23-slot int64 array (see ptq_parse_page_header layout),
        None when the window was too small (caller re-peeks larger), or
        raises ValueError on structurally corrupt bytes (caller falls back
        to the Python reader for its exact error)."""
        import numpy as np

        addr, n_in, _keep = _ptr(window)
        out = np.empty(23, dtype=np.int64)
        rc = self._lib.ptq_parse_page_header(
            addr, n_in, out.ctypes.data_as(ctypes.c_void_p)
        )
        if rc == -2:
            return None
        if rc < 0:
            raise ValueError("native: corrupt page header")
        return out


def _ext_path() -> Path:
    import sysconfig

    return _ROOT / "parquet_tpu" / (
        "_native_ext" + (sysconfig.get_config_var("EXT_SUFFIX") or ".so")
    )


def _stale(artifacts) -> bool:
    newest = max((_NATIVE_DIR / s).stat().st_mtime_ns for s in _SOURCES)
    return any(
        not a.exists() or a.stat().st_mtime_ns < newest for a in artifacts
    )


def _ensure_built() -> None:
    """Compile both native artifacts when one is missing or older than its
    sources. native/Makefile is the one recipe; it runs into a private
    directory and the results are renamed into place, so a concurrent
    process (or a dlopen in this one) only ever sees a whole file. A flock
    keeps racing processes from compiling the same thing twice. A failed
    build is remembered (require_native reports it), never raised: the
    pure-Python paths stay available."""
    global _build_error
    artifacts = (_LIB_PATH, _ext_path())
    try:
        if not _stale(artifacts):
            return
    except OSError:
        return  # installed without native/ sources: nothing to build from
    import fcntl
    import shutil
    import subprocess
    import sysconfig

    try:
        _LIB_PATH.parent.mkdir(parents=True, exist_ok=True)
        with open(_LIB_PATH.parent / ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if not _stale(artifacts):
                return  # another process built while we waited
            tmp = Path("build") / f"tmp.{os.getpid()}"
            try:
                subprocess.run(
                    [
                        "make", "-s", "-C", str(_NATIVE_DIR),
                        f"BUILD={tmp}",
                        f"PYEXT={tmp / artifacts[1].name}",
                        f"PYINC=-I{sysconfig.get_paths()['include']}",
                    ],
                    check=True, capture_output=True, text=True,
                )
                for a in artifacts:
                    os.replace(_NATIVE_DIR / tmp / a.name, a)
                _build_error = None
            finally:
                shutil.rmtree(_NATIVE_DIR / tmp, ignore_errors=True)
    except subprocess.CalledProcessError as e:
        _build_error = f"make -C native failed: {e.stderr[-800:]}"
    except OSError as e:  # no make/compiler, read-only checkout
        _build_error = f"native build unavailable: {e}"


def load_ext():
    """The `_native_ext` CPython extension module (built on first use), or
    None when it cannot be built — every caller degrades without it."""
    _ensure_built()
    try:
        from .. import _native_ext
    except ImportError:
        return None
    return _native_ext


def get_native() -> NativeLib | None:
    """Load the native helper library (building it when missing or older
    than its sources), or None if it cannot be built/loaded."""
    global _cached, _probed
    if _probed:
        return _cached
    with _probe_lock:
        if _probed:
            return _cached
        _ensure_built()
        candidates = [_LIB_PATH]
        env = os.environ.get("PARQUET_TPU_NATIVE")
        if env:
            candidates.insert(0, Path(env))
        for cand in candidates:
            if cand.exists():
                try:
                    _cached = NativeLib(ctypes.CDLL(str(cand)))
                    break
                except OSError:
                    continue
        _probed = True
    return _cached


def require_native() -> NativeLib:
    """get_native() for callers that measure or serve the device path: the
    fused native walk AND its GIL-free binding must be loaded — a run that
    quietly took the per-page Python walk is a different program."""
    lib = get_native()
    if lib is None or not lib.has_chunk_prepare or not lib.fused_gil_free:
        raise RuntimeError(
            "parquet_tpu: the native library and its _native_ext binding are "
            "required here but "
            + (_build_error or "could not be loaded")
        )
    return lib
