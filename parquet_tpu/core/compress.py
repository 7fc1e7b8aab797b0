"""Block compression registry.

Pluggable codec registry mirroring the reference's BlockCompressor model
(reference: compress.go:16-157): UNCOMPRESSED/GZIP/SNAPPY built in, others
registered at import or by the user via register_codec (the reference's public
RegisterBlockCompressor, compress.go:131-136). Decompressed output is validated
against the expected size before use (reference: compress.go:102-123).

SNAPPY and LZ4/LZ4_RAW resolve to the native C++ codecs (native/, loaded via
ctypes) when built, else pyarrow's bundled implementations. The legacy LZ4
codec (id 5) reads both Hadoop-framed and bare raw blocks and writes the
framed form (parquet-cpp's contract). ZSTD comes from the zstandard module,
BROTLI from pyarrow; LZO raises a clear 'codec not registered' error unless
the user registers an implementation.
"""

from __future__ import annotations

import threading
import zlib

from ..meta.file_meta import ParquetFileError
from ..meta.parquet_types import CompressionCodec
from ..utils import metrics as _metrics
from ..utils.trace import add_bytes as _trace_add_bytes

__all__ = [
    "compress_block",
    "decompress_block",
    "register_codec",
    "codec_supported",
    "CompressionError",
]


class CompressionError(ParquetFileError):
    """Corrupt or unsupported compressed block. A ParquetFileError so the
    API boundary's documented catch-all covers codec-level corruption the
    same as every other malformed-file path."""


class _Codec:
    name = "?"

    def compress(self, data: bytes) -> bytes:
        raise NotImplementedError

    def decompress(self, data: bytes, uncompressed_size: int) -> bytes:
        raise NotImplementedError


class _Uncompressed(_Codec):
    name = "UNCOMPRESSED"

    def compress(self, data):
        return bytes(data)

    def decompress(self, data, uncompressed_size):
        return bytes(data)


class _Gzip(_Codec):
    name = "GZIP"

    def compress(self, data):
        c = zlib.compressobj(wbits=31)  # gzip container
        # no bytes() round-trip: zlib takes any buffer, and the GIL-held
        # copy of a ~1 MiB page was measurable under the parallel encoder
        return c.compress(data) + c.flush()

    def decompress(self, data, uncompressed_size):
        # wbits=47: auto-detect gzip or zlib headers. Decompression stops at
        # the advertised size: a bomb that inflates past it raises without
        # ever materializing the excess (validation-before-allocation).
        # d.eof also guards integrity: it only turns true once the stream's
        # trailer (gzip CRC32/ISIZE) has been read and verified, so a
        # truncated stream that happens to yield the advertised size still
        # fails here.
        d = zlib.decompressobj(wbits=47)
        out = d.decompress(bytes(data), max(uncompressed_size, 1))
        if d.unconsumed_tail or not d.eof:
            raise CompressionError(
                "gzip stream truncated or inflates past advertised size "
                f"{uncompressed_size}"
            )
        return out


class _PyArrowCodec(_Codec):
    """Stock wrapper over a pyarrow-bundled codec (snappy/lz4_raw/brotli)."""

    def __init__(self, name: str, arrow_name: str):
        import pyarrow as pa

        self.name = name
        self._codec = pa.Codec(arrow_name)

    def compress(self, data):
        return self._codec.compress(bytes(data)).to_pybytes()

    def decompress(self, data, uncompressed_size):
        # memoryview over the pa.Buffer: zero-copy, buffer kept alive by the view
        return memoryview(
            self._codec.decompress(bytes(data), decompressed_size=uncompressed_size)
        )


class _NativeSnappy(_Codec):
    name = "SNAPPY"

    def __init__(self):
        from ..utils.native import get_native

        self._lib = get_native()
        if self._lib is None or not self._lib.has_snappy:
            raise ImportError("native snappy not built")

    def compress(self, data):
        return self._lib.snappy_compress(data)  # _ptr takes any buffer

    def decompress(self, data, uncompressed_size):
        return self._lib.snappy_decompress(data, uncompressed_size)


class _Zstd(_Codec):
    name = "ZSTD"

    def __init__(self):
        import zstandard

        self._zstd = zstandard
        # a zstandard context is not thread-safe (its calls release the GIL
        # over one shared ZSTD_CCtx / ZSTD_DCtx), and chunks prepare on pool
        # threads: each thread keeps its own pair
        self._local = threading.local()

    def _contexts(self):
        ctx = getattr(self._local, "ctx", None)
        if ctx is None:
            ctx = self._local.ctx = (
                self._zstd.ZstdCompressor(),
                self._zstd.ZstdDecompressor(),
            )
        return ctx

    def compress(self, data):
        return self._contexts()[0].compress(bytes(data))

    def decompress(self, data, uncompressed_size):
        return self._contexts()[1].decompress(
            bytes(data), max_output_size=max(uncompressed_size, 1)
        )


class _NativeLz4Raw(_Codec):
    """LZ4_RAW (codec 7): one raw LZ4 block per page."""

    name = "LZ4_RAW"

    def __init__(self):
        from ..utils.native import get_native

        self._lib = get_native()
        if self._lib is None or not self._lib.has_lz4:
            raise ImportError("native lz4 not built")

    def compress(self, data):
        return self._lib.lz4_compress(bytes(data))

    def decompress(self, data, uncompressed_size):
        return self._lib.lz4_decompress(data, uncompressed_size)


class _Lz4Hadoop(_Codec):
    """Legacy LZ4 (codec 5): Hadoop framing on disk — repeated
    [4B BE uncompressed size][4B BE compressed size][raw block] — with a
    bare-raw-block fallback on read (parquet-cpp's contract; pyarrow and
    parquet-mr both write the framed form)."""

    name = "LZ4"

    def __init__(self, raw: _Codec):
        self._raw = raw
        from ..utils.native import get_native

        lib = get_native()
        self._lib = lib if lib is not None and lib.has_lz4 else None

    # Hadoop's BlockCompressorStream splits writes at the codec buffer size
    # (io.compression.codec.lz4.buffersize, default 256KB): pages past that
    # emit MULTIPLE [sizes][block] frames, which is what parquet-mr files
    # actually contain — matching it keeps our large pages byte-compatible
    # with Hadoop-stack readers
    _BLOCK = 256 << 10

    def compress(self, data):
        import struct

        data = bytes(data)
        if len(data) <= self._BLOCK:
            block = self._raw.compress(data)
            return struct.pack(">II", len(data), len(block)) + block
        out = bytearray()
        for lo in range(0, len(data), self._BLOCK):
            piece = data[lo : lo + self._BLOCK]
            block = self._raw.compress(piece)
            out += struct.pack(">II", len(piece), len(block)) + block
        return bytes(out)

    def decompress(self, data, uncompressed_size):
        if self._lib is not None:
            return self._lib.lz4_decompress(data, uncompressed_size, hadoop=True)
        import struct

        buf = bytes(data)
        out = bytearray()
        pos = 0
        ok = True
        while pos < len(buf):
            if pos + 8 > len(buf):
                ok = False
                break
            usz, csz = struct.unpack_from(">II", buf, pos)
            if pos + 8 + csz > len(buf) or len(out) + usz > uncompressed_size:
                ok = False
                break
            try:
                out += self._raw.decompress(buf[pos + 8 : pos + 8 + csz], usz)
            except Exception:
                ok = False
                break
            pos += 8 + csz
        if ok and len(out) == uncompressed_size:
            return bytes(out)
        return self._raw.decompress(buf, uncompressed_size)


_REGISTRY: dict[int, _Codec] = {}


def register_codec(codec: CompressionCodec, impl) -> None:
    """Register/override a codec implementation (objects with .compress(bytes)
    and .decompress(bytes, uncompressed_size))."""
    _REGISTRY[int(codec)] = impl


def codec_supported(codec: CompressionCodec) -> bool:
    return int(codec) in _REGISTRY


_GZIP_FUSED_OK: bool | None = None


def fused_gzip_identical() -> bool:
    """One-time probe: the native deflate (ptq_gzip_compress) must produce a
    gzip stream byte-identical to zlib.compressobj(wbits=31) — true when the
    extension and CPython link the same zlib build. A CPython bundling a
    different zlib keeps GZIP chunks on the staged encoder (the fused walk's
    byte-identity contract is absolute)."""
    global _GZIP_FUSED_OK
    if _GZIP_FUSED_OK is None:
        from ..utils.native import get_native

        lib = get_native()
        ok = lib is not None and getattr(lib, "has_gzip_encode", False)
        if ok:
            probe = bytes(range(256)) * 16 + b"parquet_tpu gzip probe " * 64
            try:
                ok = lib.gzip_compress(probe) == _Gzip().compress(probe)
            except Exception:
                ok = False
        _GZIP_FUSED_OK = bool(ok)
    return _GZIP_FUSED_OK


def is_fused_encode_codec(codec) -> bool:
    """True while `codec` resolves to an implementation the fused native
    ENCODE walk reproduces byte-for-byte: the stock UNCOMPRESSED pass-through,
    the native snappy encoder (the walk calls the same function), or stock
    gzip once the deflate identity probe has passed. register_codec overrides
    and pyarrow-backed snappy stand the fused encoder down."""
    impl = _REGISTRY.get(int(codec))
    if isinstance(impl, _Uncompressed):
        return True
    if isinstance(impl, _NativeSnappy):
        return True
    if isinstance(impl, _Gzip):
        return fused_gzip_identical()
    return False


def is_builtin_codec(codec) -> bool:
    """True while `codec` still resolves to a stock implementation — the
    native whole-chunk walk inlines UNCOMPRESSED/SNAPPY/GZIP and must stand
    down when register_codec has overridden one of them."""
    impl = _REGISTRY.get(int(codec))
    return isinstance(
        impl,
        (_Uncompressed, _Gzip, _NativeSnappy, _PyArrowCodec, _NativeLz4Raw, _Lz4Hadoop),
    )


def _get(codec) -> _Codec:
    impl = _REGISTRY.get(int(codec))
    if impl is None:
        try:
            name = CompressionCodec(codec).name
        except ValueError:
            name = str(codec)
        raise CompressionError(
            f"compression codec {name} not registered "
            "(use parquet_tpu.core.compress.register_codec)"
        )
    return impl


def compress_block(data: bytes, codec) -> bytes:
    return _get(codec).compress(data)


def decompress_block(data: bytes, codec, uncompressed_size: int) -> bytes:
    """Decompress and validate the advertised uncompressed size
    (reference: compress.go:107-120)."""
    if uncompressed_size < 0:
        raise CompressionError(f"invalid uncompressed size {uncompressed_size}")
    impl = _get(codec)
    try:
        out = impl.decompress(data, uncompressed_size)
    except CompressionError:
        raise
    except Exception as e:
        raise CompressionError(f"decompression failed: {e}") from e
    if len(out) != uncompressed_size:
        raise CompressionError(
            f"decompressed size {len(out)} != advertised {uncompressed_size}"
        )
    # every staged decode path funnels through here, making this the one
    # choke point for the always-on byte counters (the fused native walk
    # bypasses it and reports its own totals in kernels/pipeline.py).
    # The same output-byte count rides the ACTIVE trace as the
    # `decode.bytes` account, so a request-scoped trace's decoded-byte
    # total reconciles EXACTLY with the process bytes_uncompressed_total
    # delta — what the serve cost ledger charges per tenant.
    _metrics.io_bytes(len(data), len(out), impl.name)
    _trace_add_bytes("decode.bytes", len(out))
    return out


def _init_registry() -> None:
    _REGISTRY[int(CompressionCodec.UNCOMPRESSED)] = _Uncompressed()
    _REGISTRY[int(CompressionCodec.GZIP)] = _Gzip()
    try:
        _REGISTRY[int(CompressionCodec.SNAPPY)] = _NativeSnappy()
    except Exception:
        try:
            _REGISTRY[int(CompressionCodec.SNAPPY)] = _PyArrowCodec("SNAPPY", "snappy")
        except Exception:
            pass
    try:
        _REGISTRY[int(CompressionCodec.ZSTD)] = _Zstd()
    except Exception:
        pass
    raw: _Codec | None
    try:
        raw = _NativeLz4Raw()
    except Exception:
        try:
            raw = _PyArrowCodec("LZ4_RAW", "lz4_raw")
        except Exception:
            raw = None
    if raw is not None:
        _REGISTRY[int(CompressionCodec.LZ4_RAW)] = raw
        _REGISTRY[int(CompressionCodec.LZ4)] = _Lz4Hadoop(raw)
    try:
        _REGISTRY[int(CompressionCodec.BROTLI)] = _PyArrowCodec("BROTLI", "brotli")
    except Exception:
        pass


_init_registry()
