"""Unit tests for the host-path encoders/decoders (ops/).

Mirrors the reference's kernel-level strategy (SURVEY §4.1-4.2): exhaustive
widths for bit-pack, roundtrips with random data for every codec, plus scalar
reference decoders as independent oracles.
"""

import threading

import numpy as np
import pytest

from parquet_tpu.core.arrays import ByteArrayData
from parquet_tpu.core import compress
from parquet_tpu.meta import CompressionCodec, Type
from parquet_tpu.ops.bitpack import bit_width, pack_bits, unpack_bits
from parquet_tpu.ops.bytearray import (
    decode_delta_byte_array,
    decode_delta_length_byte_array,
    encode_delta_byte_array,
    encode_delta_length_byte_array,
)
from parquet_tpu.ops.delta import DeltaError, decode_delta, encode_delta
from parquet_tpu.ops.dictionary import DictError, decode_dict_indices, encode_dict_indices
from parquet_tpu.ops.levels import (
    decode_levels_v1,
    decode_levels_v2,
    encode_levels_v1,
    encode_levels_v2,
)
from parquet_tpu.ops.plain import decode_plain, encode_plain
from parquet_tpu.ops.rle_hybrid import (
    HybridError,
    decode_hybrid,
    encode_hybrid,
    prescan_hybrid,
)

rng = np.random.default_rng(42)


def _scalar_unpack(data: bytes, n: int, width: int) -> list[int]:
    """Independent scalar oracle: read bit i*W..(i+1)*W LSB-first."""
    out = []
    for i in range(n):
        v = 0
        for j in range(width):
            bitpos = i * width + j
            bit = (data[bitpos // 8] >> (bitpos % 8)) & 1
            v |= bit << j
        out.append(v)
    return out


class TestBitpack:
    @pytest.mark.parametrize("width", list(range(0, 65)))
    def test_roundtrip_every_width(self, width):
        n = 64
        hi = (1 << width) if width else 1
        vals = rng.integers(0, hi, size=n, dtype=np.uint64)
        packed = pack_bits(vals, width)
        out = unpack_bits(packed, n, width)
        np.testing.assert_array_equal(out, vals)

    @pytest.mark.parametrize("width", [1, 3, 7, 8, 13, 31, 32])
    def test_against_scalar_oracle(self, width):
        n = 24
        vals = rng.integers(0, 1 << width, size=n, dtype=np.uint64)
        packed = pack_bits(vals, width)
        assert _scalar_unpack(packed, n, width) == list(vals)

    def test_width_zero(self):
        assert unpack_bits(b"", 10, 0).tolist() == [0] * 10
        assert pack_bits(np.array([0, 0]), 0) == b""

    def test_too_short_raises(self):
        with pytest.raises(ValueError):
            unpack_bits(b"\x01", 9, 8)

    def test_bit_width(self):
        assert bit_width(0) == 0
        assert bit_width(1) == 1
        assert bit_width(255) == 8
        assert bit_width(256) == 9


class TestHybrid:
    @pytest.mark.parametrize("width", [1, 2, 5, 8, 12, 20, 32])
    def test_roundtrip_random(self, width):
        n = 1000
        vals = rng.integers(0, 1 << min(width, 31), size=n, dtype=np.uint32)
        data = encode_hybrid(vals, width)
        out = decode_hybrid(data, n, width)
        np.testing.assert_array_equal(out, vals)

    def test_roundtrip_rle_heavy(self):
        # Long constant stretches must roundtrip (and hit the RLE emit path).
        vals = np.repeat(np.array([3, 0, 7, 0], dtype=np.uint32), [100, 50, 9, 41])
        data = encode_hybrid(vals, 3)
        assert len(data) < len(vals)  # RLE actually engaged
        np.testing.assert_array_equal(decode_hybrid(data, len(vals), 3), vals)

    def test_roundtrip_alternating(self):
        vals = np.tile(np.array([0, 1], dtype=np.uint32), 500)
        data = encode_hybrid(vals, 1)
        np.testing.assert_array_equal(decode_hybrid(data, len(vals), 1), vals)

    def test_unaligned_tail(self):
        for n in [1, 7, 8, 9, 15, 17]:
            vals = rng.integers(0, 4, size=n, dtype=np.uint32)
            data = encode_hybrid(vals, 2)
            np.testing.assert_array_equal(decode_hybrid(data, n, 2), vals)

    def test_decodes_rle_run_stream(self):
        # Hand-built stream: RLE run of 300 x value 5 at width 3.
        out = bytearray()
        out.append((300 << 1) & 0x7F | 0x80)
        out.append((300 << 1) >> 7)
        out.append(5)
        vals = decode_hybrid(bytes(out), 300, 3)
        assert vals.tolist() == [5] * 300

    def test_rle_value_exceeding_width_rejected(self):
        # RLE run advertising value 9 at width 3 (max 7) must be rejected
        # (reference: hybrid_decoder.go:126-129).
        stream = bytes([2 << 1, 9])
        with pytest.raises(HybridError):
            decode_hybrid(stream, 2, 3)

    def test_truncated_stream_rejected(self):
        vals = rng.integers(0, 4, size=100, dtype=np.uint32)
        data = encode_hybrid(vals, 2)
        with pytest.raises(HybridError):
            decode_hybrid(data[: len(data) // 2], 100, 2)

    def test_prescan_structure(self):
        vals = np.concatenate(
            [np.full(64, 2, np.uint32), rng.integers(0, 8, 32, dtype=np.uint32)]
        )
        t = prescan_hybrid(encode_hybrid(vals, 3), len(vals), 3)
        assert t.total_values >= len(vals)
        assert t.is_rle.any()

    def test_width_zero_stream(self):
        data = encode_hybrid(np.zeros(100, np.uint32), 0)
        np.testing.assert_array_equal(decode_hybrid(data, 100, 0), np.zeros(100))


def _scalar_delta_decode(data: bytes, nbits: int):
    """Independent scalar oracle implementing the spec directly."""
    pos = 0

    def uvar():
        nonlocal pos
        r, s = 0, 0
        while True:
            b = data[pos]
            pos += 1
            r |= (b & 0x7F) << s
            if not b & 0x80:
                return r
            s += 7

    def zz():
        n = uvar()
        return (n >> 1) ^ -(n & 1)

    mask = (1 << nbits) - 1
    bs, mc, total, first = uvar(), uvar(), uvar(), zz()
    ml = bs // mc
    vals = [first & mask]
    while len(vals) < total:
        mind = zz()
        widths = list(data[pos : pos + mc])
        pos += mc
        for w in widths:
            payload = (ml // 8) * w
            if len(vals) >= total:
                pos += payload
                continue
            chunk = data[pos : pos + payload]
            pos += payload
            for i in range(min(ml, total - len(vals))):
                v = 0
                for j in range(w):
                    bitpos = i * w + j
                    v |= ((chunk[bitpos // 8] >> (bitpos % 8)) & 1) << j
                vals.append((vals[-1] + v + mind) & mask)
    sign = 1 << (nbits - 1)
    return [v - (1 << nbits) if v >= sign else v for v in vals[:total]]


class TestDelta:
    @pytest.mark.parametrize("nbits", [32, 64])
    def test_roundtrip_random(self, nbits):
        dt = np.int32 if nbits == 32 else np.int64
        vals = rng.integers(-(2**20), 2**20, size=1000).astype(dt)
        data = encode_delta(vals, nbits)
        out, consumed = decode_delta(data, nbits)
        np.testing.assert_array_equal(out, vals)
        assert consumed == len(data)

    @pytest.mark.parametrize("nbits", [32, 64])
    def test_overflow_extremes(self, nbits):
        # min-delta subtraction overflow semantics (reference: deltabp_encoder.go:58-61)
        dt = np.int32 if nbits == 32 else np.int64
        info = np.iinfo(dt)
        vals = np.array(
            [info.min, info.max, 0, info.min, info.max, -1, 1, info.max, info.min],
            dtype=dt,
        )
        data = encode_delta(vals, nbits)
        out, _ = decode_delta(data, nbits)
        np.testing.assert_array_equal(out, vals)

    def test_against_scalar_oracle(self):
        vals = rng.integers(-(2**30), 2**30, size=300).astype(np.int32)
        data = encode_delta(vals, 32)
        assert _scalar_delta_decode(data, 32) == vals.tolist()

    def test_sorted_timestamps(self):
        base = 1_600_000_000_000_000
        vals = (base + np.cumsum(rng.integers(0, 1000, size=5000))).astype(np.int64)
        data = encode_delta(vals, 64)
        out, _ = decode_delta(data, 64)
        np.testing.assert_array_equal(out, vals)
        assert len(data) < vals.nbytes // 4  # delta actually compresses

    @pytest.mark.parametrize("n", [0, 1, 2, 127, 128, 129, 257])
    def test_sizes(self, n):
        vals = rng.integers(-100, 100, size=n).astype(np.int64)
        out, _ = decode_delta(encode_delta(vals, 64), 64)
        np.testing.assert_array_equal(out, vals)

    def test_invalid_block_size_rejected(self):
        # header: block size 100 (not multiple of 128)
        data = bytes([100, 4, 1, 0])
        with pytest.raises(DeltaError):
            decode_delta(data, 32)

    def test_width_exceeding_type_rejected(self):
        vals = np.arange(10, dtype=np.int32)
        data = bytearray(encode_delta(vals, 32))
        # corrupt first miniblock width byte to 60 (> 32)
        # header is 4 varints: 128,4,10,zz(0) -> bytes [0x80 0x01, 0x04, 0x0a, 0x00]
        # then min-delta zigzag, then 4 width bytes
        data[6] = 60
        with pytest.raises(DeltaError):
            decode_delta(bytes(data), 32)


class TestPlain:
    @pytest.mark.parametrize(
        "ptype,dtype",
        [
            (Type.INT32, np.int32),
            (Type.INT64, np.int64),
            (Type.FLOAT, np.float32),
            (Type.DOUBLE, np.float64),
        ],
    )
    def test_numeric_roundtrip(self, ptype, dtype):
        if np.issubdtype(dtype, np.integer):
            vals = rng.integers(np.iinfo(dtype).min, np.iinfo(dtype).max, 500).astype(dtype)
        else:
            vals = rng.standard_normal(500).astype(dtype)
        data = encode_plain(vals, ptype)
        out, consumed = decode_plain(data, 500, ptype)
        np.testing.assert_array_equal(out, vals)
        assert consumed == len(data)

    def test_nan_bit_exact(self):
        # NaN payload preservation (reference CHANGELOG.md:31 NaN handling)
        v = np.array([np.nan, -np.nan, np.inf, -0.0], dtype=np.float64)
        out, _ = decode_plain(encode_plain(v, Type.DOUBLE), 4, Type.DOUBLE)
        np.testing.assert_array_equal(out.view(np.uint64), v.view(np.uint64))

    def test_boolean_roundtrip(self):
        for n in [1, 7, 8, 9, 100]:
            vals = rng.integers(0, 2, n).astype(bool)
            data = encode_plain(vals, Type.BOOLEAN)
            out, consumed = decode_plain(data, n, Type.BOOLEAN)
            np.testing.assert_array_equal(out, vals)
            assert consumed == (n + 7) // 8

    def test_int96_roundtrip(self):
        vals = rng.integers(0, 256, size=(20, 12)).astype(np.uint8)
        out, _ = decode_plain(encode_plain(vals, Type.INT96), 20, Type.INT96)
        np.testing.assert_array_equal(out, vals)

    def test_fixed_roundtrip(self):
        vals = rng.integers(0, 256, size=(20, 5)).astype(np.uint8)
        data = encode_plain(vals, Type.FIXED_LEN_BYTE_ARRAY, type_length=5)
        out, _ = decode_plain(data, 20, Type.FIXED_LEN_BYTE_ARRAY, type_length=5)
        np.testing.assert_array_equal(out, vals)

    def test_byte_array_roundtrip(self):
        items = [b"", b"a", b"hello world", bytes(rng.integers(0, 256, 100).astype(np.uint8))]
        ba = ByteArrayData.from_list(items)
        data = encode_plain(ba, Type.BYTE_ARRAY)
        out, consumed = decode_plain(data, len(items), Type.BYTE_ARRAY)
        assert out.to_list() == items
        assert consumed == len(data)

    def test_byte_array_bad_length_rejected(self):
        data = (1000).to_bytes(4, "little") + b"short"
        with pytest.raises(ValueError):
            decode_plain(data, 1, Type.BYTE_ARRAY)

    def test_truncated_numeric_rejected(self):
        with pytest.raises(ValueError):
            decode_plain(b"\x00" * 7, 1, Type.INT64)


class TestByteArrayDeltas:
    def test_delta_length_roundtrip(self):
        items = [b"alpha", b"", b"beta", b"gammagamma" * 10]
        ba = ByteArrayData.from_list(items)
        data = encode_delta_length_byte_array(ba)
        out, consumed = decode_delta_length_byte_array(data, len(items))
        assert out.to_list() == items
        assert consumed == len(data)

    def test_delta_byte_array_roundtrip(self):
        items = [b"apple", b"applesauce", b"application", b"banana", b"band", b""]
        ba = ByteArrayData.from_list(items)
        data = encode_delta_byte_array(ba)
        out, consumed = decode_delta_byte_array(data, len(items))
        assert out.to_list() == items
        assert consumed == len(data)

    def test_delta_byte_array_sorted_strings_compress(self):
        items = [f"user_{i:08d}".encode() for i in range(1000)]
        data = encode_delta_byte_array(ByteArrayData.from_list(items))
        assert len(data) < sum(len(x) for x in items) // 2
        out, _ = decode_delta_byte_array(data, 1000)
        assert out.to_list() == items


class TestDictIndices:
    def test_roundtrip(self):
        idx = rng.integers(0, 100_000, size=5000).astype(np.uint32)
        data = encode_dict_indices(idx, 100_000)
        out = decode_dict_indices(data, 5000, 100_000)
        np.testing.assert_array_equal(out, idx)

    def test_out_of_range_rejected(self):
        data = encode_dict_indices(np.array([0, 5], np.uint32), 6)
        with pytest.raises(DictError):
            decode_dict_indices(data, 2, 3)

    def test_invalid_width_rejected(self):
        with pytest.raises(DictError):
            decode_dict_indices(bytes([40, 0]), 1, 10)


class TestLevels:
    def test_v1_roundtrip(self):
        levels = rng.integers(0, 4, size=999).astype(np.uint16)
        data = encode_levels_v1(levels, 3)
        out, consumed = decode_levels_v1(data, 999, 3)
        np.testing.assert_array_equal(out, levels)
        assert consumed == len(data)

    def test_v2_roundtrip(self):
        levels = rng.integers(0, 2, size=512).astype(np.uint16)
        data = encode_levels_v2(levels, 1)
        np.testing.assert_array_equal(decode_levels_v2(data, 512, 1), levels)

    def test_max_level_zero(self):
        assert encode_levels_v1([], 0) == b""
        out, consumed = decode_levels_v1(b"anything", 5, 0)
        assert out.tolist() == [0] * 5
        assert consumed == 0

    def test_level_exceeding_max_rejected(self):
        # Hand-built RLE run of value 3 at width 2; max_level 2 makes 3 invalid.
        stream = bytes([4 << 1, 3])
        with pytest.raises(ValueError):
            decode_levels_v2(stream, 4, 2)


class TestCompress:
    @pytest.mark.parametrize(
        "codec",
        [
            CompressionCodec.UNCOMPRESSED,
            CompressionCodec.GZIP,
            CompressionCodec.SNAPPY,
            CompressionCodec.ZSTD,
        ],
    )
    def test_roundtrip(self, codec):
        data = b"parquet " * 1000 + bytes(rng.integers(0, 256, 1000).astype(np.uint8))
        comp = compress.compress_block(data, codec)
        out = compress.decompress_block(comp, codec, len(data))
        assert out == data
        if codec != CompressionCodec.UNCOMPRESSED:
            assert len(comp) < len(data)

    def test_snappy_interop_with_pyarrow(self):
        import pyarrow as pa

        data = b"the quick brown fox " * 500
        ours = compress.compress_block(data, CompressionCodec.SNAPPY)
        assert pa.Codec("snappy").decompress(ours, decompressed_size=len(data)).to_pybytes() == data
        theirs = pa.Codec("snappy").compress(data).to_pybytes()
        assert compress.decompress_block(theirs, CompressionCodec.SNAPPY, len(data)) == data

    def test_size_mismatch_rejected(self):
        comp = compress.compress_block(b"hello", CompressionCodec.GZIP)
        with pytest.raises(compress.CompressionError):
            compress.decompress_block(comp, CompressionCodec.GZIP, 999)

    def test_unregistered_codec_rejected(self):
        with pytest.raises(compress.CompressionError):
            compress.compress_block(b"x", CompressionCodec.LZO)

    def test_zstd_pages_decompress_on_pool_threads_at_once(self):
        """A zstandard context is not thread-safe and its calls release the
        GIL; chunks prepare on pool threads, so every thread has its own
        (one shared context gave 'Data corruption detected' on good pages)."""
        from concurrent.futures import ThreadPoolExecutor

        pages = [bytes(rng.integers(0, 4, 80_000).astype(np.uint8)) for _ in range(8)]
        comp = [compress.compress_block(p, CompressionCodec.ZSTD) for p in pages]
        codec = compress._get(CompressionCodec.ZSTD)
        together = threading.Barrier(8)

        def work(k):
            together.wait(30)
            for _ in range(50):
                assert compress.decompress_block(comp[k], CompressionCodec.ZSTD, len(pages[k])) == pages[k]
            return codec._contexts()

        with ThreadPoolExecutor(8) as pool:
            contexts = list(pool.map(work, range(8)))
        assert len({id(c) for pair in contexts for c in pair}) == 16


class TestNativeParity:
    """The C fast paths (native/parquet_tpu_native.cc) must agree bit-for-bit
    with the NumPy reference implementations on values, consumed counts, and
    error behavior. Skipped when the library isn't built."""

    @pytest.fixture()
    def native(self):
        from parquet_tpu.utils.native import get_native

        lib = get_native()
        if lib is None:
            pytest.skip("native library not built")
        return lib

    @pytest.fixture()
    def numpy_only(self):
        """Force the pure-NumPy implementations for the duration of a test."""
        from parquet_tpu.utils import native as native_mod

        old = native_mod._cached, native_mod._probed
        native_mod._cached, native_mod._probed = None, True
        yield
        native_mod._cached, native_mod._probed = old

    def test_delta_decode_matches_numpy(self, native):
        from parquet_tpu.ops.delta import prescan_delta

        r = np.random.default_rng(7)
        for nbits, dt in ((32, np.int32), (64, np.int64)):
            for n in (0, 1, 2, 127, 128, 129, 4096):
                v = r.integers(-(10**6), 10**6, n).astype(dt)
                enc = encode_delta(v, nbits)
                got, consumed = native.delta_decode(enc, nbits, n)
                assert np.array_equal(got.view(dt), v)
                if n:
                    assert consumed == prescan_delta(enc, nbits, n).consumed

    def test_delta_decode_wrapping(self, native):
        v = np.array([2**62, -(2**62), 5, 2**62 - 1, -1], dtype=np.int64)
        enc = encode_delta(v, 64)
        got, _ = native.delta_decode(enc, 64, len(v))
        assert np.array_equal(got, v)

    def test_hybrid_encode_matches_numpy(self, native, numpy_only):
        """The C hybrid encoder must be byte-identical to encode_hybrid:
        same RLE run selection, same 8-alignment, same trailing padding."""
        from parquet_tpu.ops.rle_hybrid import encode_hybrid

        r = np.random.default_rng(13)
        for trial in range(120):
            w = int(r.integers(1, 33))
            n = int(r.integers(0, 600))
            style = trial % 4
            if style == 0:
                v = r.integers(0, 1 << w, n, dtype=np.uint64)
            elif style == 1:  # one long run
                v = np.full(n, int(r.integers(0, 1 << w)), dtype=np.uint64)
            elif style == 2:  # short runs straddling 8-boundaries
                reps = r.integers(0, 1 << w, max(n // 9, 1), dtype=np.uint64)
                v = np.repeat(reps, 9)[:n]
            else:
                v = np.zeros(n, dtype=np.uint64)
            ref = encode_hybrid(v, w)  # numpy path (native forced off)
            assert native.hybrid_encode(v, w) == ref, (trial, w, n)

    def test_delta_encode_matches_numpy(self, native, numpy_only):
        from parquet_tpu.ops.delta import encode_delta as enc_py

        r = np.random.default_rng(17)
        for nbits, dt in ((32, np.int32), (64, np.int64)):
            for n in (0, 1, 2, 100, 127, 128, 129, 513, 4096):
                v = r.integers(np.iinfo(dt).min // 2, np.iinfo(dt).max // 2, n).astype(dt)
                assert native.delta_encode(v, nbits, 128, 4) == enc_py(v, nbits)
            # wrap-around deltas
            v = np.array(
                [np.iinfo(dt).min, np.iinfo(dt).max, 0, -1, 1], dtype=dt
            )
            assert native.delta_encode(v, nbits, 128, 4) == enc_py(v, nbits)

    def test_delta_encode_exotic_mini_count_no_crash(self, native):
        """mini_count > 512 exceeds every decoder's cap (and the C encoder's
        widths buffer — a stack overflow before the guard): it must take the
        NumPy path, not crash."""
        from parquet_tpu.ops.delta import encode_delta as enc_py

        v = np.arange(20_000, dtype=np.int64)
        enc = enc_py(v, 64, block_size=8192, mini_count=1024)
        assert len(enc) > 0
        # and the C entry point itself rejects it instead of overflowing
        import ctypes

        out = np.empty(1 << 20, dtype=np.uint8)
        rc = native._lib.ptq_delta_encode(
            ctypes.c_void_p(v.ctypes.data), len(v), 64, 8192, 1024,
            ctypes.c_void_p(out.ctypes.data), len(out),
        )
        assert rc == -1

    def test_bytes_dict_probe_matches_python(self, native):
        from parquet_tpu.core.arrays import ByteArrayData

        r = np.random.default_rng(23)
        for trial in range(20):
            n = int(r.integers(0, 1500))
            items = [f"v{int(x)}".encode() for x in r.integers(0, 60, n)]
            ba = ByteArrayData.from_list(items)
            firsts, indices = native.bytes_dict_indices(ba.data, ba.offsets, 32767)
            uniq: dict = {}
            for i, it in enumerate(items):
                uniq.setdefault(it, len(uniq))
            assert [items[f] for f in firsts] == list(uniq)
            assert [int(i) for i in indices] == [uniq[it] for it in items]
        # cutoff: more uniques than the cap returns None
        items = [f"u{i}".encode() for i in range(40_000)]
        ba = ByteArrayData.from_list(items)
        assert native.bytes_dict_indices(ba.data, ba.offsets, 32767) is None

    def test_u64_dict_probe_and_minmax(self, native):
        r = np.random.default_rng(29)
        v = r.integers(0, 50, 3000, dtype=np.uint64)
        firsts, indices = native.u64_dict_indices(v, 32767)
        uniq: dict = {}
        for x in v.tolist():
            uniq.setdefault(x, len(uniq))
        assert [int(v[f]) for f in firsts] == list(uniq)
        assert all(int(v[firsts[i]]) == int(x) for x, i in zip(v.tolist(), indices))
        assert native.u64_dict_indices(
            np.arange(40_000, dtype=np.uint64), 32767
        ) is None
        # byte-array lexicographic minmax incl. prefix ties
        from parquet_tpu.core.arrays import ByteArrayData

        items = [b"bb", b"b", b"ba", b"bbb", b"a", b"ab"]
        ba = ByteArrayData.from_list(items)
        i_mn, i_mx = native.bytes_minmax(ba.data, ba.offsets)
        assert items[i_mn] == min(items) and items[i_mx] == max(items)

    def test_delta_rejects_oversized_claim(self, native):
        v = np.arange(100, dtype=np.int32)
        enc = encode_delta(v, 32)
        with pytest.raises(OverflowError):
            native.delta_decode(enc, 32, 50)

    def test_delta_rejects_implausible_header_before_alloc(self, native):
        out = bytearray()
        from parquet_tpu.ops.varint import emit_uvarint, emit_zigzag

        emit_uvarint(out, 128)  # block size
        emit_uvarint(out, 4)  # miniblocks
        emit_uvarint(out, 1 << 40)  # absurd value count for a tiny stream
        emit_zigzag(out, 0)
        with pytest.raises(ValueError):
            native.delta_decode(bytes(out), 32, None)

    def test_hybrid_decode_matches_numpy(self, native):
        r = np.random.default_rng(8)
        for width in (0, 1, 3, 8, 13, 24, 32, 47, 64):
            n = 1000
            hi = 1 << min(width, 48) if width else 1
            vals = r.integers(0, hi, n, dtype=np.uint64)
            enc = encode_hybrid(vals, width)
            nbits = 32 if width <= 32 else 64
            got, _ = native.hybrid_decode(enc, n, width, nbits)
            assert np.array_equal(got.astype(np.uint64), vals), width

    def test_hybrid_rejects_rle_value_over_width(self, native):
        # RLE run header (count 8, low bit 0) with a 1-byte value of 7 at width 2
        bad = bytes([8 << 1, 7])
        with pytest.raises(ValueError):
            native.hybrid_decode(bad, 8, 2, 32)

    def test_bytearray_take_matches_numpy(self, native, numpy_only):
        r = np.random.default_rng(9)
        items = [bytes([65 + i % 26]) * (i % 17) for i in range(300)]
        ba = ByteArrayData.from_list(items)
        idx = r.integers(0, 300, 5000)
        want = ba.take(idx)  # numpy path (fixture forces it)
        from parquet_tpu.utils import native as native_mod

        native_mod._cached, native_mod._probed = native, True
        got = ba.take(idx)
        assert got == want

    def test_varint_overflow_rejected_both_paths(self, native, numpy_only):
        from parquet_tpu.ops.varint import read_uvarint

        # 10-byte varint encoding a value >= 2**64
        bad = bytes([0xFF] * 9 + [0x7F])
        with pytest.raises(ValueError):
            read_uvarint(bad, 0, len(bad))


class TestPlainByteArrayEncodeNative:
    def test_c_encode_matches_python_loop(self):
        """The C PLAIN byte-array encoder is byte-identical to the Python
        oracle, including empty strings, empty columns, and long values."""
        from parquet_tpu.core.arrays import byte_array_from_items
        from parquet_tpu.ops.plain import encode_plain
        from parquet_tpu.utils.native import get_native

        lib = get_native()
        if lib is None or not lib.has_plain_encode_ba:
            pytest.skip("native plain encoder not built")
        for items in (
            [b"", b"a", b"bb" * 500, b"", b"xyz"],
            [],
            [b"\x00" * 7] * 100,
            [bytes([i % 256]) * (i % 13) for i in range(1000)],
        ):
            ba = byte_array_from_items(items)
            want = bytearray()
            for it in items:
                want += len(it).to_bytes(4, "little") + it
            got = encode_plain(ba, Type.BYTE_ARRAY)
            assert got == bytes(want), len(items)
            assert lib.plain_encode_bytearray(ba.data, ba.offsets) == bytes(want)
