"""Kernel-level checks of the two decode kernels against a plain numpy
expansion of the same tables (tests/test_tpu_backend.py drives them through
whole files). The tables are built here in the upload layout of
kernels/pipeline.py's freeze functions: padding entries of every start
table hold n_pad + 1, zero-length runs repeat a start."""

import numpy as np
import pytest

import jax.numpy as jnp

from parquet_tpu.kernels.device_ops import (
    _segment_of,
    _spread,
    delta_packed_decode_device,
    expand_hybrid_device,
)


def _pack_lsb(values, width: int) -> np.ndarray:
    """LSB-first bit stream (uint8 0/1 array) of `values` at `width` bits each."""
    shifts = np.arange(width, dtype=np.uint64)
    values = np.asarray(values, dtype=np.uint64)
    return ((values[:, None] >> shifts[None, :]) & np.uint64(1)).astype(np.uint8).reshape(-1)


def _words(bits: np.ndarray, dtype) -> np.ndarray:
    """The bit stream as little-endian words, zero-padded to a power-of-two
    bucket that leaves room for the guard word."""
    raw = np.packbits(bits, bitorder="little").tobytes()
    raw += b"\x00" * ((-len(raw)) % np.dtype(dtype).itemsize)
    got = np.frombuffer(raw, dtype=dtype)
    w_pad = 1024
    while w_pad <= len(got):
        w_pad <<= 1
    out = np.zeros(w_pad, dtype=dtype)
    out[: len(got)] = got
    return out


# -- expand_hybrid_device -------------------------------------------------------


def _hybrid_case(counts, is_rle, width, run_pad, n_pad, rle_bit_start=0):
    """(buf, expected[:total]) for runs of `counts` values each. An RLE run's
    bit_start is read by nobody; `rle_bit_start` is what the table holds there
    (a batch near MAX_DEVICE_BATCH_BITS leaves the payload's end in it)."""
    rng = np.random.default_rng(0)
    counts = np.asarray(counts, dtype=np.int64)
    is_rle = np.asarray(is_rle, dtype=bool)
    k = len(counts)
    total = int(counts.sum())
    assert k <= run_pad and total <= n_pad
    out_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    top = 1 << width
    rle_value = rng.integers(0, top, size=k).astype(np.uint32)
    expected = np.zeros(total, dtype=np.uint32)
    bit_start = np.zeros(k, dtype=np.int64)
    packed = []
    bits_so_far = 0
    for r in range(k):
        a, c = int(out_start[r]), int(counts[r])
        if is_rle[r]:
            expected[a : a + c] = rle_value[r]
            bit_start[r] = rle_bit_start
            continue
        vals = rng.integers(0, top, size=c).astype(np.uint32)
        expected[a : a + c] = vals
        bit_start[r] = bits_so_far
        packed.append(vals)
        bits_so_far += c * width
    flat = np.concatenate(packed) if packed else np.zeros(0, np.uint32)
    words = _words(_pack_lsb(flat, width), np.uint32)
    buf = np.zeros(4 * run_pad + len(words), dtype=np.uint32)
    buf[run_pad : 2 * run_pad] = np.int32(n_pad + 1).view(np.uint32)  # sentinel
    buf[:k] = is_rle
    buf[run_pad : run_pad + k] = out_start.astype(np.int32).view(np.uint32)
    buf[2 * run_pad : 2 * run_pad + k] = rle_value
    buf[3 * run_pad : 3 * run_pad + k] = bit_start.astype(np.int32).view(np.uint32)
    buf[4 * run_pad :] = words
    return buf, expected


def _many_runs(k, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 4, size=k), rng.integers(0, 2, size=k)


_HYBRID_CASES = {
    # name: (counts, is_rle, width, run_pad, n_pad)
    "single-rle-run": ([1024], [1], 3, 64, 1024),
    "single-bitpacked-run": ([1024], [0], 5, 64, 1024),
    "run_pad-64-mixed": ([8, 100, 16, 1, 899], [0, 1, 0, 1, 0], 3, 64, 1024),
    "run_pad-65536": (*_many_runs(40_000, 1), 2, 65536, 131072),
    "run_pad-4096-full-table": (*_many_runs(4096, 2), 7, 4096, 16384),
    "zero-length-run-in-the-middle": ([40, 0, 60, 0, 0, 924], [1, 0, 0, 1, 0, 1], 4, 64, 1024),
    "zero-length-run-at-the-end": ([500, 524, 0], [0, 1, 0], 3, 64, 1024),
    "zero-length-run-at-the-end-short": ([300, 200, 0, 0], [0, 1, 1, 0], 3, 64, 1024),
    "zero-length-run-first": ([0, 0, 1000], [1, 0, 0], 6, 64, 1024),
    "total-below-n_pad": ([700, 301], [0, 1], 9, 64, 2048),
    "width-0": ([10, 1014], [1, 0], 0, 64, 1024),
    "rle-only": ([1, 2, 3, 1018], [1, 1, 1, 1], 1, 64, 1024),
    "bitpacked-only": ([8, 16, 1000], [0, 0, 0], 12, 64, 1024),
    "width-32": ([100, 200], [0, 1], 32, 64, 1024),
    # what bringing base = bit_start - out_start * width to the values could break
    "long-rle-run-first-negative-base": ([900, 124], [1, 0], 13, 64, 1024),
    "rle-runs-between-bitpacked-negative-base": ([300, 8, 500, 16, 200], [1, 0, 1, 0, 1], 14, 64, 1024),
    "rle-run-past-the-payload-end": ([8, 60_000], [0, 1], 13, 64, 65536),
    "rle-run-past-the-payload-end-width-1": ([8, 100_000, 8], [0, 1, 0], 1, 64, 131072),
    "width-32-rle-first": ([500, 100, 424], [1, 0, 1], 32, 64, 1024),
    "width-32-bitpacked-only": ([1024], [0], 32, 64, 1024),
    "rle-bit_start-near-2^31": ([24, 500, 500], [0, 1, 0], 3, 64, 1024, (1 << 31) - 8),
    "rle-bit_start-near-2^31-width-32": ([8, 1000, 16], [0, 1, 0], 32, 64, 1024, (1 << 31) - 32),
    "run_pad-4096-long-rle-runs": (
        np.tile([3000, 8], 2048), np.tile([1, 0], 2048), 3, 4096, 1 << 23),
}


@pytest.mark.parametrize("case", sorted(_HYBRID_CASES), ids=sorted(_HYBRID_CASES))
def test_expand_hybrid_equals_numpy_expansion(case):
    counts, is_rle, width, run_pad, n_pad, *rest = _HYBRID_CASES[case]
    buf, expected = _hybrid_case(counts, is_rle, width, run_pad, n_pad, *rest)
    got = np.asarray(expand_hybrid_device(jnp.asarray(buf), width, n_pad, run_pad))
    assert got.shape == (n_pad,) and got.dtype == np.uint32
    # positions past the table's total belong to no run: callers slice them off
    np.testing.assert_array_equal(got[: len(expected)], expected)


# -- delta_packed_decode_device -------------------------------------------------


def _delta_case(page_sizes, nbits, m_pad, p_pad, n_pad, max_width=None, min_or=0):
    """(meta32, wide, expected[:total]): pages of the given value counts,
    miniblocks of 32 deltas, each with its own width and min (`min_or` is
    or-ed into every min, shifted to the top of the value's width)."""
    rng = np.random.default_rng(0)
    ud = np.uint32 if nbits == 32 else np.uint64
    max_width = nbits if max_width is None else max_width
    widths, bit_starts, out_starts, mins, adj_all = [], [], [], [], []
    page_start, page_first, expected = [], [], []
    base = 0
    bits_so_far = 0
    for size in page_sizes:
        first = ud(rng.integers(0, 1 << 31))
        page_start.append(base)
        page_first.append(first)
        vals = np.zeros(size, dtype=ud)
        vals[0] = first
        deltas = np.zeros(size - 1, dtype=ud)
        for a in range(0, size - 1, 32):
            c = min(32, size - 1 - a)
            w = int(rng.integers(0, max_width + 1))
            adj = (
                rng.integers(0, 1 << w, size=c, dtype=np.uint64, endpoint=False)
                if w < 64
                else rng.integers(0, 1 << 63, size=c, dtype=np.uint64) * 2 + 1
            )
            mn = rng.integers(0, np.iinfo(ud).max, dtype=ud, endpoint=True)
            mn |= ud(min_or << (nbits - 8))
            deltas[a : a + c] = adj.astype(ud) + mn  # wraps, as a negative min does
            widths.append(w)
            bit_starts.append(bits_so_far)
            out_starts.append(base + 1 + a)
            mins.append(mn)
            adj_all.append((adj, w))
            bits_so_far += c * w
        vals[1:] = first + np.cumsum(deltas, dtype=ud)
        expected.append(vals)
        base += size
    total = base
    m, p = len(widths), len(page_sizes)
    assert m <= m_pad and p <= p_pad and total <= n_pad
    bits = np.concatenate([np.zeros(0, np.uint8)] + [_pack_lsb(a, w) for a, w in adj_all])
    words = _words(bits, ud)
    sentinel = np.int32(n_pad + 1).view(np.uint32)
    tail32 = (m_pad + p_pad + len(words)) if nbits == 32 else 0
    meta32 = np.zeros(3 * m_pad + p_pad + tail32, dtype=np.uint32)
    meta32[2 * m_pad : 3 * m_pad] = sentinel
    meta32[3 * m_pad : 3 * m_pad + p_pad] = sentinel
    meta32[:m] = widths
    meta32[m_pad : m_pad + m] = np.asarray(bit_starts, np.int32).view(np.uint32)
    meta32[2 * m_pad : 2 * m_pad + m] = np.asarray(out_starts, np.int32).view(np.uint32)
    meta32[3 * m_pad : 3 * m_pad + p] = np.asarray(page_start, np.int32).view(np.uint32)
    if nbits == 32:
        b = 3 * m_pad + p_pad
        meta32[b : b + m] = mins
        meta32[b + m_pad : b + m_pad + p] = page_first
        meta32[b + m_pad + p_pad :] = words
        wide = np.zeros(0, dtype=np.uint32)
    else:
        wide = np.zeros(m_pad + p_pad + len(words), dtype=np.uint64)
        wide[:m] = mins
        wide[m_pad : m_pad + p] = page_first
        wide[m_pad + p_pad :] = words
    return meta32, wide, np.concatenate(expected)


_DELTA_CASES = {
    # name: (page_sizes, m_pad, p_pad, n_pad, max_width)
    "one-page-p_pad-64": ([1024], 64, 64, 1024, None),
    "one-page-one-value": ([1], 64, 64, 1024, None),
    "several-pages": ([100, 33, 1, 500, 34, 356], 64, 64, 1024, None),
    "page-of-one-value-between-pages": ([65, 1, 1, 957], 64, 64, 1024, None),
    "total-below-n_pad": ([300, 301], 64, 64, 2048, None),
    "m_pad-4096": ([20_000, 20_000, 25_000], 4096, 64, 65536, 20),
    "full-miniblock-table": ([2048], 64, 64, 2048, 9),
    # what bringing width, base, min and the page offset to the values could break
    "page-of-one-value-last": ([500, 1], 64, 64, 1024, None),
    "pages-of-one-value-only": ([1] * 40, 64, 64, 1024, None),
    "mins-with-the-top-bit-set": ([300, 33, 691], 64, 64, 1024, None, 0x80),
    "mins-all-ones-on-top": ([1024], 64, 64, 1024, 12, 0xFF),
    "hundreds-of-pages": ([8] * 300 + [70] * 20, 512, 512, 4096, None),
    "full-page-table": ([16] * 64, 64, 64, 1024, None),
    "width-0-miniblocks-only": ([700, 324], 64, 64, 1024, 0),
}


@pytest.mark.parametrize("nbits", [32, 64])
@pytest.mark.parametrize("case", sorted(_DELTA_CASES), ids=sorted(_DELTA_CASES))
def test_delta_decode_equals_numpy_expansion(case, nbits):
    page_sizes, m_pad, p_pad, n_pad, max_width, *rest = _DELTA_CASES[case]
    meta32, wide, expected = _delta_case(
        page_sizes, nbits, m_pad, p_pad, n_pad, max_width, *rest
    )
    got = np.asarray(
        delta_packed_decode_device(
            jnp.asarray(meta32), jnp.asarray(wide), nbits, n_pad, m_pad, p_pad
        )
    )
    assert got.shape == (n_pad,)
    assert got.dtype == (np.int32 if nbits == 32 else np.int64)
    np.testing.assert_array_equal(got[: len(expected)], expected.view(got.dtype))


# -- what both kernels share: the lookup, and a field brought to its segment ---

_SEGMENT_CASES = {
    "one-start": ([0], 64, 1024),
    "no-start-at-zero": ([1, 700], 64, 1024),  # the delta tables: -1 at i = 0
    "repeated-starts": ([0, 5, 5, 5, 9, 1024, 1024], 64, 2048),
    "start-at-the-last-position": ([0, 1023], 64, 1024),
    "start-at-num_values-is-dropped": ([0, 512, 1024], 64, 1024),
    "every-position-starts": (list(range(4096)), 4096, 4096),
    "num_values-below-the-scan-block": ([0, 3, 3, 60], 64, 64),
}


@pytest.mark.parametrize("case", sorted(_SEGMENT_CASES), ids=sorted(_SEGMENT_CASES))
def test_segment_of_equals_searchsorted(case):
    starts, pad, n = _SEGMENT_CASES[case]
    table = np.full(pad, n + 1, dtype=np.int32)
    table[: len(starts)] = starts
    got = np.asarray(_segment_of(jnp.asarray(table), n))
    want = np.searchsorted(table, np.arange(n), side="right") - 1
    np.testing.assert_array_equal(got, want)


def _evenly(k, n):
    return np.unique(np.linspace(0, n - 1, k).astype(np.int64)).tolist()


_SPREAD_CASES = {
    # name: (starts, table length, num_values)
    "single-segment": ([0], 64, 1024),
    "no-start-at-zero": ([3, 700], 64, 1024),  # 0 before the first start
    "repeated-starts-first": ([0, 0, 0, 40, 900], 64, 1024),
    "repeated-starts-in-the-middle": ([0, 5, 5, 5, 9, 600], 64, 1024),
    "repeated-starts-last": ([0, 17, 1023, 1023, 1023], 64, 1024),
    "start-at-num_values-is-dropped": ([0, 512, 1024, 1024], 64, 1024),
    "padding-at-n_pad+1-only": ([], 64, 1024),
    "full-table-of-64": (_evenly(64, 1024), 64, 1024),
    "table-of-4096": (_evenly(3000, 1 << 16), 4096, 1 << 16),
    "table-of-65536-every-position-starts": (list(range(65536)), 65536, 65536),
    "num_values-below-the-scan-block": ([0, 3, 3, 60], 64, 64),
}


@pytest.mark.parametrize("dtype", ["uint32", "int32", "uint64"])
@pytest.mark.parametrize("case", sorted(_SPREAD_CASES), ids=sorted(_SPREAD_CASES))
def test_spread_equals_the_gather_through_segment_of(case, dtype):
    starts, pad, n = _SPREAD_CASES[case]
    table = np.full(pad, n + 1, dtype=np.int32)
    table[: len(starts)] = starts
    info = np.iinfo(dtype)
    rng = np.random.default_rng(len(starts))
    field = rng.integers(info.min, info.max, size=pad, dtype=dtype, endpoint=True)
    # neighbours at the dtype's two ends: every difference between them wraps
    field[0 : min(len(starts), 8) : 2] = info.max
    field[1 : min(len(starts), 8) : 2] = info.min
    field[len(starts) :] = 0  # the freeze functions leave padding at zero
    got = np.asarray(_spread(jnp.asarray(table), jnp.asarray(field), n))
    assert got.dtype == field.dtype and got.shape == (n,)
    segment = np.searchsorted(table, np.arange(n), side="right") - 1
    want = np.where(segment >= 0, field[segment], 0).astype(dtype)
    np.testing.assert_array_equal(got, want)
    # the contract as the docstring words it
    through = np.asarray(_segment_of(jnp.asarray(table), n))
    np.testing.assert_array_equal(got[through >= 0], field[through[through >= 0]])
