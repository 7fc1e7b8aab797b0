"""Kernel-level checks of the two decode kernels against a plain numpy
expansion of the same tables (tests/test_tpu_backend.py drives them through
whole files). The uploads are built by the packers that live beside the
kernels (pack_hybrid_upload, pack_delta_upload: the one statement of the
layout), so each case checks kernel after packer against an independent
answer; the literal goldens at the end pin the layouts themselves."""

import numpy as np
import pytest

import jax.numpy as jnp

from parquet_tpu.kernels.device_ops import (
    _segment_of,
    _spread,
    delta_packed_decode_device,
    expand_hybrid_device,
    pack_delta_upload,
    pack_hybrid_upload,
)
from parquet_tpu.ops.rle_hybrid import (
    _emit_bitpacked,
    _emit_uvarint,
    decode_hybrid,
    encode_hybrid,
    prescan_hybrid,
)


def _pack_lsb(values, width: int) -> np.ndarray:
    """LSB-first bit stream (uint8 0/1 array) of `values` at `width` bits each."""
    shifts = np.arange(width, dtype=np.uint64)
    values = np.asarray(values, dtype=np.uint64)
    return ((values[:, None] >> shifts[None, :]) & np.uint64(1)).astype(np.uint8).reshape(-1)


def _wire(bits: np.ndarray) -> bytes:
    return np.packbits(bits, bitorder="little").tobytes()


# -- expand_hybrid_device -------------------------------------------------------


def _shipped(width: int) -> int:
    """The rule, written out again: the least number >= width with at most
    two set bits (one plane a set bit)."""
    return next(w for w in range(width, 33) if bin(w).count("1") <= 2)


def _hybrid_case(counts, is_rle, width, rle_bit_start=0):
    """(frozen upload, expected[:total]) for runs of `counts` values each. An
    RLE run's bit_start is read by nobody; `rle_bit_start` is what the table
    holds there (a batch near MAX_DEVICE_BATCH_BITS leaves the payload's end
    in it)."""
    rng = np.random.default_rng(0)
    counts = np.asarray(counts, dtype=np.int64)
    is_rle = np.asarray(is_rle, dtype=bool)
    k = len(counts)
    total = int(counts.sum())
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
    frozen, seconds = pack_hybrid_upload(
        is_rle, counts, rle_value, bit_start, _wire(_pack_lsb(flat, width)), width
    )
    assert seconds >= 0
    return frozen, expected


def _many_runs(k, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(1, 4, size=k), rng.integers(0, 2, size=k)


_HYBRID_CASES = {
    # name: (counts, is_rle, width, the n_pad the packer's bucket gives[, an RLE run's bit_start])
    "single-rle-run": ([1024], [1], 3, 1024),
    "single-bitpacked-run": ([1024], [0], 5, 1024),
    "5-runs-mixed": ([8, 100, 16, 1, 899], [0, 1, 0, 1, 0], 3, 1024),
    "40000-runs": (*_many_runs(40_000, 1), 2, 131072),
    "4096-runs": (*_many_runs(4096, 2), 7, 8192),
    "zero-length-run-in-the-middle": ([40, 0, 60, 0, 0, 924], [1, 0, 0, 1, 0, 1], 4, 1024),
    "zero-length-run-at-the-end": ([500, 524, 0], [0, 1, 0], 3, 1024),
    "zero-length-run-at-the-end-short": ([300, 200, 0, 0], [0, 1, 1, 0], 3, 1024),
    "zero-length-run-first": ([0, 0, 1000], [1, 0, 0], 6, 1024),
    "zero-length-runs-only": ([0, 0, 0], [1, 0, 1], 5, 1024),
    "total-below-n_pad": ([700, 301], [0, 1], 9, 1024),
    "width-0": ([10, 1014], [1, 0], 0, 1024),
    "rle-only": ([1, 2, 3, 1018], [1, 1, 1, 1], 1, 1024),
    "bitpacked-only": ([8, 16, 1000], [0, 0, 0], 12, 1024),
    "width-32": ([100, 200], [0, 1], 32, 1024),
    "long-rle-run-first": ([900, 124], [1, 0], 13, 1024),
    "rle-runs-between-bitpacked": ([300, 8, 500, 16, 200], [1, 0, 1, 0, 1], 14, 1024),
    "rle-run-past-the-payload-end": ([8, 60_000], [0, 1], 13, 65536),
    "rle-run-past-the-payload-end-width-1": ([8, 100_000, 8], [0, 1, 0], 1, 131072),
    "width-32-rle-first": ([500, 100, 424], [1, 0, 1], 32, 1024),
    "width-32-bitpacked-only": ([1024], [0], 32, 1024),
    "rle-bit_start-near-2^31": ([24, 500, 500], [0, 1, 0], 3, 1024, (1 << 31) - 8),
    "rle-bit_start-near-2^31-width-32": ([8, 1000, 16], [0, 1, 0], 32, 1024, (1 << 31) - 32),
    "rle-bit_start-negative": ([24, 500, 500], [0, 1, 0], 9, 1024, -8),
    "4096-runs-long-rle-runs": (np.tile([3000, 8], 2048), np.tile([1, 0], 2048), 3, 1 << 23),
    # the frame's own edges: a run longer than the writer's block of 1,024
    # values, runs across a plane's wrap (slot L of a plane of L words), every
    # slot real, and the widths that ship rounded up
    "runs-across-the-planes-wraps": ([31, 2, 30, 3, 1000, 7, 975], [0, 1, 0, 1, 0, 1, 0], 17, 2048),
    "every-slot-real": ([1000, 1048], [0, 1], 6, 2048),
    "bitpacked-run-of-5000": ([5000, 3000, 192], [0, 1, 0], 10, 8192),
    "width-7-ships-as-8": ([100, 900], [1, 0], 7, 1024),
    "width-11-ships-as-12": ([400, 300, 324], [0, 1, 0], 11, 1024),
    "width-15-ships-as-16": ([1000], [0], 15, 1024),
    "width-21-ships-as-24": ([10, 1000], [1, 0], 21, 1024),
    "width-25-ships-as-32": ([600, 400], [0, 1], 25, 1024),
    "width-31-ships-as-32": ([1024], [0], 31, 1024),
}


@pytest.mark.parametrize("case", sorted(_HYBRID_CASES), ids=sorted(_HYBRID_CASES))
def test_expand_hybrid_equals_numpy_expansion(case):
    counts, is_rle, width, n_pad, *rest = _HYBRID_CASES[case]
    f, expected = _hybrid_case(counts, is_rle, width, *rest)
    # the shape the case is named for is the one the packer's bucket gives
    assert (f.width, f.n_pad, f.total, f.run_pad) == (_shipped(width), n_pad, len(expected), 0)
    if "-ships-as-" in case:
        assert f.width == int(case.rsplit("-", 1)[1])
    # the upload's length: a function of (shipped width, n_pad) and nothing else
    assert f.buf.dtype == np.uint32 and f.buf.shape == (n_pad * f.width // 32,)
    got = np.asarray(expand_hybrid_device(jnp.asarray(f.buf), f.width, f.n_pad))
    assert got.shape == (n_pad,) and got.dtype == np.uint32
    np.testing.assert_array_equal(got[: len(expected)], expected)
    assert not got[len(expected) :].any()  # the slots past the total hold 0


def test_two_streams_of_other_runs_and_wire_sizes_give_one_shape():
    """The padded delivery's contract, for every chunk: neither the count of
    runs nor the payload's size reaches the upload's shape."""
    few, _ = _hybrid_case([3000, 8], [1, 0], 9)  # 2 runs, 9 bytes of payload
    many, _ = _hybrid_case(*_many_runs(1800, 3), 9)  # 1,800 runs
    dense, _ = _hybrid_case([4096], [0], 9)  # one run, 4,608 bytes of payload
    assert few.total != many.total != dense.total
    assert {(f.width, f.n_pad, f.buf.shape) for f in (few, many, dense)} == {(9, 4096, (4096 * 9 // 32,))}


@pytest.mark.parametrize("width", [1, 3, 8, 9, 12, 16, 17, 24, 32])
def test_hybrid_expand_reads_no_position_through_an_index(width):
    """The lowered kernel holds no gather, no dynamic slice and no array of
    n_pad indices (an iota): the frame is position-indexed, so the planes
    unpack by static shifts and one concatenation each."""
    import jax

    n_pad = 4096
    jaxpr = jax.make_jaxpr(lambda x: expand_hybrid_device(x, width=width, num_values=n_pad))(
        jnp.zeros(n_pad * width // 32, jnp.uint32)
    )
    seen = set()
    for eqn in _eqns(jaxpr.jaxpr):
        seen.add(str(eqn.source_info.name_stack))
        assert eqn.primitive.name not in ("gather", "dynamic_slice", "iota", "scatter-add", "while"), (
            eqn.primitive.name, str(eqn.source_info.name_stack))
    if width < 32:  # at 32 bits the plane is the answer: no equation at all
        assert any("pqt.hybrid_expand/unpack" in x for x in seen)  # the walk saw the scope


def test_hybrid_frame_refuses_a_run_outside_the_payload_or_the_slots():
    ok = ([0, 1], [16, 8], [0, 3], [0, 0], bytes(10), 5)
    pack_hybrid_upload(*ok)
    for bad in (
        ([0], [17], [0], [0], bytes(10), 5),  # 85 bits of a payload of 80
        ([0], [8], [0], [48], bytes(10), 5),  # starts too far in
        ([0], [8], [0], [-8], bytes(10), 5),  # a bit-packed run's offset is read
        ([1, 0], [8, -1], [0, 0], [0, 0], bytes(10), 5),
    ):
        with pytest.raises(ValueError):
            pack_hybrid_upload(*(np.asarray(x) if isinstance(x, list) else x for x in bad))


# -- the frame of wire streams, through the numpy reference ----------------------
#
# Every case is pages of real hybrid wire (ops/rle_hybrid.encode_hybrid, or
# written by hand where the encoder would not produce the shape), prescanned
# and clamped the way the walks do it, and frozen by pack_hybrid_upload. The
# kernel answers to the numpy decode of the same wire
# (ops/rle_hybrid.decode_hybrid).


def _wire_runs(runs, width):
    """A hybrid stream written run by run: ('rle', count, value) or
    ('bp', values) with len(values) a multiple of 8."""
    out = bytearray()
    for run in runs:
        if run[0] == "rle":
            _emit_uvarint(out, run[1] << 1)
            out += int(run[2]).to_bytes((width + 7) // 8, "little")
        else:
            _emit_bitpacked(out, np.asarray(run[1], dtype=np.uint64), width)
    return bytes(out)


def _freeze_pages(pages, width, zero_length_runs=False):
    """(frozen upload, numpy decode) of `pages` = [(wire, values wanted)]:
    each page prescanned, its last run clamped to the page's count, the
    payloads end to end — kernels/pipeline.py's _hybrid_tables_of, by hand.
    With zero_length_runs, an empty run goes in after every run, RLE and
    bit-packed (at the payload's end) in turn."""
    is_rle, counts, values, bit_starts, packed, expected = [], [], [], [], [], []
    n_bytes = 0
    for wire, n in pages:
        t = prescan_hybrid(wire, n, width)
        c = t.counts.astype(np.int64)
        c[-1] -= int(c.sum()) - n
        assert c[-1] > 0
        is_rle.append(np.asarray(t.is_rle, dtype=np.uint8))
        counts.append(c)
        values.append(np.asarray(t.rle_values, dtype=np.uint64))
        bit_starts.append(np.where(t.is_rle, 0, (t.bp_offsets + n_bytes) * 8))
        packed.append(np.frombuffer(bytes(t.packed), dtype=np.uint8))
        n_bytes += len(t.packed)
        expected.append(decode_hybrid(wire, n, width))
    is_rle, counts, values, bit_starts = (np.concatenate(x) for x in (is_rle, counts, values, bit_starts))
    if zero_length_runs:
        k = len(counts)
        kind = np.arange(k) % 2
        is_rle = np.stack([is_rle, kind.astype(np.uint8)], axis=1).reshape(-1)
        counts = np.stack([counts, np.zeros(k, np.int64)], axis=1).reshape(-1)
        values = np.stack([values, np.full(k, (1 << width) - 1, np.uint64)], axis=1).reshape(-1)
        bit_starts = np.stack([bit_starts, np.where(kind == 1, 0, n_bytes * 8)], axis=1).reshape(-1)
    f, _seconds = pack_hybrid_upload(is_rle, counts, values, bit_starts, np.concatenate(packed), width)
    return f, np.concatenate(expected)


def _wire_pages(shape, width, rng):
    top = 1 << width
    draw = lambda n: rng.integers(0, top, size=n, dtype=np.uint64)  # noqa: E731
    if shape == "one-bit-packed-run":
        v = draw(2000)
        return [(_wire_runs([("bp", v)], width), 2000)]
    if shape == "rle-only":
        return [(_wire_runs([("rle", int(c), int(v)) for c, v in zip(rng.integers(1, 90, 40), draw(40))], width), 1500)]
    if shape in ("alternating-30-pairs", "alternating-1100-pairs"):
        pairs = int(shape.split("-")[1])
        runs = []
        for v in draw(pairs):
            runs += [("bp", draw(8)), ("rle", 8, int(v))]
        return [(_wire_runs(runs, width), 16 * pairs)]
    if shape == "clamped-mid-group-at-page-ends":
        # each page ends inside a bit-packed group: the run's count is clamped
        # (the frame leaves the group's overshoot out), the next page's
        # payload starts at the next group
        return [(encode_hybrid(draw(n), width), n) for n in (13, 27, 100, 5, 1, 8, 403)] + [
            (_wire_runs([("rle", 50, int(draw(1)[0])), ("bp", draw(24))], width), 50 + 17)
        ]
    if shape == "zero-length-runs":
        runs = []
        for v in draw(12):
            runs += [("bp", draw(16)), ("rle", 11, int(v))]
        return [(_wire_runs(runs, width), 27 * 12)]
    if shape == "last-group-read-to-the-payload's-last-byte":
        # the last values' bits end the payload: no 8-byte load fits there
        return [(_wire_runs([("rle", 40, int(draw(1)[0])), ("bp", draw(2000))], width), 2040)]
    raise AssertionError(shape)


_WIRE_WIDTHS = [1, 2, 3, 5, 7, 8, 9, 12, 14, 16, 17, 24, 31, 32]
_WIRE_SHAPES = [
    "one-bit-packed-run", "rle-only", "alternating-30-pairs", "alternating-1100-pairs",
    "clamped-mid-group-at-page-ends", "zero-length-runs", "last-group-read-to-the-payload's-last-byte",
]
_WIRE_CASES = [(shape, width) for shape in _WIRE_SHAPES for width in _WIRE_WIDTHS]


@pytest.mark.parametrize("shape,width", _WIRE_CASES, ids=[f"{s}-{w}" for s, w in _WIRE_CASES])
def test_frame_of_wire_pages_equals_numpy_decode(shape, width):
    rng = np.random.default_rng(width * 131 + _WIRE_SHAPES.index(shape))
    f, expected = _freeze_pages(
        _wire_pages(shape, width, rng), width, zero_length_runs=shape == "zero-length-runs"
    )
    assert f.total == len(expected)
    assert (f.width, f.buf.shape) == (_shipped(width), (f.n_pad * _shipped(width) // 32,))
    got = np.asarray(expand_hybrid_device(jnp.asarray(f.buf), f.width, f.n_pad))
    np.testing.assert_array_equal(got[: f.total], expected)
    assert not got[f.total :].any()


# -- delta_packed_decode_device -------------------------------------------------


def _frame_width(bits: int) -> int:
    """The quantum, written out again: 0, 8, 16, 32 and, above bit 32, as much."""
    return next(q for q in (0, 8, 16, 32, 40, 48, 64) if q >= bits)


def _delta_case(page_sizes, nbits, max_width=None, min_or=0, min_span=None):
    """(frozen upload, expected[:total], the frame's width reckoned here in
    Python integers): pages of the given value counts, miniblocks of 32
    deltas, each with its own width and min. `min_or` is or-ed into every min,
    shifted to the top of the value's width; with `min_span` the mins lie
    within 2^min_span of a negative base (a frame narrower than nbits),
    without it anywhere in the value's range."""
    rng = np.random.default_rng(0)
    ud = np.uint32 if nbits == 32 else np.uint64
    max_width = nbits if max_width is None else min(max_width, nbits)
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
            if min_span is None:
                mn = rng.integers(0, np.iinfo(ud).max, dtype=ud, endpoint=True)
            else:  # a negative base: the signed least min is not the unsigned one
                mn = ud(((1 << nbits) - 1000 + int(rng.integers(0, 1 << min_span))) % (1 << nbits))
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
    bits = np.concatenate([np.zeros(0, np.uint8)] + [_pack_lsb(a, w) for a, w in adj_all])
    frozen, _seconds = pack_delta_upload(
        widths, bit_starts, out_starts, mins, page_start, page_first, _wire(bits), nbits, base
    )
    # the frame of reference again, in Python integers
    signed = [int(m) - (1 << nbits) * (int(m) >> (nbits - 1)) for m in mins]
    bound = max((m - min(signed) + (1 << w) - 1 for m, w in zip(signed, widths)), default=0)
    width = _frame_width(bound.bit_length()) if bound < (1 << nbits) else nbits
    return frozen, np.concatenate(expected), width


_DELTA_CASES = {
    # name: (page_sizes, the p_pad and n_pad the packer's buckets give, max_width[, min_or[, min_span]])
    "one-page-p_pad-64": ([1024], 64, 1024, None),
    "one-page-one-value": ([1], 64, 1024, None),
    "several-pages": ([100, 33, 1, 500, 34, 356], 64, 1024, None),
    "page-of-one-value-between-pages": ([65, 1, 1, 957], 64, 1024, None),
    "total-below-n_pad": ([300, 301], 64, 1024, None),
    "3126-miniblocks": ([30_000, 30_000, 40_000], 64, 131072, 20),
    "64-miniblocks-of-one-page": ([2048], 64, 2048, 9),
    # what bringing the frame of reference and the page offset to the values could break
    "page-of-one-value-last": ([500, 1], 64, 1024, None),
    "pages-of-one-value-only": ([1] * 40, 64, 1024, None),
    "mins-with-the-top-bit-set": ([300, 33, 691], 64, 1024, None, 0x80),
    "mins-all-ones-on-top": ([1024], 64, 1024, 12, 0xFF),
    "hundreds-of-pages": ([8] * 300 + [70] * 20, 512, 4096, None),
    "full-page-table": ([16] * 64, 64, 1024, None),
    "width-0-miniblocks-only": ([700, 324], 64, 1024, 0),
    # every width of the quantum (at nbits 32 those past 32 are the whole value)
    "frame-width-0": ([700, 324], 64, 1024, 0, 0, 0),
    "frame-width-8": ([100, 33, 1, 500, 34, 356], 64, 1024, 6, 0, 7),
    "frame-width-16": ([65, 1, 1, 957], 64, 1024, 13, 0, 15),
    "frame-width-32": ([3000, 1096], 64, 4096, 29, 0, 30),
    "frame-width-40": ([300, 33, 691], 64, 1024, 36, 0, 38),
    "frame-width-48": ([1024], 64, 1024, 45, 0, 47),
    "frame-width-64-narrow-mins": ([500, 524], 64, 1024, 64, 0, 3),
}


@pytest.mark.parametrize("nbits", [32, 64])
@pytest.mark.parametrize("case", sorted(_DELTA_CASES), ids=sorted(_DELTA_CASES))
def test_delta_decode_equals_numpy_expansion(case, nbits):
    page_sizes, p_pad, n_pad, *rest = _DELTA_CASES[case]
    f, expected, width = _delta_case(page_sizes, nbits, *rest)
    assert (f.nbits, f.width, f.p_pad, f.n_pad, f.total) == (nbits, width, p_pad, n_pad, len(expected))
    if case.startswith("frame-width-"):
        assert width == min(int(case.split("-")[2]), nbits)
    # the upload's length: a function of (nbits, W, n_pad, p_pad) and nothing else
    assert f.frame.dtype == np.uint32 and f.frame.shape == ((2 + nbits // 32) * p_pad + n_pad * width // 32,)
    got = np.asarray(
        delta_packed_decode_device(jnp.asarray(f.frame), f.nbits, f.width, f.n_pad, f.p_pad)
    )
    assert got.shape == (n_pad,)
    assert got.dtype == (np.int32 if nbits == 32 else np.int64)
    np.testing.assert_array_equal(got[: len(expected)], expected.view(got.dtype))


def _eqns(jaxpr):
    """Every equation of a jaxpr, the bodies of its calls and loops included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("nbits,width", [(32, 8), (32, 32), (64, 16), (64, 32), (64, 40), (64, 64)])
def test_delta_decode_reads_no_position_through_an_index(nbits, width):
    """No array of n_pad indices is built and no gather over positions is
    left: under pqt.delta_decode/unpack there is no gather at all, and the
    kernel's only gathers (the rebase's c[page_start]) take p_pad indices."""
    import jax

    n_pad, p_pad = 4096, 64
    frame = jnp.zeros((2 + nbits // 32) * p_pad + n_pad * width // 32, jnp.uint32)
    jaxpr = jax.make_jaxpr(
        lambda x: delta_packed_decode_device(x, nbits=nbits, width=width, num_values=n_pad, p_pad=p_pad)
    )(frame)
    seen = set()
    for eqn in _eqns(jaxpr.jaxpr):
        stack = str(eqn.source_info.name_stack)
        seen.add(stack)
        if eqn.primitive.name in ("gather", "dynamic_slice", "iota"):
            assert "pqt.delta_decode/unpack" not in stack, (eqn.primitive.name, stack)
            assert all(np.prod(v.aval.shape) <= p_pad for v in eqn.outvars), (eqn.primitive.name, stack)
    assert any("pqt.delta_decode/unpack" in x for x in seen)  # the walk saw the scope


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


# -- the upload format itself, word for word ------------------------------------
#
# What the packers write where, as literals: a change of the format (the next
# perf_opt's business) has to change these on purpose. Everything not listed is 0.


def _nonzero(a: np.ndarray) -> dict:
    return {int(i): int(a[i]) for i in np.flatnonzero(a)}


def test_hybrid_upload_golden_two_planes():
    # width 3 = 2 + 1: 5 x 6 | 0..7 bit-packed at bit 0 | a zero-length run | 3
    # of the group 7..0 at bit 24 (its other 5 values are the page's overshoot:
    # gone) | 3 x 2 | 50 x 5. The RLE runs' bit offsets are what the native
    # walk leaves there for an upload's second group of pages: garbage,
    # negative, and read by nobody.
    f, seconds = pack_hybrid_upload(
        is_rle=np.array([1, 0, 1, 0, 1, 1], dtype=np.uint8),
        counts=np.array([5, 8, 0, 3, 3, 50], dtype=np.int64),
        rle_values=np.array([6, 0, 7, 0, 2, 5], dtype=np.uint64),
        bit_starts=np.array([-8, 0, -8, 24, -8, -8], dtype=np.int64),
        packed=bytes([0x88, 0xC6, 0xFA, 0x77, 0x39, 0x05]),
        width=3,
    )
    assert (f.width, f.n_pad, f.run_pad, f.total) == (3, 1024, 0, 69) and seconds >= 0
    assert f.buf.dtype == np.uint32 and f.buf.shape == (1024 * 3 // 32,)
    # the first plane, 64 words of sixteen 2-bit parts: the low 2 bits of slot
    # s in bits (s // 64) * 2 of word s % 64
    want = {k: 2 for k in range(5)}  # 6 = 0b110
    want.update({5 + v: v & 3 for v in range(8) if v & 3})  # 0..7
    want.update({13: 3, 14: 2, 15: 1, 16: 2, 17: 2, 18: 2})  # 7, 6, 5; 3 x 2
    want.update({k: 1 for k in range(19, 64)})  # 5 = 0b101, slots 19..63
    for k in range(5):  # slots 64..68, the second part of words 0..4
        want[k] |= 1 << 2
    # the second plane, 32 words of thirty-two 1-bit parts: bit 2 of slot s in
    # bit s // 32 of word 64 + s % 32. Slots 0..4, 9..15 and 19..31 hold a 1
    # there (bit 0), every slot of 32..63 (bit 1) and of 64..68 (bit 2)
    want.update({64 + k: 2 for k in (5, 6, 7, 8, 16, 17, 18)})
    want.update({64 + k: 3 for k in (*range(9, 16), *range(19, 32))})
    want.update({64 + k: 7 for k in range(5)})
    assert _nonzero(f.buf) == want
    got = np.asarray(expand_hybrid_device(jnp.asarray(f.buf), f.width, f.n_pad))
    assert got[: f.total].tolist() == [6] * 5 + list(range(8)) + [7, 6, 5] + [2] * 3 + [5] * 50
    assert not got[f.total :].any()


def test_hybrid_upload_golden_one_plane():
    # width 8: 1..8 bit-packed at bit 0 | 250 x 0xAB | 3 of the group 9, 10, 11,
    # 0xFF x 5 at bit 64 | 2 x 7: 263 values, past the plane's 256 words.
    f, _seconds = pack_hybrid_upload(
        is_rle=np.array([0, 1, 0, 1], dtype=np.uint8),
        counts=np.array([8, 250, 3, 2], dtype=np.int64),
        rle_values=np.array([0, 0xAB, 0, 7], dtype=np.uint64),
        bit_starts=np.array([0, 0, 64, 0], dtype=np.int64),
        packed=bytes([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF]),
        width=8,
    )
    assert (f.width, f.n_pad, f.run_pad, f.total) == (8, 1024, 0, 263)
    assert f.buf.dtype == np.uint32 and f.buf.shape == (1024 * 8 // 32,)
    # one plane, 256 words of four 8-bit parts: slot s in bits (s // 256) * 8 of word s % 256
    want = {k: k + 1 for k in range(8)}  # slots 0..7
    want.update({k: 0xAB for k in range(8, 256)})  # slots 8..255
    want[0] |= 0xAB << 8  # slots 256, 257: the run's last two
    want[1] |= 0xAB << 8
    want.update({2: 3 | 9 << 8, 3: 4 | 10 << 8, 4: 5 | 11 << 8})  # slots 258..260
    want.update({5: 6 | 7 << 8, 6: 7 | 7 << 8})  # slots 261, 262
    assert _nonzero(f.buf) == want
    got = np.asarray(expand_hybrid_device(jnp.asarray(f.buf), f.width, f.n_pad))
    assert got[: f.total].tolist() == list(range(1, 9)) + [0xAB] * 250 + [9, 10, 11] + [7, 7]


@pytest.mark.parametrize("nbits", [32, 64])
def test_delta_upload_golden(nbits):
    # three pages. 10, 13, 12 (deltas 3, -1: min -1, two 3-bit residues 4, 0);
    # F, F + 5 (one delta of width 0, min 5), F past 32 bits where it fits; and
    # 300 values from G at position 5: ten miniblocks of min -1, all of width 0
    # (each value one less) but the ninth, whose 32 deltas of 8 bits 1..32 sit
    # in slots 262..293, past the plane's 256 words.
    far = (1 << (40 if nbits == 64 else 20)) + 7
    g = far + 12345
    ones = (1 << 64) - 1
    f, seconds = pack_delta_upload(
        widths=np.array([3, 0] + [0] * 8 + [8, 0], dtype=np.uint32),
        bit_starts=np.array([0, 8] + [8] * 8 + [8, 264], dtype=np.int64),
        out_starts=np.array([1, 4] + [6 + 32 * j for j in range(10)], dtype=np.int64),
        mins=np.array([ones, 5] + [ones] * 10, dtype=np.uint64),
        page_starts=np.array([0, 3, 5], dtype=np.int64),
        page_firsts=np.array([10, far, g], dtype=np.int64),
        stream=bytes([0x04, *range(1, 33)]),
        nbits=nbits,
        total=305,
    )
    # M = -1 (the least min), so a slot holds raw + (min + 1): at most 32 -> 8 bits
    assert (f.nbits, f.width, f.n_pad, f.p_pad, f.total) == (nbits, 8, 1024, 64, 305) and seconds >= 0
    parts = nbits // 32
    head = (2 + parts) * 64
    assert f.frame.dtype == np.uint32 and f.frame.shape == (head + 1024 * 8 // 32,)
    want = {1: 3, 2: 5, **{k: 1025 for k in range(3, 64)}}  # page_start, padded with n_pad + 1
    want.update({64: 10, 64 + 1: far & 0xFFFFFFFF, 64 + 2: g & 0xFFFFFFFF})  # page_first, low words
    if nbits == 64:
        want.update({128 + 1: far >> 32, 128 + 2: g >> 32})  # page_first, high words
    want.update({(1 + parts) * 64 + k: 0xFFFFFFFF for k in range(parts)})  # M = -1
    # the one plane, 256 words of four 8-bit slots: slot s in bits (s // 256) * 8 of word s % 256
    want.update({head + 1: 4, head + 4: 6})  # slots 1 and 4: 4 + 0 and 0 + (5 + 1); slot 2 holds 0
    want.update({head + 262 + k - 256: (k + 1) << 8 for k in range(32)})  # slots 262..293
    assert _nonzero(f.frame) == want
    # what shapes_check.py reads of the record, by the wire upload's names
    assert f.meta32 is f.frame and f.wide.shape == (0,) and f.m_pad == 0
    got = np.asarray(
        delta_packed_decode_device(jnp.asarray(f.frame), f.nbits, f.width, f.n_pad, f.p_pad)
    )
    page3 = g + np.cumsum([0] + [-1] * 256 + list(range(32)) + [-1] * 11)
    assert got[: f.total].tolist() == [10, 13, 12, far, far + 5, *page3.tolist()]


# -- merge_mixed_numeric_device ---------------------------------------------------
#
# Driven through its one call site, _ChunkPlan.device_column, on plans built by
# hand (pyarrow writes dictionary pages, then PLAIN pages, and nothing else):
# the dispatch pads every upload on the host, the segment table carries the
# counts, and the answer is a numpy merge of the same pages, bit for bit.

_MIXED_TYPES = {
    # name: (schema type, page dtype, doubles=, delivered bit pattern dtype)
    "int32": ("int32", np.int32, None, np.uint32),
    "int64": ("int64", np.int64, None, np.uint64),
    "float-u32-patterns": ("float", np.float32, None, np.uint32),
    "double-u64-patterns": ("double", np.float64, "bits", np.uint64),
    "double-float32": ("double", np.float64, "float32", np.uint32),
}

_MIXED_LAYOUTS = {
    # pages in order: ("dict" | "values" | "empty", rows)
    "dict-then-plain": [("dict", 300), ("dict", 211), ("values", 500), ("values", 402), ("values", 77)],
    "one-dict-page-then-plain": [("dict", 1), ("values", 1500)],
    "plain-dict-plain": [("values", 40), ("dict", 700), ("dict", 3), ("values", 90)],
    "one-under-the-bucket": [("dict", 1000), ("values", 1047)],
    "at-the-bucket": [("dict", 1000), ("values", 1048)],
    "at-the-bucket-dict-last": [("values", 1025), ("dict", 1023)],
    "empty-pages-between": [("empty", 0), ("dict", 64), ("empty", 0), ("dict", 8), ("values", 0), ("values", 31), ("empty", 0)],
    "two-index-batches": [("dict", 96), ("dict", 32), ("dict", 40), ("values", 500)],
    # dictionary and PLAIN pages interleaved page by page (legal, unseen): one
    # segment a page, 96 of them in a table of 128
    "a-segment-a-page": [("dict", 3), ("values", 5)] * 48,
}


def _mixed_plan(type_name, layout, doubles=None, batch_pages=None, seed=0, padded=False):
    """(plan before dispatch, the numpy merge of its pages). `batch_pages`
    splits the dictionary pages into index batches of that many pages;
    `padded` makes it the padded delivery's plan (one list of all the rows)."""
    from parquet_tpu.kernels.pipeline import _ChunkPlan, _shape_double_dictionary
    from parquet_tpu.schema.dsl import parse_schema

    schema_type, dt, _, _ = _MIXED_TYPES[type_name]
    column = parse_schema(f"message m {{ required {schema_type} x; }}").column(("x",))
    rng = np.random.default_rng(seed)
    n_dict = 37

    def draw(n):
        if np.dtype(dt).kind == "f":
            v = rng.standard_normal(n) * 1e3
            v[::5] = -0.0
            return v.astype(dt)
        return rng.integers(np.iinfo(dt).min, np.iinfo(dt).max, n, dtype=dt)

    dictionary = draw(n_dict)
    plan = _ChunkPlan(column, sum(n for _, n in layout), doubles, padded)
    if padded:
        plan.list_elements = plan.expected
        plan.list_lengths = np.array([plan.expected], np.int32)
    plan.dictionary = dictionary
    merged, plain_pages, batches, batch = [], [], [], []
    for kind, n in layout:
        if kind == "dict":
            idx = rng.integers(0, n_dict, n).astype(np.uint32)
            merged.append(dictionary[idx])
            batch.append(idx)
            if batch_pages and len(batch) == batch_pages:
                batches.append(batch)
                batch = []
            plan.page_infos.append((n, None, None, "dict", n))
        elif kind == "values":
            page = draw(n)
            merged.append(page)
            plain_pages.append(page)
            plan.page_infos.append((n, None, None, "values", page))
        else:
            plan.page_infos.append((0, None, None, "empty", None))
    if batch:
        batches.append(batch)
    width = 6
    for pages in batches:
        idx = np.concatenate(pages)
        plan.frozen_hybrid.append(pack_hybrid_upload(
            np.array([False]), np.array([len(idx)]), np.array([0], np.uint32), np.array([0]),
            _wire(_pack_lsb(idx, width)), width,
        )[0])
    plan.plain_host = np.concatenate(plain_pages)
    if doubles is not None:
        _shape_double_dictionary(plan)
    return plan, np.concatenate(merged)


def _mixed_cases():
    for layout in _MIXED_LAYOUTS:
        yield pytest.param("int64", layout, id=f"int64-{layout}")
    for type_name in _MIXED_TYPES:
        if type_name != "int64":
            yield pytest.param(type_name, "plain-dict-plain", id=f"{type_name}-plain-dict-plain")
            yield pytest.param(type_name, "at-the-bucket", id=f"{type_name}-at-the-bucket")
    yield pytest.param("double-float32", "a-segment-a-page", id="double-float32-a-segment-a-page")


@pytest.mark.parametrize("type_name, layout_name", _mixed_cases())
def test_merge_mixed_numeric_is_the_numpy_merge(type_name, layout_name):
    from parquet_tpu.kernels.pipeline import _mixed_segments
    from parquet_tpu.utils import metrics
    from parquet_tpu.utils.trace import decode_trace

    _, dt, doubles, bits = _MIXED_TYPES[type_name]
    plan, want = _mixed_plan(
        type_name, _MIXED_LAYOUTS[layout_name], doubles,
        batch_pages=2 if layout_name == "two-index-batches" else None,
    )
    if doubles == "float32":
        want = want.astype(np.float32)
    name = 'events_total{event="mixed_chunks_by_segments"}'
    before = metrics.snapshot().get(name, 0)
    with decode_trace() as tr:
        dc = plan.dispatch_device().device_column()
    assert metrics.snapshot().get(name, 0) == before + 1
    assert tr.counters().get("mixed_chunks_by_segments") == 1
    assert dc.mixed and dc.num_values == len(want)
    got = np.asarray(dc.values)
    assert got.dtype == (want.dtype if doubles != "bits" else np.uint64)
    np.testing.assert_array_equal(got.view(bits), want.view(bits))
    # every array the kernel took is at a bucket, whatever the chunk's counts
    assert plan.mixed_numeric
    for arr in (*plan.dev_hybrid, plan.dict_dev, plan.dev_plain):
        assert arr.shape[0] & (arr.shape[0] - 1) == 0
    seg_kind, seg_row_start, seg_src, n_rows = _mixed_segments(
        plan.page_infos, plan.padded_totals[1], [int(b.shape[0]) for b in plan.dev_hybrid]
    )
    assert n_rows == len(want) and len(seg_kind) >= 4 and len(seg_kind) & (len(seg_kind) - 1) == 0
    if layout_name == "a-segment-a-page":
        assert len(seg_kind) == 128 and seg_row_start[96] == n_rows
    # the round trip through the host (finalize) cuts the whole batches itself
    host = np.asarray(plan.finalize().values)
    if doubles == "float32":
        host = host.astype(np.float32)
    np.testing.assert_array_equal(host.view(bits), want.view(bits))


@pytest.mark.parametrize("type_name, layout_name", [
    ("int64", "dict-then-plain"),
    ("int64", "at-the-bucket"),
    ("int32", "two-index-batches"),
    ("double-float32", "plain-dict-plain"),
])
def test_a_mixed_chunk_under_the_padded_delivery(type_name, layout_name):
    """device_values_padded's exact fallback on a plan prepared padded: the
    dispatch left every array whole for the merge, so the fallback must not
    cut them to the counts (it does for every other chunk shape), and the
    merged values come back at their bucket with the count beside them."""
    from parquet_tpu.utils import metrics

    _, dt, doubles, bits = _MIXED_TYPES[type_name]
    plan, want = _mixed_plan(
        type_name, _MIXED_LAYOUTS[layout_name], doubles, padded=True,
        batch_pages=2 if layout_name == "two-index-batches" else None,
    )
    if doubles == "float32":
        want = want.astype(np.float32)
    names = ['events_total{event="%s"}' % e for e in ("padded_delivery_exact_chunks", "mixed_chunks_by_segments")]
    before = [metrics.snapshot().get(n, 0) for n in names]
    values, count = plan.dispatch_device().device_values_padded()
    assert [metrics.snapshot().get(n, 0) - b for n, b in zip(names, before)] == [1, 1]
    assert plan.mixed_numeric and plan.dev_lengths is not None
    got = np.asarray(values)
    assert count == len(want) and len(got) >= count and len(got) & (len(got) - 1) == 0
    np.testing.assert_array_equal(got[:count].view(bits), want.view(bits))
    for arr in (*plan.dev_hybrid, plan.dict_dev, plan.dev_plain):
        assert arr.shape[0] & (arr.shape[0] - 1) == 0


@pytest.mark.parametrize("layout_name, segments", [
    ("dict-then-plain", [(1, 0, 0), (0, 511, 0)]),
    ("plain-dict-plain", [(0, 0, 0), (1, 40, 0), (0, 743, 40)]),
    ("empty-pages-between", [(1, 0, 0), (0, 72, 0)]),
    # two batches of 128 and 40 indices at their buckets of 1,024: the second starts at 1,024
    ("two-index-batches", [(1, 0, 0), (1, 128, 1024), (0, 168, 0)]),
])
def test_mixed_segments_coalesce_adjacent_pages(layout_name, segments):
    from parquet_tpu.kernels.pipeline import _mixed_segments

    plan, want = _mixed_plan(
        "int64", _MIXED_LAYOUTS[layout_name], batch_pages=2 if layout_name == "two-index-batches" else None
    )
    totals = [f.total for f in plan.frozen_hybrid]
    seg_kind, seg_row_start, seg_src, n_rows = _mixed_segments(
        plan.page_infos, totals, [f.n_pad for f in plan.frozen_hybrid]
    )
    S = len(segments)
    assert len(seg_kind) == 4 and n_rows == len(want)
    assert list(zip(seg_kind[:S], seg_row_start[:S], seg_src[:S])) == segments
    # the padding: empty segments at the chunk's end
    assert (seg_row_start[S:] == n_rows).all() and not seg_kind[S:].any() and not seg_src[S:].any()


def test_merge_mixed_numeric_kernel_does_not_clamp_its_slices():
    """XLA clamps a dynamic_slice's start to keep the slice in bounds, which
    would shift the rows silently: starts at both ends of the source, on
    sources shorter and longer than the output."""
    from parquet_tpu.kernels.device_ops import merge_mixed_numeric_device

    rng = np.random.default_rng(3)
    rows_pad = 2048
    dictionary = rng.integers(-(1 << 62), 1 << 62, 1024)
    idx = np.full(1024, 0xFFFFFFFF, np.uint32)  # past the true count: garbage
    idx[:1000] = rng.integers(0, 1024, 1000)
    for plain_len in (1024, 4096):
        plain = rng.integers(-(1 << 62), 1 << 62, plain_len)
        # the PLAIN rows come from the END of their pool, the dictionary rows last
        seg_kind = np.array([0, 1, 0, 0], np.int32)
        seg_row_start = np.array([0, 1024, 2024, 2048, 2048], np.int32)
        seg_src = np.array([plain_len - 1024, 0, 0, 0], np.int32)
        got = np.asarray(merge_mixed_numeric_device(
            jnp.asarray(idx), jnp.asarray(dictionary), jnp.asarray(plain),
            jnp.asarray(seg_kind), jnp.asarray(seg_row_start), jnp.asarray(seg_src), rows_pad=rows_pad,
        ))
        want = np.concatenate([plain[plain_len - 1024:], dictionary[idx[:1000]], plain[:24]])
        np.testing.assert_array_equal(got, want)
