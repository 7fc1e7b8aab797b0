"""Columnar value containers.

The reference moves decoded values as `[]interface{}` — one heap-boxed value
per cell (reference: interfaces.go:29-52, SURVEY §7.1 'invert the execution
model'). Here every column is a typed array end-to-end:

  - numeric/boolean columns: NumPy arrays (bit-exact views of the wire bytes)
  - BYTE_ARRAY columns: Arrow-style (offsets, flat byte buffer) — no per-string
    materialization (SURVEY §7.3 hard-part #3)
  - INT96: (n, 12) uint8 rows (legacy Impala timestamps)
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

__all__ = ["ByteArrayData", "byte_array_from_items"]

from ..utils.native import load_ext

# CPython extension (native/pyext.c), built on first use; None when no
# compiler is available — every caller degrades without it
_ext = load_ext()


@dataclass
class ByteArrayData:
    """Variable-length binary column: values[i] = data[offsets[i]:offsets[i+1]]."""

    offsets: np.ndarray  # int64, length n+1, offsets[0] == 0
    data: bytes

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def __getitem__(self, i: int) -> bytes:
        return self.data[self.offsets[i] : self.offsets[i + 1]]

    def to_list(self, cache: bool = False) -> list[bytes]:
        """Per-value bytes. The write path asks repeatedly on the same chunk
        (dictionary build, PLAIN encode, stats) and opts into memoization
        with cache=True — those callers share one list and must not mutate
        it (the writer wraps caller-owned arrays, so the cache never pins a
        user object). cache=False always builds a fresh list: read-path
        callers neither retain extra memory nor alias the shared one."""
        if cache:
            cached = getattr(self, "_list_cache", None)
            if cached is not None:
                return cached
        o = self.offsets.tolist()
        d = self.data
        out = [d[o[i] : o[i + 1]] for i in range(len(o) - 1)]
        if cache:
            self._list_cache = out
        return out

    @classmethod
    def from_list(cls, items) -> "ByteArrayData":
        lengths = np.fromiter((len(x) for x in items), dtype=np.int64, count=len(items))
        offsets = np.zeros(len(items) + 1, dtype=np.int64)
        np.cumsum(lengths, out=offsets[1:])
        return cls(offsets=offsets, data=b"".join(items))


    def take(self, indices: np.ndarray) -> "ByteArrayData":
        """Gather rows by index (dictionary expansion), fully vectorized.

        Builds one fancy-index over the source buffer: for output row k the
        source positions are starts[k] + [0, len_k); expressed as
        arange(total) - repeat(out_starts) + repeat(src_starts).
        """
        indices = np.asarray(indices, dtype=np.int64)
        if _ext is not None and len(indices):
            # one C pass, ONE uninitialized output allocation (offsets,
            # lengths, bounds checks and the gather all inside); ~2x the
            # ctypes route, which pays a memset + an extra result copy
            off_b, data = _ext.take_bytes(
                self.data,
                np.ascontiguousarray(self.offsets, dtype=np.int64),
                np.ascontiguousarray(indices),
            )
            return ByteArrayData(
                offsets=np.frombuffer(off_b, dtype=np.int64), data=data
            )
        if len(indices) and (
            int(indices.min()) < 0 or int(indices.max()) >= len(self)
        ):
            raise IndexError("byte-array take: index out of range")
        o = self.offsets
        lengths = (o[1:] - o[:-1])[indices]
        new_off = np.zeros(len(indices) + 1, dtype=np.int64)
        np.cumsum(lengths, out=new_off[1:])
        total = int(new_off[-1])
        if total == 0:
            return ByteArrayData(offsets=new_off, data=b"")
        from ..utils.native import get_native

        lib = get_native()
        if lib is not None and lib.has_bytearray_take:
            data = lib.bytearray_take(self.data, o, indices, new_off, total)
            return ByteArrayData(offsets=new_off, data=data)
        src = np.frombuffer(self.data, dtype=np.uint8)
        starts = o[:-1][indices]
        gather = (
            np.arange(total, dtype=np.int64)
            - np.repeat(new_off[:-1], lengths)
            + np.repeat(starts, lengths)
        )
        return ByteArrayData(offsets=new_off, data=src[gather].tobytes())

    def __eq__(self, other) -> bool:
        if not isinstance(other, ByteArrayData):
            return NotImplemented
        return (
            np.array_equal(self.offsets, other.offsets) and self.data == other.data
        )


def byte_array_from_items(items, to_bytes=None) -> ByteArrayData:
    """Sequence of str/bytes (or anything `to_bytes` can convert) -> column.

    The common all-str/bytes case runs as one C pass (native/_native_ext);
    exotic item types fall back to per-item conversion."""
    if _ext is not None:
        try:
            flat, lens_b = _ext.encode_items(items)
        except TypeError:
            pass
        else:
            lengths = np.frombuffer(lens_b, dtype="<i8")
            offsets = np.zeros(len(lengths) + 1, dtype=np.int64)
            np.cumsum(lengths, out=offsets[1:])
            return ByteArrayData(offsets=offsets, data=flat)
    if to_bytes is None:
        to_bytes = _default_to_bytes
    return ByteArrayData.from_list([to_bytes(x) for x in items])


def _default_to_bytes(v) -> bytes:
    if isinstance(v, bytes):
        return v
    if isinstance(v, str):
        return v.encode("utf-8")
    if isinstance(v, (bytearray, memoryview)):
        return bytes(v)
    raise TypeError(f"cannot convert {type(v).__name__} to bytes")
