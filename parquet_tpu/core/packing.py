"""Sequence packing: a LIST<int32|int64> leaf (one document a row) as fixed
[sequences, seq_len] device batches — FileReader.iter_device_batches(
lists="pack", seq_len=...).

The stream is the concatenation, in row order, of every document's elements;
a null or empty document adds nothing and nothing is inserted between
documents. Sequence s is stream[s * seq_len : (s + 1) * seq_len]: a document
cut by a sequence's end continues at the start of the next one, across row
groups too, and only the file's last sequence is padded. Piece starts are each
sequence's slot 0 and every slot that holds a document's first token;
segment_ids[s, j] counts the piece starts of sequence s up to slot j (>= 1 on
real tokens), positions[s, j] is j minus the slot of the latest piece start.
Padding reads token 0, segment id 0, position 0 (the T5X / MaxText packing
convention). benchmark/lib/reference_packed.py states the same in numpy.

SequencePacker is the host side: it holds the device-resident carry between
row groups and batches, knows every count as a host int (they come from the
documents' lengths, which the chunk plan derived from the level streams), and
hands them to the three kernels of kernels/device_ops.py as runtime scalars,
so that no compiled shape follows the data.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from ..utils import metrics as _metrics
from ..utils import trace as _trace

__all__ = ["PackedBatch", "SequencePacker"]


class PackedBatch(NamedTuple):
    """One batch of packed sequences, each array int32[sequences, seq_len]
    and resident on the device (a NamedTuple is a jax pytree node: a jitted
    step takes the batch whole). Attention within a sequence is allowed
    where segment_ids agree and are not 0; position embeddings read
    `positions`."""

    tokens: object
    segment_ids: object
    positions: object


def _count(name: str, n: int) -> None:
    if n:
        _metrics.event(name, n)
        _trace.count(name, n)


class SequencePacker:
    """The packer's state between row groups: a carry of fewer than
    batch * seq_len tokens with their start flags on the device, and its
    fill on the host. append() takes one row group; ready() / emit() cut
    whole batches; sequences_left() / tail() pad and cut what a file's end
    leaves."""

    def __init__(self, batch: int, seq_len: int):
        import jax.numpy as jnp

        self.batch, self.seq_len = batch, seq_len
        self.span = batch * seq_len
        self._carry = (jnp.zeros(self.span, jnp.int32), jnp.zeros(self.span, jnp.int32))
        self._work = None  # (tokens, flags) of the last append, until its batches are cut
        self._offset = 0  # slots of the work buffers already emitted
        self._fill = 0  # valid slots of the work buffers (of the carry when there are none)
        self._stream = 0  # tokens of the file before this group: where a document lies in its sequence

    def append(self, values, lengths_dev, lengths: np.ndarray, count: int) -> None:
        """One row group: its values in HBM at a padded length (`count` of
        them real), its documents' lengths on the device (zero-padded) and
        on the host."""
        from ..kernels.device_ops import pack_append_device

        self._settle()
        self._work = pack_append_device(*self._carry, values, lengths_dev, np.int32(self._fill))
        self._carry = None
        first = self._stream + np.cumsum(lengths, dtype=np.int64) - lengths
        last = first + lengths - 1
        cut = (lengths > 0) & (first // self.seq_len != last // self.seq_len)
        _count("packed_tokens", count)
        _count("packed_documents", len(lengths))
        _count("packed_documents_cut", int(cut.sum()))
        self._stream += count
        self._fill += count

    def ready(self) -> bool:
        return self._fill - self._offset >= self.span

    def emit(self) -> PackedBatch:
        """The next whole batch (ready() must hold)."""
        batch = self._cut(self.span)
        self._offset += self.span
        _count("packed_sequences", self.batch)
        return batch

    def sequences_left(self) -> int:
        """Sequences a file's end leaves to tail(): fewer than a batch, or a
        whole batch whose last sequence is padded; 0 where nothing is left."""
        return -(-(self._fill - self._offset) // self.seq_len)

    def tail(self) -> PackedBatch:
        """What the file's end leaves (sequences_left() of them), as one
        batch of full shape: the last sequence padded, the rows past it all
        padding."""
        left = self._fill - self._offset
        _count("packed_sequences", self.sequences_left())
        _count("packed_padding_tokens", -left % self.seq_len)
        batch = self._cut(left)
        self._offset = self._fill
        return batch

    def _cut(self, n_valid: int) -> PackedBatch:
        from ..kernels.device_ops import pack_emit_device

        return PackedBatch(*pack_emit_device(
            *self._work, np.int32(self._offset), np.int32(n_valid), self.batch, self.seq_len
        ))

    def _settle(self) -> None:
        """Before an append: what the emitted batches left of the work
        buffers becomes the carry again, at slot 0."""
        from ..kernels.device_ops import pack_carry_device

        if self._work is not None:
            self._carry = pack_carry_device(*self._work, np.int32(self._offset), self.span)
            self._work = None
            self._fill -= self._offset
            self._offset = 0
