"""The plain reference of sequence packing: what a LIST<int32|int64> column
of documents is, as fixed [sequences, seq_len] arrays with segment ids and
positions, written the slow obvious way. numpy + pyarrow only: never the
program, never jax. tests/test_pack_sequences.py imports this same file by
path; there is no second copy.

The rules (ISSUE 33; parquet_tpu/core/packing.py states the same):

  - the stream is the concatenation, in row order, of every document's
    elements; a null or empty document adds nothing; nothing is inserted;
    a null ELEMENT is refused (ValueError): dropping it would shift positions;
  - sequence s is stream[s * L : (s + 1) * L]; a document cut by a sequence's
    end continues at the start of the next one; only the last sequence is
    padded: token 0, segment id 0, position 0;
  - piece starts are each sequence's slot 0 and every slot that holds a
    document's first token; segment_ids[s, j] = the count of piece starts in
    [s, 0..j] (so >= 1 on real tokens, restarting in every sequence);
    positions[s, j] = j minus the slot of the latest piece start at or before j;
  - INT64 elements are delivered as their low 32 bits.
"""

from __future__ import annotations

MASK64 = 0xFFFFFFFFFFFFFFFF


def pack(documents, seq_len: int) -> tuple:
    """(tokens, segment_ids, positions), each int32[S, seq_len], of a pyarrow
    ListArray (or ChunkedArray of lists) of integer documents."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc

    if isinstance(documents, pa.ChunkedArray):
        documents = documents.combine_chunks() if documents.num_chunks else pa.array([], documents.type)
    lengths = pc.fill_null(pc.list_value_length(documents), 0).to_numpy(zero_copy_only=False).astype(np.int64)
    elements = documents.flatten()  # the elements of the non-null lists, in row order
    if elements.null_count:
        raise ValueError("null elements inside lists: packing would shift positions")
    stream = elements.to_numpy(zero_copy_only=False).astype(np.int32, copy=False)  # int64 ids: their low 32 bits
    total = len(stream)
    if total != int(lengths.sum()):
        raise ValueError("list lengths and elements disagree")
    n_seq = -(-total // seq_len)
    tokens = np.zeros((n_seq, seq_len), dtype=np.int32)
    tokens.reshape(-1)[:total] = stream
    first = np.zeros((n_seq, seq_len), dtype=bool)  # slots that hold a document's first token
    first.reshape(-1)[(np.cumsum(lengths) - lengths)[lengths > 0]] = True
    segment_ids = np.zeros((n_seq, seq_len), dtype=np.int32)
    positions = np.zeros((n_seq, seq_len), dtype=np.int32)
    slots = np.arange(seq_len)
    for s in range(n_seq):
        real = s * seq_len + slots < total
        start = first[s].copy()
        start[0] = True
        latest = np.maximum.accumulate(np.where(start, slots, 0))
        segment_ids[s] = np.where(real, np.cumsum(start), 0)
        positions[s] = np.where(real, slots - latest, 0)
    return tokens, segment_ids, positions


def digests(tokens, segment_ids, positions, first_sequence: int = 0) -> dict:
    """What one file's packed sequences are compared by inside a measured
    window: the sequence count and seven wrapped uint64 sums. The plain sums of
    tokens and positions; two sensitive to where in its sequence a value sits
    (slot j weighs j + 1); and, of each array, one sensitive to which sequence
    of the file it sits in (sequence s weighs s + 1): sequences or batches
    delivered in another order, or swapped across a row group's carry, change
    them. `first_sequence` is the file's index of row 0, for a part of a file."""
    import numpy as np

    def wrapped(a, axis: int, weights) -> int:
        sums = a.sum(axis=axis, dtype=np.int64).tolist()  # exact: Python integers from here on
        return sum(c * w for c, w in zip(sums, weights)) & MASK64

    n_seq, seq_len = tokens.shape
    plain, by_slot = [1] * seq_len, range(1, seq_len + 1)
    by_sequence = range(first_sequence + 1, first_sequence + n_seq + 1)
    return {
        "sequences": int(n_seq),
        "tokens": wrapped(tokens, 0, plain),
        "tokens_weighted": wrapped(tokens, 0, by_slot),
        "segments_weighted": wrapped(segment_ids, 0, by_slot),
        "positions": wrapped(positions, 0, plain),
        "tokens_by_sequence": wrapped(tokens, 1, by_sequence),
        "segments_by_sequence": wrapped(segment_ids, 1, by_sequence),
        "positions_by_sequence": wrapped(positions, 1, by_sequence),
    }
