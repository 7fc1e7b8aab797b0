"""Bytes the sequence packer has to move, from its counts: what
`pack_sequences_roofline` divides by the device time under the scope
pqt.pack_sequences and the chip's `hbm_bytes_per_s` (lib/peaks.py). The
packer does no arithmetic worth counting beside its traffic (a few integer
adds and compares a slot), so there is no operations function: memory bounds it.

The least the algorithm needs, not what the program's buffers happen to move:
a token is read once as a decoded id (4 B) and written once into each of the
three delivered arrays (tokens, segment ids, positions: 12 B); a document's
length is read once (4 B). Padding slots, the carry's copies and the start
flags are the implementation's, so they count as time and not as bytes.
"""


def pack_sequences_bytes(tokens: int, documents: int) -> int:
    """pack_append_device + pack_emit_device + pack_carry_device over a
    window that packed `tokens` tokens of `documents` documents."""
    return 16 * tokens + 4 * documents
