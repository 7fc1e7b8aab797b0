"""Bytes the grouped reduction has to move, from its count: what
`group_agg_roofline` divides by the kernel's device time and the chip's
`hbm_bytes_per_s` (lib/peaks.py). The least the algorithm reads, not what the
program's buffers happen to move: the kernel as shipped passes over its
materialised inputs once a LIVE slot and its 64-bit sums are emulated on the
VPU, so its share is far below 100 % — the share against HBM is the bound
that cannot be passed.
"""


def group_agg_bytes(rows: int, columns: int = 4, keys: int = 2) -> int:
    """group_agg_device over `rows` rows (the program's query_group_rows
    counter): each distinct int64 input column is read once (8 B a row), each
    key's index stream once (4 B a row) and the row mask once (1 B a row);
    the result is a few slots. Q1 reads four columns (l_quantity,
    l_extendedprice, l_discount, l_tax: disc_price and charge are programs
    over them) and two keys: 41 B a row."""
    return rows * (8 * columns + 4 * keys + 1)
