"""Bytes the device query lane's kernels have to move, from their counts: what
a `<kernel>_roofline` share divides by the kernel's device time and the chip's
`hbm_bytes_per_s` (lib/peaks.py). The least the algorithm reads, not what the
program's buffers happen to move; no kernel here does arithmetic worth
counting beside its traffic (a multiply and an add a row), so there is no
operations function: memory bounds it.
"""


def expr_agg_bytes(rows: int, columns: int = 2) -> int:
    """expr_agg_device over `rows` rows (the program's query_expr_rows
    counter): each column of the tree is read once as resident int64 (8 B a
    row) and the row mask once (1 B a row); the result is a scalar. Q6's
    tree, l_extendedprice*l_discount, has two columns."""
    return rows * (8 * columns + 1)
