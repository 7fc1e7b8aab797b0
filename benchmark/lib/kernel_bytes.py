"""Bytes a kernel has to move for one call, from its shapes: what a
`<kernel>_roofline` share divides by the kernel's device time and the chip's
`hbm_bytes_per_s` (lib/peaks.py). No kernel here does arithmetic worth
counting beside its traffic, so there is no operations function.

Read so far by selftest/micro_doubles.py alone: `pqt.double_narrow` runs in no
cell's window (every DOUBLE chunk of the seeded corpus is a dictionary, which
narrows on the host), so no per-layer metric divides by this yet; the cell
that ships PLAIN doubles (PERF.md section 7) reads it from here.
"""


def double_narrow_bytes(n_values: int) -> int:
    """double_narrow_device: 8 B read and 4 B written per value."""
    return 12 * n_values
