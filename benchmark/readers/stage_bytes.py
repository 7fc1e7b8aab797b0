"""Bytes recorded on the program's decode_trace stages, summed over the named
stages. A count: it repeats exactly. upload_bytes_per_row reads the
`dispatch.upload` stages through it (PERF.md section 3)."""

from per import scaled, stage_total


def read(obs, stages, per):
    total = stage_total(obs, stages, "bytes")
    return None if total is None else scaled(obs, total, per)
