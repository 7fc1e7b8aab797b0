"""Bytes recorded on the program's decode_trace stages, summed over the named
stages. A count: it repeats exactly. (No stage on today's upload path records
bytes, so no metric uses this yet: PERF.md section 3, upload_bytes_per_row.)"""

from per import scaled, stage_total


def read(obs, stages, per):
    total = stage_total(obs, stages, "bytes")
    return None if total is None else scaled(obs, total, per)
