"""Seconds of the program's decode_trace stages, summed over the named stages
(a name ending in * is a prefix). Stage seconds are sums over pool threads:
busy time, not wall time."""

from per import scaled, stage_total


def read(obs, stages, per):
    total = stage_total(obs, stages, "seconds")
    return None if total is None else scaled(obs, total, per)
