"""What a per-layer reader divides by, named in the metric's file as "per":
"mrow" (10^6 rows delivered in the window), "window_percent" (the window's
length, as a share in %), or {"counter": key or prefix*} (the rise of a
program counter over the window, e.g. query units). None when there is
nothing to divide by, so the metric is left out."""

from __future__ import annotations


def stage_total(obs, names: list, field: str):
    """Sum of `field` (seconds, bytes, calls) over the decode_trace stages
    named (a name ending in * is a prefix); None when none was recorded."""
    hits = [s[field] for k, s in obs.stages.items()
            if any(k == n or (n.endswith("*") and k.startswith(n[:-1])) for n in names)]
    return sum(hits) if hits and sum(hits) else None


def counter_rise(obs, key: str):
    if key.endswith("*"):
        hits = [v for k, v in obs.counters.items() if k.startswith(key[:-1])]
        return sum(hits) if hits else None
    return obs.counters.get(key)


def scaled(obs, amount: float, per):
    """`amount` (seconds -> ms, or a count) over the denominator `per`."""
    if per == "mrow":
        return amount * 1e3 / (obs.rows / 1e6) if obs.rows else None
    if per == "window_percent":
        return 100.0 * amount / obs.window_s if obs.window_s else None
    if per == "row":
        return amount / obs.rows if obs.rows else None
    if isinstance(per, dict) and "counter" in per:
        n = counter_rise(obs, per["counter"])
        return amount * 1e3 / n if n else None
    raise ValueError(f"unknown denominator {per!r}")
