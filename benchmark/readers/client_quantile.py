"""A quantile of a series the load generator recorded per request (e.g.
late_ms: sent - due)."""

from quantile import quantile


def read(obs, series, q):
    return quantile(obs.client.get(series, []), q)
