"""Mean of a /metrics histogram over the window: rise of <series>_sum over
rise of <series>_count (the registry's snapshot, the same series /metrics
renders), times `scale`."""


def read(obs, series, labels="", scale=1.0):
    n = obs.counters.get(f"{series}_count{labels}")
    total = obs.counters.get(f"{series}_sum{labels}")
    if not n or total is None:
        return None
    return scale * total / n
