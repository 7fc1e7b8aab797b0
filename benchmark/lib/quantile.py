"""One quantile rule for every latency and lateness the benchmark reports:
linear between the two nearest ranks of the sorted sample."""


def quantile(values, q: float):
    v = sorted(values)
    if not v:
        return None
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
