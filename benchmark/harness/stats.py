"""Small statistics, kept with the benchmark so no PR can change them."""
import math


def percentile(samples, q):
    """Nearest-rank percentile; raises on an empty sample (never NaN)."""
    if not samples:
        raise ValueError("percentile of an empty sample")
    xs = sorted(samples)
    k = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return float(xs[k])


def median(samples):
    return percentile(samples, 50)


def mean(samples):
    if not samples:
        raise ValueError("mean of an empty sample")
    return float(sum(samples)) / len(samples)
