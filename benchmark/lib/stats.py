"""The one percentile of the benchmark: nearest rank, the smallest value with
at least p % of the samples at or below it, so always a value that was
observed."""
import math


def percentile(values, p: float) -> float:
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return float(xs[rank - 1])

