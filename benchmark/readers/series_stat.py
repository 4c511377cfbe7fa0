"""A percentile (nearest rank, as bench.percentiles has it) or the mean of
one of the run's series; None where the series is empty or absent."""
import math


def read(ctx, series: str, stat):
    values = sorted(ctx.get("series", {}).get(series) or [])
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    rank = max(1, math.ceil(float(stat) / 100.0 * len(values)))
    return values[rank - 1]
