"""A percentile (nearest rank) or the mean of the durations, in
milliseconds, of the program's own spans of one name inside the traced
window, optionally only those whose attributes match ``where``. None where
there is no such span."""
import math

from benchmark.trace.program import program_of, spans_named


def read(ctx, span: str, stat, where=None):
    program = program_of(ctx)
    if not program:
        return None
    values = sorted(sp["duration_s"] * 1e3
                    for sp in spans_named(program, span, where))
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    rank = max(1, math.ceil(float(stat) / 100.0 * len(values)))
    return values[rank - 1]
