"""Median device duration, in ms, of one execution of the compiled
programs whose name matches ``pattern`` — from the device trace."""
import re
import statistics


def read(ctx, pattern: str):
    trace = ctx.get("trace")
    if not trace:
        return None
    durations = [d for name, ds in trace["modules"].items()
                 if re.search(pattern, name) for d in ds]
    return statistics.median(durations) * 1e3 if durations else None
