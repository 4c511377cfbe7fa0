"""One of the run's counters over the product of others, in percent;
None where any of them is absent or the product is zero."""


def read(ctx, numerator: str, denominators):
    c = ctx.get("counters", {})
    den = 1.0
    for name in denominators:
        if c.get(name) is None:
            return None
        den *= c[name]
    if c.get(numerator) is None or not den:
        return None
    return 100.0 * c[numerator] / den
