"""Work completed inside the window over the window's seconds: all the
work and all the time, nothing trimmed."""


def read(ctx, numerator: str, denominator: str = "window_s"):
    c = ctx.get("counters", {})
    if c.get(numerator) is None or not c.get(denominator):
        return None
    return c[numerator] / c[denominator]
