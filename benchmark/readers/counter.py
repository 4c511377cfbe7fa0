"""One of the run's counters as it is."""


def read(ctx, name: str):
    return ctx.get("counters", {}).get(name)
