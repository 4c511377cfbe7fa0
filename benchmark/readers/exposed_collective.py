"""Collective time that no compute hides, over the traced window, in %:
per device the union of the collective operations' intervals minus the
union of the compute operations', averaged over the devices."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or trace["devices"] < 2 or not trace["window_s"]:
        return None
    return 100.0 * trace["exposed_collective_s"] / trace["window_s"]
