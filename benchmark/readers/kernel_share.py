"""Device time of the Pallas kernels over device busy time, in %."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["kernels"] or not trace["busy_s"]:
        return None
    seconds = sum(k["seconds"] for k in trace["kernels"]) / trace["devices"]
    return 100.0 * seconds / trace["busy_s"]
