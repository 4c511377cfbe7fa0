"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the traced calls (per call the larger of flops over
peak FLOP/s and bytes over peak bytes/s, both from the shapes the trace
shows) over the time the calls took on the device. ``which_bound`` is
printed on an earlier line by the driver's notes."""
from benchmark.trace import opsbytes


def bounds(ctx, causal: bool):
    """(least seconds, measured seconds, {"compute"|"memory": least s})."""
    trace, peaks = ctx.get("trace"), ctx["peaks"]
    least = measured = 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    for k in (trace or {}).get("kernels", []):
        kind = opsbytes.classify_flash(k)
        if kind is None:
            continue
        fn = opsbytes.flash_forward if kind[0] == "fwd" \
            else opsbytes.flash_backward
        ebytes = opsbytes.DTYPE_BYTES[k["operands"][0][0]]
        flops, nbytes = fn(*kind[1:], causal, ebytes)
        t_c = flops / peaks["bf16_flops_per_s"]
        t_m = nbytes / peaks["hbm_bytes_per_s"]
        least += max(t_c, t_m) * k["calls"]
        by_bound["compute" if t_c >= t_m else "memory"] += \
            max(t_c, t_m) * k["calls"]
        measured += k["seconds"]
    return least, measured, by_bound


def read(ctx, causal: bool = True):
    least, measured, _ = bounds(ctx, causal)
    return 100.0 * least / measured if measured else None
