"""The experts' grouped matrix products' share of their roofline: the
least time the chip could take for the traced calls (per call the larger
of flops over peak FLOP/s and bytes over peak bytes/s) over the time the
calls took on the device. The shapes come from the trace; how many
experts a call's rows hit and how many rows were routed come from the
engine's own counters over the traced interval, as a mean a layer a step
(``trace_experts_hit``, ``trace_expert_rows`` over ``trace_steps`` x
``expert_layers``). None where the trace holds no such kernel or the
program no such counter."""
from benchmark.trace import opsbytes_moe


def read(ctx):
    trace, c, peaks = ctx.get("trace"), ctx.get("counters", {}), ctx["peaks"]
    layer_steps = (c.get("trace_steps") or 0) * (c.get("expert_layers") or 0)
    if not trace or not layer_steps or "trace_experts_hit" not in c:
        return None
    hit = c["trace_experts_hit"] / layer_steps       # experts a call
    rows = c["trace_expert_rows"] / layer_steps      # routed rows a call
    least = measured = 0.0
    for k in trace.get("kernels", []):
        shape = opsbytes_moe.classify_grouped_matmul(k)
        if shape is None:
            continue
        m, kk, n, g, ebytes = shape
        flops, nbytes = opsbytes_moe.grouped_matmul(
            min(rows, m), min(hit, g), m, kk, n, ebytes)
        least += k["calls"] * max(flops / peaks["bf16_flops_per_s"],
                                  nbytes / peaks["hbm_bytes_per_s"])
        measured += k["seconds"]
    return 100.0 * least / measured if measured else None
