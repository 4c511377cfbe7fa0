"""The delta-rule recurrence's share of its roofline: the least time the
chip could take for the rows the traced steps carried (per row the larger
of flops over peak FLOP/s and bytes over peak bytes/s,
``trace/opsbytes_kda.py``) over the device seconds under the program's
``scope``. How many rows a step carried comes from the engine's own
counter over the traced interval, as a mean a step (``trace_kda_rows``,
summed over the recurrent layers, over ``trace_steps``); how many steps
the trace holds from the executions of the step programs in it; the head
count and sizes from the configuration. None where the program has no
such counter or scope, or the configuration no such layer."""
import re

from benchmark.trace import opsbytes_kda
from benchmark.trace.program import program_of


def read(ctx, scope: str, pattern: str):
    trace, c, peaks = ctx.get("trace"), ctx.get("counters", {}), ctx["peaks"]
    program = program_of(ctx)
    geo = ctx.get("config", {}).get("linear_attn_config")
    if not trace or not program or not geo or not c.get("trace_steps") \
            or "trace_kda_rows" not in c:
        return None
    measured = program.get("scopes", {}).get(scope)
    steps = sum(len(ds) for name, ds in trace["modules"].items()
                if re.search(pattern, name))
    if not measured or not steps:
        return None
    rows = steps * c["trace_kda_rows"] / c["trace_steps"]
    flops, nbytes = opsbytes_kda.row(geo["num_heads"], geo["head_dim"],
                                     geo["head_dim"])
    least = rows * max(flops / peaks["bf16_flops_per_s"],
                       nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / measured
