"""The state-space recurrence's share of its roofline: the least time the
chip could take for the rows the traced steps carried (the larger of
their flops over peak FLOP/s and their bytes over peak bytes/s,
``trace/opsbytes_ssm.py``) over the device seconds under the program's
``scope``. How many rows a step carried, and how many tokens they held,
come from the engine's own counters over the traced interval, as means a
step: ``trace_ssm_rows`` (summed over the recurrent layers), and for the
tokens a decode row's one and a prompt chunk's ``trace_prefill_tokens``
over ``trace_steps_block`` chunks. How many steps the trace holds comes
from the executions of the step programs in it; the head count and sizes
from the configuration. None where the program has no such counter or
scope, or the configuration no such layer."""
import re

from benchmark.trace import opsbytes_ssm
from benchmark.trace.program import program_of


def read(ctx, scope: str, pattern: str):
    trace, c, peaks = ctx.get("trace"), ctx.get("counters", {}), ctx["peaks"]
    program, cfg = program_of(ctx), ctx.get("config", {})
    layers = list(cfg.get("layer_types") or []).count("mamba")
    if not trace or not program or not layers or not c.get("trace_steps") \
            or "trace_ssm_rows" not in c:
        return None
    measured = program.get("scopes", {}).get(scope)
    steps = sum(len(ds) for name, ds in trace["modules"].items()
                if re.search(pattern, name))
    if not measured or not steps:
        return None
    # a chunk is one row of its layer and holds the lane's tokens
    rows = c["trace_ssm_rows"]
    tokens = rows + layers * (c.get("trace_prefill_tokens", 0)
                              - c.get("trace_steps_block", 0))
    flops, nbytes = opsbytes_ssm.rows(
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
        rows, max(tokens, rows))
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * (steps / c["trace_steps"]) * least / measured
