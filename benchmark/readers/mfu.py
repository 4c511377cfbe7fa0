"""Model FLOP/s utilisation: tokens per second x the operations the
forward and backward passes require per token (recomputation not counted)
over chips x the published peak. Tokens per second here is the steady
rate, tokens a step over the median time from one step's start to the
next (batch wait + step): this metric is read in the traced run, where
starting and stopping the profiler stalls the loop for seconds, so the
window's own rate would understate it. Untraced, the two agree."""
import statistics

from benchmark.trace import opsbytes


def read(ctx, flops_fn: str, series: str = "cycle_ms"):
    c = ctx.get("counters", {})
    cycles = ctx.get("series", {}).get(series)
    if not cycles or not c.get("tokens_per_step"):
        return None
    tokens_per_s = c["tokens_per_step"] / (statistics.median(cycles) * 1e-3)
    per_token = getattr(opsbytes, flops_fn)(ctx["config"], c["seq"])
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * tokens_per_s * per_token / peak
