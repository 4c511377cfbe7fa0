"""Share of decode slots that produced a token. The engine's counters are
read inside the traced window but not at its edges, so two rates are
compared: tokens delivered a second (less each finished request's first
token, the prefill lane's) over the counters' interval, against slot-steps
a second in the trace (executions of the engine's step programs x tokens
one execution advances a slot x slots, over the traced window)."""
import re


def read(ctx, pattern: str):
    trace, c = ctx.get("trace"), ctx.get("counters", {})
    if not trace or not c.get("trace_counts_s") or not trace["window_s"]:
        return None
    steps = sum(len(ds) for name, ds in trace["modules"].items()
                if re.search(pattern, name))
    if not steps:
        return None
    produced_per_s = (c["trace_tokens"] - c.get("trace_requests", 0)) \
        / c["trace_counts_s"]
    slot_steps_per_s = steps * c.get("decode_block", 1) * c["num_slots"] \
        / trace["window_s"]
    return 100.0 * produced_per_s / slot_steps_per_s
