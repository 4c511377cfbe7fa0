"""Sum of one attribute over sum of another, in percent, across the
program's own spans of one name inside the traced window (``rt.llm.step``:
slots that decoded over slots there were). ``skip`` leaves out spans whose
attributes match it (steps that dispatched no program). None where the
trace's reduction has no program part or no such span: a program without
the spans leaves the metric out."""
from benchmark.trace.program import program_of, spans_named


def read(ctx, span: str, numerator: str, denominator: str, skip=None):
    program = program_of(ctx)
    if not program:
        return None
    num = den = 0.0
    for sp in spans_named(program, span):
        attrs = sp["attrs"]
        if skip and all(attrs.get(k) == v for k, v in skip.items()):
            continue
        if numerator not in attrs or denominator not in attrs:
            continue
        num += float(attrs[numerator])
        den += float(attrs[denominator])
    return 100.0 * num / den if den else None
