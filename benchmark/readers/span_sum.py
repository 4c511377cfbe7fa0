"""A total over the program's own spans of one name inside the traced
window: of the attribute ``attr`` (spans without it count 0), or, with no
``attr``, of their durations in milliseconds. 0.0 where the engine
stepped and nothing of the kind happened (no collection, no compile);
None where the reduction has no program part or the engine's loop left
no ``rt.llm.step`` in it."""
from benchmark.trace.program import program_of, spans_named


def read(ctx, span: str, attr=None):
    program = program_of(ctx)
    if not program or not any(spans_named(program, "rt.llm.step")):
        return None
    if attr is None:
        return sum(sp["duration_s"] * 1e3
                   for sp in spans_named(program, span))
    return float(sum(sp["attrs"].get(attr, 0)
                     for sp in spans_named(program, span)))
