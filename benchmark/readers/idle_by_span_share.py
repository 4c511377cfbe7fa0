"""Seconds the device was idle inside the traced window under the named
host spans of the program, in percent of the window: ``names`` are taken
letter for letter (``host idle`` is the reduction's name for a gap no
``rt.*`` span covers), ``prefixes`` take every span whose name starts so.
A gap belongs to the SHORTEST span that covers its middle
(``trace/program.py _innermost``), so a parent's share is what its
children leave. 0.0 where the engine stepped and no gap lay under such a
span; None where the reduction has no program part or the engine's loop
left no ``rt.llm.step`` in it."""
from benchmark.trace.program import program_of, spans_named


def read(ctx, names=(), prefixes=()):
    program = program_of(ctx)
    if not program or not program.get("window_s") or \
            not any(spans_named(program, "rt.llm.step")):
        return None
    idle = sum(seconds for name, seconds in program["idle_by_span"].items()
               if name in names or name.startswith(tuple(prefixes)))
    return 100.0 * idle / program["window_s"]
