"""Device seconds under the named scopes, in percent of the device's busy
seconds. ``phase`` restricts to one half of a differentiated program
(``forward`` / ``backward``; recomputation runs in the backward half).
None where the trace's reduction has no program part or the program
carries none of the scopes."""
from benchmark.trace.program import program_of


def read(ctx, scopes, phase=None):
    program = program_of(ctx)
    if not program or not program.get("busy_s"):
        return None
    if phase is None:
        table, keys = program["scopes"], list(scopes)
    else:
        table = program["scope_phases"]
        keys = [f"{s}:{phase}" for s in scopes]
    if not any(k in table for k in keys):
        return None
    return 100.0 * sum(table.get(k, 0.0) for k in keys) / program["busy_s"]
