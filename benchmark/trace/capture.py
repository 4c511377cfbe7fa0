"""Start and finish a profiler trace in the process that holds the chip
(only that process can trace it); the serving replica and the train worker
both record through here."""

from __future__ import annotations

import glob
import os
import shutil

from benchmark.trace import reduce as trace_reduce


def start(directory: str) -> None:
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from TraceAnnotation
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)


def reduce_and_remove(directory: str) -> dict:
    """The reduced trace of the ``.xplane.pb`` under ``directory``, which
    is then deleted: traces are large and only their reduction is kept."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under "
                           f"{directory}")
    try:
        return trace_reduce.reduce(trace_reduce.load_xplane(found[0]))
    finally:
        shutil.rmtree(directory, ignore_errors=True)
