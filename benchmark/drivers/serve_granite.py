"""Driver of the ``granite-4.0-h-micro`` serving cells: ``drivers/serve.py``'s
run, whole and unchanged (runtime -> ``serve.run`` -> HTTP, the cell's
traffic generator, counters, repeated request, reference), with two of
its names bound to this configuration's before it starts, as
``serve_solar.py`` binds its own:

  * the replica class ``deploy`` wraps (``serve_granite_replica.py``: the
    published sizes as the program's ``GraniteConfig``, the program part
    of the trace, the step's counters);
  * the bound on the reference comparison's largest gap (the limits on
    the mean gap and on the state a slot holds are applied by the
    replica; ``serve_granite_replica.py`` has all four beside the
    readings they are set from).

A checkout whose program has no such family (the parent of the PR that
added it) fails here, before the runtime is started: non-zero, at once.
"""

from __future__ import annotations


def require_family(config: dict) -> None:
    import importlib.util

    from ray_tpu.models import serving

    family = "granite"
    if family not in getattr(serving, "FAMILIES", ()) or \
            importlib.util.find_spec(f"ray_tpu.models.{family}") is None:
        raise RuntimeError(
            f"configuration {config['name']!r} needs the {family!r} serving "
            "family (ray_tpu/models/granite.py, named in models/serving.py "
            "FAMILIES); this checkout's program has none")


def run(manifest, cell: dict, **kwargs) -> dict:
    require_family(cell["config"])
    from benchmark.drivers import serve as base
    from benchmark.drivers.serve_granite_replica import (REFERENCE_MAX_GAP,
                                                         GraniteBenchServer)

    base.BenchLLMServer = GraniteBenchServer
    base.REFERENCE_MAX_GAP = REFERENCE_MAX_GAP
    return base.run(manifest, cell, **kwargs)
