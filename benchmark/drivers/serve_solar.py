"""Driver of the ``solar-open2-250b`` serving cells: ``drivers/serve.py``'s
run, whole and unchanged (runtime -> ``serve.run`` -> HTTP, the cell's
traffic generator, counters, repeated request, reference), with two of
its names bound to this configuration's before it starts, as
``serve_lfm2.py`` binds its own:

  * the replica class ``deploy`` wraps (``serve_solar_replica.py``: the
    published sizes and this chip's share as the program's
    ``SolarConfig``, the program part of the trace, the step's counters);
  * the bound on the reference comparison's largest gap (below; the
    limits on the mean gap and on the KDA state a slot holds are applied
    by the replica).

A checkout whose program has no such family (the parent of the PR that
added it) fails here, before the runtime is started: non-zero, at once.
"""

from __future__ import annotations

from benchmark.drivers import serve as base
from benchmark.drivers.serve_solar_replica import (REFERENCE_MAX_GAP,
                                                   SolarBenchServer)

# The reference comparison, as ``serve.py`` makes it: each sampled request's
# prompt and generated tokens through the float32 reference
# (``reference/solar_open2.py``, given the same share of the experts and
# the same slice of the vocabulary), and at every generated position the
# largest reference logit minus the reference logit of the token the engine
# chose (0 where it chose the reference's argmax). The engine computes its
# products in bfloat16 and carries the KDA state in float32; the reference
# is float32 throughout.
#
# As for ``lfm2-24b-a2b`` (``serve_lfm2.py``) a router's near-tie sets the
# LARGEST gap of a run whatever the precision: where the reference's 8th
# and 9th scores lie closer than the rounding of the router's input, the
# engine picks the other expert, and if either is one of the 20 held here
# that token's FFN output in that layer changes by one expert's part. So
# the two limits are ``lfm2``'s two: the MEAN gap is the one a lower
# precision, a dropped pick or a stale state fails; the LARGEST gap keeps a
# gross fault at a single position from hiding in the mean.
#
# Neither sees HOW THE STATE IS HELD: a KDA state rounded to bfloat16 after
# every token stays under both. So the replica runs one sampled request
# once more, alone, reads the state its slot then holds, and the reference
# holds that to its own state after the same tokens (``state_err``) and to
# the float32 the configuration states for it (``state_bits``).
#
# The readings all four are set from are in ``serve_solar_replica.py``
# beside the numbers, and in PERF.md Findings PR 39.


def require_family(config: dict) -> None:
    import importlib.util

    from ray_tpu.models import serving

    family = "solar"
    if family not in getattr(serving, "FAMILIES", ()) or \
            importlib.util.find_spec(f"ray_tpu.models.{family}") is None:
        raise RuntimeError(
            f"configuration {config['name']!r} needs the {family!r} serving "
            "family (ray_tpu/models/solar.py, named in models/serving.py "
            "FAMILIES); this checkout's program has none")


def run(manifest, cell: dict, **kwargs) -> dict:
    require_family(cell["config"])
    base.BenchLLMServer = SolarBenchServer
    base.REFERENCE_MAX_GAP = REFERENCE_MAX_GAP
    return base.run(manifest, cell, **kwargs)
