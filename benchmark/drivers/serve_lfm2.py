"""Driver of the ``lfm2-24b-a2b`` serving cells: ``drivers/serve.py``'s
run, whole and unchanged (runtime -> ``serve.run`` -> HTTP, the cell's
traffic generator, counters, repeated request, reference), with two of
its names bound to this configuration's before it starts:

  * the replica class ``deploy`` wraps (``serve_lfm2_replica.py``: the
    published sizes as the program's ``Lfm2Config``, the program part of
    the trace, the expert counters);
  * the bound on the reference comparison's largest gap, which this
    configuration sets for itself (below; its second limit, on the mean
    gap, is applied by the replica).

``serve.py`` looks both up in its own module when it runs, and a
benchmark run is one process for one cell, so binding them there is the
whole of it; a PR of this kind may add benchmark files and edit none.

A checkout whose program has no such family (the parent of the PR that
added it) fails here, before the runtime is started: non-zero, at once.
"""

from __future__ import annotations

from benchmark.drivers import serve as base
from benchmark.drivers.serve_lfm2_replica import (REFERENCE_MAX_GAP,
                                                  Lfm2BenchServer)

# The reference comparison, as ``serve.py`` makes it: each sampled request's
# prompt and generated tokens through the float32 reference, and at every
# generated position the largest reference logit minus the reference logit
# of the token the engine chose (0 where it chose the reference's argmax).
# The engine computes in bfloat16; the reference is float32 throughout.
#
# Beside the rounding every bfloat16 model has, this architecture has a
# source of error of its own: where the reference's 4th and 5th expert
# scores lie closer than the rounding of the router's input moves them,
# the engine picks the other expert, and a quarter of that token's FFN
# output in that layer is another expert's. Such a flip moves a logit by
# tenths, whatever the precision: it sets the LARGEST gap of a run, in
# bfloat16 as in float8, so the largest gap cannot tell the two apart. How
# OFTEN the choice leaves the reference's argmax, and by how much on
# average, can: a lower precision disturbs every token's every layer.
#
# Readings on the chip, one TPU v5e (PERF.md Findings, PR 31), engine,
# over 15 seeds of the cell: mean gap 0.0021 to 0.0055, largest 0.35 to
# 0.96, on the argmax 95.4 to 98.4 %; the same forward pass with the
# experts' weights rounded to float8 e4m3, the nearest precision below
# the one the configuration states (``tools/lfm2_precision_probe.py``,
# its argmax tokens over 512 positions, three seeds): mean gap 0.040,
# 0.050 and 0.042, largest 0.66, 0.64 and 0.65, on the argmax 70 to 71 %;
# with the fourth expert of every token dropped: mean 0.17, 0.18 and
# 0.17, largest 1.31, 1.16 and 1.21, on the argmax 46 to 49 %.
#
# So two limits, both in ``serve_lfm2_replica.py``: the MEAN gap, set
# between the engine's largest and float8's smallest with room on both
# sides, is the one a lower precision or a dropped expert fails; the
# LARGEST gap keeps a gross fault at a single position from hiding in the
# mean (a wrong token among thousands: a uniformly random token reads 3.57
# on average and 1.62 at the least of 512 positions; the engine's largest
# over 15 seeds is 0.96).


def require_family(config: dict) -> None:
    import importlib.util

    from ray_tpu.models import serving

    family = "lfm2"
    if family not in getattr(serving, "FAMILIES", ()) or \
            importlib.util.find_spec(f"ray_tpu.models.{family}") is None:
        raise RuntimeError(
            f"configuration {config['name']!r} needs the {family!r} serving "
            "family (ray_tpu/models/lfm2.py, named in models/serving.py "
            "FAMILIES); this checkout's program has none")


def run(manifest, cell: dict, **kwargs) -> dict:
    require_family(cell["config"])
    base.BenchLLMServer = Lfm2BenchServer
    base.REFERENCE_MAX_GAP = REFERENCE_MAX_GAP
    return base.run(manifest, cell, **kwargs)
