"""The replica the ``lfm2-24b-a2b`` cells deploy: the program's own
``LLMServer`` as ``serve_replica.BenchLLMServer`` extends it (timing of
streamed requests, the profiler, memory, the reference check), with

  * the configuration file's published sizes registered as the program's
    ``Lfm2Config`` under its name, before ``LLMServer.__init__`` looks the
    name up;
  * the PROGRAM part of the trace (``trace/program.py``: the ``rt.*``
    spans and device seconds by scope name) reduced here, from the
    ``.xplane.pb`` that ``capture.reduce_and_remove`` is about to delete,
    under the key ``program`` where the readers look for it — with this
    family's scope names added to the ones that module knows;
  * the engine's expert counters read when the trace starts and when it
    stops; their differences travel with ``engine_info``, whose keys the
    driver puts among the run's counters.

A program that has no such counter or scope leaves the keys out; nothing
here raises for it.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re

from benchmark.drivers.serve_replica import BenchLLMServer
from benchmark.manifest import Manifest
from benchmark.trace import capture
from benchmark.trace import program as trace_program
from ray_tpu.llm.serve import LLMServer

# scope names of models/lfm2.py that trace/program.py does not list; a
# dotted name is one word here
SCOPES = ("conv", "moe.route", "moe.experts")
COUNTERS = ("experts_hit", "expert_rows", "expert_rows_max", "steps_block",
            "steps_decode_only")
# The two limits of the reference comparison; ``drivers/serve_lfm2.py``
# says what they are set from.
REFERENCE_MAX_GAP = 2.0
REFERENCE_MEAN_GAP = 0.02


def lfm2_config(cfg: dict):
    """The configuration file's published keys as the program's
    ``Lfm2Config``; every width is the file's, none is derived."""
    import jax.numpy as jnp

    from ray_tpu.models.lfm2 import Lfm2Config

    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    if cfg["conv_bias"]:
        raise ValueError("the program's short convolution has no bias")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return Lfm2Config(
        vocab_size=cfg["vocab_size"], max_seq=cfg["max_position_embeddings"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        d_mlp=cfg["intermediate_size"], d_expert=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        use_expert_bias=bool(cfg["use_expert_bias"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        conv_L_cache=cfg["conv_L_cache"], norm_eps=float(cfg["norm_eps"]),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]), dtype=dtype)


@contextlib.contextmanager
def scopes_known(extra=SCOPES):
    """``trace/program.py`` with ``extra`` among its scope names and a
    dot allowed inside a word, for the reduction made inside."""
    was = trace_program.SCOPES, trace_program._TOKEN
    trace_program.SCOPES = was[0] + tuple(extra)
    trace_program._TOKEN = re.compile(r"[A-Za-z_][A-Za-z_0-9.]*")
    try:
        yield
    finally:
        trace_program.SCOPES, trace_program._TOKEN = was


def reduce_program(directory: str):
    """The program part of the trace under ``directory``, or None."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return None
    with scopes_known():
        return trace_program.reduce(trace_program.load(found[0]))


class Lfm2BenchServer(BenchLLMServer):
    def __init__(self, *args, bench_root: str, bench_config: str,
                 bench_chips: int, bench_rehearsal: bool = False, **kwargs):
        from ray_tpu.models import lfm2
        from ray_tpu.parallel.mesh import device_triple

        device = device_triple()
        if not bench_rehearsal and (device["platform"] == "cpu"
                                    or device["count"] < bench_chips):
            raise RuntimeError(
                f"the cell needs {bench_chips} accelerator chip(s); this "
                f"replica finds {device}. The benchmark does not run on "
                "the CPU.")
        self._bench_manifest = Manifest(bench_root)
        self._bench_cfg = self._bench_manifest.config(bench_config)
        lfm2.CONFIGS[self._bench_cfg["name"]] = lfm2_config(self._bench_cfg)
        LLMServer.__init__(self, *args, **kwargs)
        self._bench_handles = None  # None = not collecting
        self._bench_counts = {}
        submit = self.engine.submit

        def keeping_submit(prompt, *a, **kw):
            handle = submit(prompt, *a, **kw)
            if self._bench_handles is not None:
                self._bench_handles.append((kw.get("seed"), handle))
            return handle

        self.engine.submit = keeping_submit

    def _expert_counts(self) -> dict:
        return {k: getattr(self.engine, k) for k in COUNTERS
                if hasattr(self.engine, k)}

    def trace_start(self, directory: str) -> dict:
        out = super().trace_start(directory)
        self._bench_counts = self._expert_counts()
        return out

    def trace_stop(self) -> dict:
        now = self._expert_counts()
        delta = {f"trace_{k}": now[k] - v
                 for k, v in self._bench_counts.items()}
        if "trace_steps_block" in delta:
            delta["trace_steps"] = (delta.pop("trace_steps_block")
                                    + delta.pop("trace_steps_decode_only"))
        self._bench_counts = delta
        return super().trace_stop()

    def engine_info(self) -> dict:
        """Beside the decode block: what the expert layers counted
        between the trace's start and its stop (``trace_<counter>``), and
        how many experts x expert layers a step could have hit."""
        cfg = self.engine.cfg
        layers = len(cfg.layer_types) - cfg.num_dense_layers
        return dict(super().engine_info(), **self._bench_counts,
                    expert_slots=cfg.num_experts * layers,
                    expert_layers=layers)

    def check_reference(self, samples: list, reference: str) -> dict:
        """``serve.py``'s run holds the result to ONE limit, on
        ``max_gap``, and asks besides that every gap be ``finite``. This
        configuration has a second limit, on ``mean_gap``, and it is the
        one that tells a lower precision apart (``drivers/serve_lfm2.py``):
        its verdict is reported under its own keys and reaches the run
        through ``finite``, the only other thing the run asks of the
        result."""
        res = super().check_reference(samples, reference)
        res["mean_gap_bound"] = REFERENCE_MEAN_GAP
        res["mean_gap_ok"] = bool(res["mean_gap"] <= REFERENCE_MEAN_GAP)
        res["gaps_finite"] = res["finite"]
        res["finite"] = bool(res["finite"] and res["mean_gap_ok"])
        return res

    def trace_reduce(self) -> dict:
        program = reduce_program(self._bench_trace_dir)
        out = capture.reduce_and_remove(self._bench_trace_dir)
        if program is not None:
            out["program"] = program
        return out
