"""The replica the ``granite-4.0-h-micro`` cells deploy: the program's own
``LLMServer`` as ``serve_replica.BenchLLMServer`` extends it (timing of
streamed requests, the profiler, memory, the reference check), with, as
``serve_solar_replica.py`` has for its family,

  * the configuration file's published sizes registered as the program's
    ``GraniteConfig`` under its name, before ``LLMServer.__init__`` looks
    the name up;
  * the reference comparison given, beside the sampled requests' tokens,
    the state-space state a slot held after one of them
    (:func:`slot_state_after`);
  * the PROGRAM part of the trace reduced here with this family's scope
    names added to the ones ``trace/program.py`` knows;
  * the engine's step counters read when the trace starts and when it
    stops; their differences travel with ``engine_info``.

A program that has no such counter or scope leaves the keys out; nothing
here raises for it.
"""

from __future__ import annotations

import glob
import os
import time

import numpy as np

from benchmark.drivers.serve_lfm2_replica import scopes_known
from benchmark.drivers.serve_replica import BenchLLMServer
from benchmark.manifest import Manifest
from benchmark.trace import capture
from benchmark.trace import program as trace_program
from ray_tpu.llm.serve import LLMServer

# scope names of models/granite.py that trace/program.py does not list; a
# dotted name is one word here
SCOPES = ("ssm.proj", "ssm.conv", "ssm.scan", "ssm.out")
COUNTERS = ("ssm_rows", "prefill_tokens", "steps_block", "steps_decode_only")
# The four limits of the reference comparison. What is compared
# (``reference/granite_hybrid.py check_generated``): each sampled request's
# prompt and generated tokens through the float32 reference, and at every
# generated position the largest reference logit minus the reference logit
# of the token the engine chose (0 where it chose the reference's argmax);
# and the state one slot holds after a sampled request has run once more,
# alone, against the reference's own after the same tokens. The engine
# computes its products in bfloat16 and carries the state in float32; the
# reference is float32 throughout. The head is the embedding, drawn at
# 0.02 / 12 (the configuration's ``assumed``), so a position's logits
# spread by ``logit_std`` ~ 0.01 and every gap is read against that.
# With no router there is no near-tie to set the largest gap, so both gap
# limits are tighter than the sparse families'.
#
# Readings, one TPU v5e, PR 43 (PERF.md Findings has the table). The
# ENGINE over the runs of the final numerics (1261-1687 generated
# positions of four sampled requests a run): mean gap 1.3e-5 to 1.8e-5,
# largest gap 7.8e-4 to 1.15e-3, state_err 0.029-0.030, state_err_early
# 0.030-0.034, state_bits 23. The REFERENCE's own forward pass with ONE
# fault and the products in bfloat16 passes, which is the rounding such an
# engine has besides (``tools/granite_precision_probe.py``, three seeds,
# 512 generated positions after a prompt of 1024); with no fault it reads
# mean 1.0e-5 to 1.5e-5, largest 4.7e-4 to 5.9e-4, state_err 0.022-0.023,
# early 0.023-0.024:
#   every weight matrix in float8 e4m3 (the nearest precision below the
#     bfloat16 the configuration states): mean 5.5e-3 to 5.9e-3, largest
#     0.021-0.024, state_err 0.55-0.58;
#   the state rounded to bfloat16 after every token (the nearest below
#     its float32): state_bits 7; every other reading as with no fault;
#   the state not reset at admission: state_err_early 0.111-0.129; every
#     other reading as with no fault (after hundreds of tokens what a slot
#     kept has faded: hence the early replay);
#   ``D x`` dropped: mean 0.035-0.037 (a random token reads 0.036),
#     state_err 1.3-1.4;
#   the softmax at 1/8 for the published 1/64: mean 4.3e-3 to 4.4e-3,
#     largest 0.0165, state_err 0.44-0.49.
# Each limit lies between the engine's largest reading and the nearest
# fault's smallest, near their geometric middle: the mean gap 17 x above
# the engine and 14 x under the softmax scale's; the largest gap 3.5 x
# and 4 x; state_err 3.3 x and 4.4 x; state_err_early 1.8 x and 1.85 x
# (the narrowest: what a slot kept fades by half in 40 tokens).
REFERENCE_MAX_GAP = 0.004
REFERENCE_MEAN_GAP = 0.0003
REFERENCE_STATE_ERR = 0.1
REFERENCE_STATE_BITS = 16
REFERENCE_STATE_ERR_EARLY = 0.06
# the early replay: that many tokens of the first sample's prompt, and a
# few generated ones
EARLY_TOKENS, EARLY_NEW = 32, 8


def granite_config(cfg: dict):
    """The configuration file's keys as the program's ``GraniteConfig``:
    every width is the file's, none is derived."""
    import jax.numpy as jnp

    from ray_tpu.models.granite import GraniteConfig

    for key, want in (("position_embedding_type", "nope"),
                      ("num_local_experts", 0), ("mamba_n_groups", 1),
                      ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", True)):
        if cfg[key] != want:
            raise ValueError(f"the program computes {key} = {want!r} only")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not list num_hidden_layers layers")
    if cfg["mamba_expand"] * cfg["hidden_size"] != \
            cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is not heads x d_head")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return GraniteConfig(
        vocab_size=cfg["vocab_size"], max_seq=cfg["max_position_embeddings"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_mlp=cfg["shared_intermediate_size"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], conv_kernel=cfg["mamba_d_conv"],
        layer_types=tuple(cfg["layer_types"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=dtype)


def slot_state_after(engine, prompt, tokens, timeout: float = 300.0):
    """``prompt`` through the engine once more, greedy, for ``len(tokens)``
    tokens, with nothing else in flight -> (the tokens it gave, the state
    [mamba layers, H, P, N] float32 its slot holds once the engine has
    nothing left to do, or None if the slot was never seen). The state is
    read by a control operation on the engine's own thread, between
    steps."""
    from ray_tpu.ops.ssm_scan import heads_view

    handle = engine.submit(prompt, max_new=len(tokens), temperature=0.0)
    idx = None
    while idx is None and not handle._done.is_set():
        idx = next((i for i, s in enumerate(engine._slots)
                    if s is not None and s.handle is handle), None)
        time.sleep(0.002)
    got = handle.result(timeout).tokens
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with engine._work:
            if not engine._has_work_locked():
                break
        time.sleep(0.01)
    if idx is None:
        return got, None
    return got, engine._run_control(lambda: np.asarray(heads_view(
        engine._cache["ssm"][:, idx], engine.cfg.ssm_heads)))


def reduce_program(directory: str):
    """The program part of the trace under ``directory``, or None."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return None
    with scopes_known(SCOPES):
        return trace_program.reduce(trace_program.load(found[0]))


class GraniteBenchServer(BenchLLMServer):
    def __init__(self, *args, bench_root: str, bench_config: str,
                 bench_chips: int, bench_rehearsal: bool = False, **kwargs):
        from ray_tpu.models import granite
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
        granite.CONFIGS[self._bench_cfg["name"]] = granite_config(
            self._bench_cfg)
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

    def _step_counts(self) -> dict:
        return {k: getattr(self.engine, k) for k in COUNTERS
                if hasattr(self.engine, k)}

    def trace_start(self, directory: str) -> dict:
        out = super().trace_start(directory)
        self._bench_counts = self._step_counts()
        return out

    def trace_stop(self) -> dict:
        now = self._step_counts()
        delta = {f"trace_{k}": now[k] - v
                 for k, v in self._bench_counts.items()}
        if "trace_steps_block" in delta:
            delta["trace_steps"] = (delta["trace_steps_block"]
                                    + delta.pop("trace_steps_decode_only"))
        self._bench_counts = delta
        return super().trace_stop()

    def engine_info(self) -> dict:
        """Beside the decode block: what the step counted between the
        trace's start and its stop (``trace_<counter>``)."""
        return dict(super().engine_info(), **self._bench_counts)

    def check_reference(self, samples: list, reference: str) -> dict:
        """The first sample runs once more, alone, and the state its slot
        then holds goes to the reference with it. The limits on
        ``mean_gap``, ``state_err`` and ``state_bits`` reach
        ``serve.py``'s run through ``finite`` (as the limits of
        ``serve_solar_replica.py`` do)."""
        first = samples[0]
        again, state = slot_state_after(self.engine, first["prompt"],
                                        first["tokens"])
        # and its first EARLY_TOKENS alone, a few tokens out: what a slot
        # kept of its last request has not yet faded there (after
        # hundreds of tokens it has, and no limit above would see a slot
        # that admission did not reset)
        early = {"prompt": list(first["prompt"][:EARLY_TOKENS])}
        early["tokens"], early["state"] = slot_state_after(
            self.engine, early["prompt"], [0] * EARLY_NEW)
        samples = [dict(first, state=state)] + list(samples[1:])
        res = super().check_reference(samples, reference)
        ref = self._bench_manifest.load_module("reference", reference)
        res["state_err_early"] = ref.check_generated(
            self.engine._params, self._bench_cfg, [early]).get(
                "state_err", float("inf"))
        res.update(
            state_err_early_bound=REFERENCE_STATE_ERR_EARLY,
            mean_gap_bound=REFERENCE_MEAN_GAP,
            mean_gap_ok=bool(res["mean_gap"] <= REFERENCE_MEAN_GAP),
            state_err_bound=REFERENCE_STATE_ERR,
            state_bits_bound=REFERENCE_STATE_BITS,
            state_replay_same=list(again) == list(first["tokens"]),
            state_ok=bool(res.get("state_err", np.inf) <= REFERENCE_STATE_ERR
                          and res["state_err_early"]
                          <= REFERENCE_STATE_ERR_EARLY
                          and res.get("state_bits", 0)
                          >= REFERENCE_STATE_BITS),
            gaps_finite=res["finite"])
        res["finite"] = bool(res["finite"] and res["mean_gap_ok"]
                             and res["state_ok"]
                             and res["state_replay_same"])
        return res

    def trace_reduce(self) -> dict:
        program = reduce_program(self._bench_trace_dir)
        out = capture.reduce_and_remove(self._bench_trace_dir)
        if program is not None:
            out["program"] = program
        return out
