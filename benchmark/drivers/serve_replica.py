"""The replica the serving cells deploy: the program's own ``LLMServer``
with the published geometry registered first and a few read-only methods
added. The request path (``__call__``, proxy, router, engine thread) is the
program's, untouched.

What is added, and only added:
  * the configuration's sizes become a ``LlamaConfig`` under its name in
    ``llama.CONFIGS`` inside this process, before ``LLMServer.__init__``
    looks the name up;
  * a wrapper round ``engine.submit`` that, while collecting, keeps the
    handles it returns, so the engine's own per-request timing exists for
    streamed requests too (a streamed response drops it);
  * ``trace_start`` / ``trace_stop`` / ``trace_reduce`` round
    ``jax.profiler`` — only the process that holds the chip can trace it;
  * ``memory_stats``, ``check_reference``.
"""

from __future__ import annotations

from benchmark.manifest import Manifest
from benchmark.trace import capture
from ray_tpu.llm.serve import LLMServer


def llama_config(cfg: dict):
    """The configuration file's published keys as the program's
    ``LlamaConfig``; every width is the file's, none is derived."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("hidden_size is not a multiple of the head count")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_model=cfg["hidden_size"],
        d_mlp=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
        dtype=dtype, remat=False)


class BenchLLMServer(LLMServer):
    def __init__(self, *args, bench_root: str, bench_config: str,
                 bench_chips: int, bench_rehearsal: bool = False, **kwargs):
        from ray_tpu.models import llama
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
        llama.CONFIGS[self._bench_cfg["name"]] = llama_config(self._bench_cfg)
        super().__init__(*args, **kwargs)
        self._bench_handles = None  # None = not collecting
        submit = self.engine.submit

        def keeping_submit(prompt, *a, **kw):
            import jax

            with jax.profiler.TraceAnnotation("bench.engine_submit"):
                handle = submit(prompt, *a, **kw)
            if self._bench_handles is not None:
                self._bench_handles.append((kw.get("seed"), handle))
            return handle

        self.engine.submit = keeping_submit

    # -- engine-side timing for streamed requests --------------------------

    def collect_timing(self, on: bool) -> int:
        self._bench_handles = [] if on else None
        return 0

    def engine_timings(self) -> list:
        """``[{"id", **timing}]`` of the finished requests kept so far."""
        out = []
        for rid, handle in self._bench_handles or []:
            if handle.timing is not None and rid is not None:
                out.append(dict(handle.timing, id=int(rid)))
        return out

    def engine_info(self) -> dict:
        """Tokens one execution of a step program advances a slot."""
        return {"decode_block": int(getattr(self.engine, "decode_block", 1))}

    # -- device trace -------------------------------------------------------

    def trace_start(self, directory: str) -> dict:
        capture.start(directory)
        self._bench_trace_dir = directory
        return self._counts()

    def trace_stop(self) -> dict:
        import jax

        counts = self._counts()  # before the seconds stop_trace takes
        jax.profiler.stop_trace()
        return counts

    def _counts(self) -> dict:
        """The engine's counters and the clock at this instant: read
        after the profiler has started and before it is stopped, because
        starting takes about a second and stopping several. The interval
        they span lies inside the traced window without being it, so the
        reader compares rates, not counts."""
        import time

        return {"tokens": self.engine.tokens_generated,
                "requests": self.engine.requests_completed,
                "t": time.monotonic()}

    def trace_reduce(self) -> dict:
        return capture.reduce_and_remove(self._bench_trace_dir)

    def memory_stats(self) -> dict:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()]
        return {"memory_peak_bytes": int(max(peaks)),
                "per_device_peak": [int(p) for p in peaks]}

    # -- correctness ----------------------------------------------------------

    def check_reference(self, samples: list, reference: str) -> dict:
        """Teacher-forces each sample's prompt + generated tokens through
        the benchmark's float32 reference on this replica's weights and
        returns, per generated token, how far its reference logit lies
        under that position's largest."""
        ref = self._bench_manifest.load_module("reference", reference)
        return ref.check_generated(self.engine._params, self._bench_cfg,
                                   samples)
