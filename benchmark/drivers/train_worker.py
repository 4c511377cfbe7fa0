"""The training cells' loop: runs in the train worker, the process that
holds the chip(s), started by ``DataParallelTrainer.fit()``.

It builds the step exactly as a user of the Train library does
(``build_sharded_train`` over the mesh of the ``ScalingConfig``), then
does what a loop that reports every step does: take the next batch from a
prefetch thread, run the step, fetch the loss.
"""

from __future__ import annotations

import math
import queue
import threading
import time

TRACE_SECONDS = 5.0
# |program loss - float32 reference loss| on the same weights and batch.
# The program computes activations in bfloat16 with float32 layer norm,
# softmax and loss, and the loss is a mean over thousands of tokens, so
# the rounding errors of single logits average out: measured on the chip
# the difference was at most 0.0001 at a loss near 7 (PERF.md Findings).
# Eight-bit floats have sixteen times bfloat16's rounding error per
# operation and a bias that does not average out, and fail this.
REFERENCE_LOSS_TOLERANCE = 0.002


def _optimizer(training: dict):
    import optax

    from ray_tpu.train.optim import adamw_lowmem

    lr = training["learning_rate"]
    if training["optimizer"] == "adamw_lowmem":
        return adamw_lowmem(lr)
    if training["optimizer"] == "adamw":
        # float32 moments; same clip, betas and decay as adamw_lowmem
        return optax.chain(optax.clip_by_global_norm(1.0),
                           optax.adamw(lr, b1=0.9, b2=0.95,
                                       weight_decay=0.1))
    raise ValueError(f"unknown optimizer {training['optimizer']!r}")


def _gpt2(cfg: dict, training: dict):
    import jax.numpy as jnp

    from ray_tpu.models import gpt2

    if cfg["n_embd"] % cfg["n_head"]:
        raise ValueError("n_embd is not a multiple of n_head")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        training.get("dtype", "bfloat16")]
    mcfg = gpt2.GPT2Config(
        vocab_size=cfg["vocab_size"], max_seq=cfg["n_positions"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        d_model=cfg["n_embd"], d_mlp=cfg.get("n_inner"), dtype=dtype,
        attention_impl=training["attention_impl"], remat=True,
        remat_policy=training["remat_policy"])
    return (lambda key: gpt2.init_params(key, mcfg)), \
        (lambda rules: lambda p, b: gpt2.loss_fn(p, b, mcfg, rules))


MODELS = {"gpt2": _gpt2}


def _bytes_by_device(tree) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return out


def train_loop(config: dict) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark.manifest import Manifest
    from benchmark.trace import capture
    from ray_tpu.parallel.mesh import MeshSpec, device_triple
    from ray_tpu.parallel.sharding import prune_rules_for_mesh
    from ray_tpu.train import session
    from ray_tpu.train.step import build_sharded_train

    device = device_triple()
    chips = config["chips"]
    if not config["rehearsal"] and (device["platform"] == "cpu"
                                    or device["count"] < chips):
        raise RuntimeError(f"the cell needs {chips} accelerator chip(s); "
                           f"this worker finds {device}")
    cfg, traffic = config["config"], config["traffic"]
    training = cfg["training"]
    seconds, seed = config["seconds"], config["seed"]
    t_phase = {"start": time.time()}
    mesh_spec = config.get("mesh_spec") or MeshSpec(**training["mesh"])
    mesh = mesh_spec.build(jax.devices()[:mesh_spec.num_devices])
    init_fn, loss_for = MODELS[training["model"]](cfg, training)
    rules = prune_rules_for_mesh(mesh)
    sinit, sstep, _ = build_sharded_train(
        init_fn, loss_for(rules), mesh, optimizer=_optimizer(training),
        master_fp32=training["master_fp32"])
    params, opt_state, step = sinit(jax.random.PRNGKey(seed % (2**31 - 1)))
    jax.block_until_ready(params)
    t_phase["init"] = time.time()

    # Input pipeline: a host thread keeps `prefetch_depth` batches ready.
    manifest = Manifest(config["root"])
    gen = manifest.load_module("traffic", traffic["generator"])
    stream = gen.batches(traffic, seed, training["batch"], cfg["vocab_size"])
    ready: queue.Queue = queue.Queue(maxsize=int(traffic["prefetch_depth"]))
    stop = threading.Event()

    def produce():
        for batch in stream:
            while not stop.is_set():
                try:
                    ready.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if stop.is_set():
                return

    producer = threading.Thread(target=produce, name="bench-prefetch",
                                daemon=True)
    producer.start()
    try:
        first = {"tokens": jnp.asarray(ready.get())}
        lowered = sstep.lower(params, opt_state, step, first)
        pallas_calls = lowered.as_text().count("tpu_custom_call")
        compiled = lowered.compile()
        t_phase["compile"] = time.time()
        data = first
        for _ in range(int(traffic.get("warmup_steps", 3))):
            params, opt_state, step, metrics = compiled(
                params, opt_state, step, data)
            float(metrics["loss"])
            data = {"tokens": jnp.asarray(ready.get())}
        t_phase["warmup"] = time.time()

        # ---- the window: closes at the first step boundary past --seconds
        setup_s = time.time() - config["t0"]
        step_s, wait_s, losses = [], [], []
        tracing, traced, trace_dir = "no", None, config.get("trace_dir")
        trace_at = max(0.0, (seconds - TRACE_SECONDS) / 2)
        t_start = time.monotonic()
        while True:
            now = time.monotonic() - t_start
            if trace_dir and tracing == "no" and now >= trace_at:
                capture.start(trace_dir)
                tracing, t_trace = "on", now
            with jax.profiler.TraceAnnotation("bench.next_batch"):
                t0 = time.monotonic()
                data = {"tokens": jnp.asarray(ready.get())}
                t1 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.step_dispatch"):
                params, opt_state, step, metrics = compiled(
                    params, opt_state, step, data)
            with jax.profiler.TraceAnnotation("bench.fetch_loss"):
                loss = float(metrics["loss"])
            t2 = time.monotonic()
            wait_s.append(t1 - t0)
            step_s.append(t2 - t1)
            losses.append(loss)
            if tracing == "on" and (t2 - t_start) - t_trace >= min(
                    TRACE_SECONDS, seconds):
                jax.profiler.stop_trace()
                tracing = "done"
            if t2 - t_start >= seconds:
                break
        window_s = time.monotonic() - t_start
        if tracing == "on":
            jax.profiler.stop_trace()

        # ---- outside the window: reference, spread, memory, trace ---------
        ref = manifest.load_module("reference", cfg["reference"])
        check_batch = ready.get()
        ref_loss = ref.loss(params, cfg, check_batch)
        params, opt_state, step, metrics = compiled(
            params, opt_state, step, {"tokens": jnp.asarray(check_batch)})
        prog_loss = float(metrics["loss"])
        spread = None
        if mesh.size > 1:
            by_dev = _bytes_by_device((params, opt_state))
            total = sum(by_dev.values())
            spread = {str(d): b / total for d, b in sorted(by_dev.items())}
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:mesh.size]]
        if tracing != "no":
            traced = capture.reduce_and_remove(trace_dir)
    finally:
        stop.set()
        producer.join(timeout=10)
    session.report({
        "device": device, "setup_s": setup_s, "window_s": window_s,
        "step_s": step_s, "wait_s": wait_s, "losses": losses,
        "pallas_calls": pallas_calls, "ref_loss": ref_loss,
        "prog_loss": prog_loss, "state_spread": spread,
        "memory_peak_bytes": int(max(peaks)), "per_device_peak": peaks,
        "trace": traced, "mesh": mesh_spec.describe(),
        "phases_s": {k: round(t_phase[k] - t_phase[p], 3) for p, k in
                     (("start", "init"), ("init", "compile"),
                      ("compile", "warmup"))},
        "worker_started_s": round(t_phase["start"] - config["t0"], 3),
        "all_finite": all(math.isfinite(x) for x in losses),
    })
