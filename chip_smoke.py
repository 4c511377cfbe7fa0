#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the two paths the chip executes, once each, through the entry
points a user calls, at the full width and depth of a model the repo
supports (weights random, from ``--seed``), and checks what comes out.

    python chip_smoke.py             one chip: Serve phase, then Train phase
    python chip_smoke.py --chips 4   the two cross-chip paths, and nothing else

One chip (what the driver runs), one phase after the other; the first
shuts its runtime down, which frees the chip, before the second starts:

  serve  rt.init -> serve.start -> serve.run(build_llm_app("llama-1b", 8
         slots, chunk 128, decode_block 16)) -> HTTP: a cold request, the
         same again, four at once, one streamed, a two-turn session.
  train  DataParallelTrainer(loop, ScalingConfig(num_workers=1,
         use_tpu=True)).fit(): gpt2-774m, batch 8 x seq 1024, bf16, flash
         attention, mem2 remat, adamw_lowmem over an fp32 master.

A chip belongs to one process at a time, so THIS process never initialises
a JAX backend on that run: the replica and the train worker hold the chip,
and the device triple of the last line is the one they report. Without an
accelerator the script fails within seconds (a probe child asks JAX and
exits); it never goes on on the CPU. Any failed check raises, the script
exits 1, and its last line says ``"ok": false``.

``--chips 4`` runs, in this one process over all four chips, (a) the
gpt2-774m step on MeshSpec(fsdp=2, tp=2) against the same steps on one of
the four devices, and (b) SlotEngine llama-1b greedy tokens at tp=4
against tp=1 — with each device's bytes printed and the spread asserted.

Last line of stdout, exactly:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

import argparse
import gc
import json
import math
import os
import socket
import sys
import threading
import time
import traceback
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

T0 = time.monotonic()

# The settings every earlier chip number of the repo was taken at
# (bench.py bench_llm and main()).
SERVE = dict(model="llama-1b", num_slots=8, chunk=128, decode_block=16)
PROMPT_LEN, MAX_NEW, TURN_NEW = 128, 64, 32
TRAIN = dict(model="gpt2-774m", batch=8, seq=1024, steps=4, lr=1e-5)


def log(msg: str) -> None:
    print(f"[chip_smoke {time.monotonic() - T0:7.1f}s] {msg}", flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def probe(chips: int) -> dict:
    """Fail fast without an accelerator; the count doubles as the node's
    TPU resource, so rt.init() need not probe again."""
    from ray_tpu.core.runtime import probe_devices

    found = probe_devices()
    log(f"probe: JAX finds {found}")
    require(found["platform"] != "cpu",
            f"JAX finds no accelerator ({found}); this script does not "
            "run on the CPU")
    require(found["count"] >= chips,
            f"need {chips} chip(s), JAX finds {found['count']}")
    return found


def _no_backend_here() -> None:
    backends = sys.modules.get("jax._src.xla_bridge")
    require(backends is None or not backends.backends_are_initialized(),
            "the parent process initialised a JAX backend: it would hold "
            "the chip its workers need")


# --------------------------------------------------------------------------
# Serve phase
# --------------------------------------------------------------------------

def _post(url: str, body: dict, headers=None, timeout: float = 600.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    t0 = time.monotonic()
    with urllib.request.urlopen(req, timeout=timeout) as r:
        raw = r.read()
    return raw, time.monotonic() - t0


def _check_response(out: dict, prompt, max_new: int, vocab: int) -> None:
    require(out.get("finish_reason") == "length", f"finish_reason: {out}")
    require(out.get("prompt_len") == len(prompt), f"prompt_len: {out}")
    toks = out.get("tokens")
    require(isinstance(toks, list) and len(toks) == max_new,
            f"wanted {max_new} tokens, got {toks!r}")
    require(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
            f"token outside [0, {vocab}): {toks}")
    require(out["timing"]["produced_tokens"] == max_new, f"timing: {out}")


def serve_phase(found: dict, seed: int) -> dict:
    import numpy as np

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.core.runtime import get_head_runtime
    from ray_tpu.llm import build_llm_app
    from ray_tpu.models.llama import CONFIGS

    vocab = CONFIGS[SERVE["model"]].vocab_size
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(1, vocab, size=PROMPT_LEN)]
               for _ in range(5)]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    url = f"http://127.0.0.1:{port}/llm"

    t_phase = time.monotonic()
    rt.init(num_cpus=4, resources={"TPU": float(found["count"])})
    try:
        stores = [n.store.backend for n in
                  get_head_runtime().scheduler.nodes()]
        log(f"serve: runtime up; object store {stores} "
            "(arena = native build, segment = Python fallback)")
        require(stores == ["arena"], f"native object store did not load: "
                                     f"{stores}")
        serve.start(http_port=port)
        handle = serve.run(build_llm_app(
            seed=seed, ray_actor_options={"num_tpus": 1}, **SERVE))
        # Resolves once the replica has built its weights, compiled and
        # warmed up: the constructor runs before any method.
        base = rt.get(handle.stats.remote(), timeout=1000)
        log(f"serve: replica ready {time.monotonic() - t_phase:.1f}s after "
            f"rt.init; set-up seconds inside it {base['startup_s']} "
            f"(warmup = compile of both programs); device {base['device']}")

        def ask(prompt, max_new=MAX_NEW, **kw):
            raw, dt = _post(url, {"prompt": prompt, "max_tokens": max_new},
                            **kw)
            out = json.loads(raw)
            _check_response(out, prompt, max_new, vocab)
            return out, dt

        cold, dt = ask(prompts[0])
        log(f"serve: cold request {PROMPT_LEN}+{MAX_NEW} tokens in {dt:.3f}s "
            f"(prefill {cold['timing']['prefill_s']:.3f}s, decode "
            f"{cold['timing']['decode_s']:.3f}s)")
        again, dt = ask(prompts[0])
        require(again["tokens"] == cold["tokens"],
                "the same greedy request gave other tokens the second time")
        log(f"serve: repeated request identical, {dt:.3f}s, matched_tokens "
            f"{again['timing']['matched_tokens']}")

        # Four at once; the first repeats the cold prompt, so continuous
        # batching beside three strangers must not change its tokens.
        outs, errs = {}, []

        def call(i, prompt):
            try:
                outs[i] = ask(prompt)
            except Exception as e:  # noqa: BLE001 — re-raised below
                errs.append(e)

        threads = [threading.Thread(target=call, args=(i, p))
                   for i, p in enumerate([prompts[0]] + prompts[1:4])]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        if errs:
            raise errs[0]
        require(len(outs) == 4, f"{len(outs)}/4 concurrent requests answered")
        require(outs[0][0]["tokens"] == cold["tokens"],
                "tokens changed when the request shared the batch")
        log(f"serve: 4 concurrent requests in {time.monotonic() - t0:.3f}s "
            f"({4 * MAX_NEW} tokens)")

        raw, dt = _post(url, {"prompt": prompts[0], "max_tokens": MAX_NEW,
                              "stream": True})
        streamed = [json.loads(ln) for ln in raw.decode().splitlines() if ln]
        require(streamed == cold["tokens"],
                f"streamed tokens differ from the unstreamed: {streamed}")
        log(f"serve: streamed request, {len(streamed)} token lines, "
            f"{dt:.3f}s")

        sid = {"x-serve-session": f"chip-smoke-{seed}"}
        turn1, dt1 = ask(prompts[4], TURN_NEW, headers=sid)
        follow = [int(t) for t in rng.integers(1, vocab, size=16)]
        turn2, dt2 = ask(prompts[4] + turn1["tokens"] + follow, TURN_NEW,
                         headers=sid)
        matched = turn2["timing"]["matched_tokens"]
        require(matched > 0, f"second session turn matched no prefix: "
                             f"{turn2['timing']}")
        log(f"serve: session turns {dt1:.3f}s / {dt2:.3f}s, second turn "
            f"matched {matched} of {len(prompts[4]) + TURN_NEW + 16} "
            "prompt tokens")

        stats = rt.get(handle.stats.remote(), timeout=60)
        sent = 2 + 4 + 1 + 2
        tokens = (2 + 4 + 1) * MAX_NEW + 2 * TURN_NEW
        for key, want in (("requests_completed", sent),
                          ("tokens_generated", tokens),
                          ("requests_shed", 0)):
            got = stats[key] - base[key]
            require(got == want, f"stats {key}: {got}, sent {want}")
        hits = stats["prefix_hits"] - base["prefix_hits"]
        require(hits >= 4, f"prefix_hits {hits}: the repeat, the batched "
                           "repeat, the stream and turn 2 each hit")
        require(stats["num_slots"] == SERVE["num_slots"]
                and stats["sessions_resident"] == 1, f"stats: {stats}")
        require(stats["device"] == base["device"], "device changed")
        log(f"serve: stats agree — {sent} requests, {tokens} tokens, "
            f"{hits} prefix hits, 0 shed; {stats['steps_block']} fused and "
            f"{stats['steps_decode_only']} decode-only steps, "
            f"{stats['slot_steps_active']} of {stats['slot_steps']} "
            f"slot-steps decoded, {stats['overshoot_tokens']} tokens "
            f"overshot; {stats['kv_pages_read']} KV pages read a layer; "
            f"{stats['prefill_tokens']} prompt tokens through a lane of "
            f"{stats['prefill_lane']}, prefill_lane_fill "
            f"{stats['prefill_lane_fill']:.3f}; "
            f"{stats['gc_pauses']} collections stopped the replica for "
            f"{stats['gc_pause_s']:.3f}s, {stats['compiles']} programs "
            f"built in {stats['compile_s']:.1f}s, "
            f"{stats['compile_cache_hits']} of them from the cache")
        loop = stats["loop"]
        log(f"serve: the engine loop's account — {loop['holes']} holes "
            f"({loop['hole_s']:.3f}s over the typical step)"
            + "".join(f"; {h['stage']} +{h['over_ms']:.0f}ms at step "
                      f"{h['step']}" for h in loop["last_holes"][-4:])
            + "; stages (count, mean us, max ms): " + ", ".join(
                f"{r['handler'][len('rt.llm.'):]} {r['count']} "
                f"{r['mean_us']:.0f} {r['max_ms']:.1f}"
                for r in loop["stages"]))
        return stats["device"]
    finally:
        try:
            serve.shutdown()
        finally:
            rt.shutdown()
            log(f"serve: runtime down, chip released; phase took "
                f"{time.monotonic() - t_phase:.1f}s")


# --------------------------------------------------------------------------
# Train phase
# --------------------------------------------------------------------------

def _gpt2_train(model: str, seq: int, lr: float, mesh):
    """(init, step, cfg): the training cell as bench.py main() builds it —
    bf16, flash attention, mem2 remat, chunked CE, adamw_lowmem over an
    fp32 master — over ``mesh``."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.sharding import prune_rules_for_mesh
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.step import build_sharded_train

    base = gpt2.CONFIGS[model]
    cfg = gpt2.GPT2Config(
        vocab_size=base.vocab_size, max_seq=seq, num_layers=base.num_layers,
        num_heads=base.num_heads, d_model=base.d_model, dtype=jnp.bfloat16,
        attention_impl="flash", remat=True, remat_policy="mem2")
    rules = prune_rules_for_mesh(mesh)
    sinit, sstep, _ = build_sharded_train(
        lambda key: gpt2.init_params(key, cfg),
        lambda p, b: gpt2.loss_fn(p, b, cfg, rules), mesh,
        # A constant rate where bench.py warms up from 0, so that the
        # first steps already move the loss; 1e-5 is the rate at which
        # it fell at every one of them on the chip (1e-4 zigzags).
        optimizer=adamw_lowmem(lr), master_fp32=True)
    return sinit, sstep, cfg


def _run_steps(sinit, sstep, cfg, batch: int, seq: int, steps: int,
               seed: int) -> dict:
    """Initialise, compile ahead of time (so compile seconds stand apart),
    then ``1 + steps`` steps on one seeded batch, each waited for."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.monotonic()
    params, opt_state, step = sinit(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.monotonic() - t0
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1)), jnp.int32)
    data = {"tokens": tokens}
    lowered = sstep.lower(params, opt_state, step, data)
    pallas_calls = lowered.as_text().count("tpu_custom_call")
    t0 = time.monotonic()
    compiled = lowered.compile()
    compile_s = time.monotonic() - t0
    losses, step_s = [], []
    for _ in range(1 + steps):
        t0 = time.monotonic()
        params, opt_state, step, metrics = compiled(
            params, opt_state, step, data)
        jax.block_until_ready(metrics)
        step_s.append(time.monotonic() - t0)
        losses.append(float(metrics["loss"]))
    return {"init_s": init_s, "compile_s": compile_s, "losses": losses,
            "step_s": step_s, "pallas_calls": pallas_calls,
            "state": (params, opt_state)}


def _kernel_vs_reference() -> float:
    """Flash kernel against the plain reference on a small input, on
    whatever device this process holds; max abs error."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_attention, mha_reference

    q, k, v = (jax.random.normal(key, (2, 4, 512, 64), jnp.bfloat16)
               for key in jax.random.split(jax.random.PRNGKey(0), 3))
    return float(jnp.max(jnp.abs(
        flash_attention(q, k, v).astype(jnp.float32)
        - mha_reference(q, k, v).astype(jnp.float32))))


def train_loop(config: dict) -> None:
    """Runs in the train worker, the process that holds the chip."""
    import jax

    from ray_tpu.parallel.mesh import MeshSpec, device_triple
    from ray_tpu.train import session

    device = device_triple()
    kernel_err = _kernel_vs_reference()
    sinit, sstep, cfg = _gpt2_train(config["model"], config["seq"],
                                    config["lr"], MeshSpec().build())
    run = _run_steps(sinit, sstep, cfg, config["batch"], config["seq"],
                     config["steps"], config["seed"])
    del run["state"]  # device arrays stay with the chip's process
    mem = jax.devices()[0].memory_stats() or {}
    session.report({
        **run, "device": device, "kernel_vs_reference_max_err": kernel_err,
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "bytes_limit": mem.get("bytes_limit"),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    })


def _check_losses(losses, vocab: int, what: str) -> None:
    require(all(math.isfinite(x) for x in losses), f"{what}: {losses}")
    require(abs(losses[0] - math.log(vocab)) < 1.0,
            f"{what}: first loss {losses[0]:.3f} is not near ln(vocab) = "
            f"{math.log(vocab):.3f}, as seeded random weights give")
    require(losses[-1] < losses[0], f"{what}: loss did not fall: {losses}")


def train_phase(found: dict, seed: int) -> dict:
    import ray_tpu as rt
    from ray_tpu.models import gpt2
    from ray_tpu.train.config import ScalingConfig
    from ray_tpu.train.trainer import DataParallelTrainer

    t_phase = time.monotonic()
    rt.init(num_cpus=4, resources={"TPU": float(found["count"])})
    try:
        result = DataParallelTrainer(
            train_loop, train_loop_config={**TRAIN, "seed": seed},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
        ).fit()
    finally:
        rt.shutdown()
        log(f"train: runtime down, chip released; phase took "
            f"{time.monotonic() - t_phase:.1f}s")
    require(result.ok, f"trainer failed: {result.error}")
    m = result.metrics
    log(f"train: device {m['device']}; init {m['init_s']:.1f}s, compile "
        f"{m['compile_s']:.1f}s (cache at {m['compile_cache_dir']}); "
        f"steps {[round(s, 3) for s in m['step_s']]} s")
    log(f"train: losses {[round(x, 4) for x in m['losses']]}; "
        f"peak_bytes_in_use {m['peak_bytes_in_use']} of "
        f"{m['bytes_limit']}; flash vs reference max err "
        f"{m['kernel_vs_reference_max_err']:.4f}")
    require(len(m["losses"]) >= 1 + 3, "fewer than 3 steps after the first")
    _check_losses(m["losses"], gpt2.CONFIGS[TRAIN["model"]].vocab_size,
                  "train")
    require(m["kernel_vs_reference_max_err"] < 0.05,
            "flash kernel disagrees with the reference")
    require(m["pallas_calls"] >= 2,
            f"{m['pallas_calls']} Pallas custom calls in the lowered step: "
            "the flash kernel is not in it")
    log(f"train: {m['pallas_calls']} Pallas custom calls in the lowered "
        "step (flash forward + fused backward)")
    return m["device"]


# --------------------------------------------------------------------------
# Four chips: one process drives them all
# --------------------------------------------------------------------------

def _bytes_by_device(tree) -> dict:
    """Bytes of ``tree``'s arrays resident on each device, from their
    shards — what "really spread" means, whatever else is allocated."""
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return out


def _require_spread(by_device: dict, n: int, what: str) -> None:
    total = sum(by_device.values())
    shares = {d: round(b / total, 3) for d, b in sorted(by_device.items())}
    log(f"4chip: {what}: {total / 2**30:.3f} GiB over devices, shares "
        f"{shares}")
    require(len(by_device) == n and all(
        0.8 / n <= s <= 1.25 / n for s in shares.values()),
        f"{what} not spread over {n} devices: {shares}")


def _log_device_memory(what: str) -> None:
    import jax

    used = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()}
    log(f"4chip: bytes_in_use after {what}: {used}")


def four_chip_phase(seed: int) -> dict:
    import jax
    import numpy as np

    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.llm.serve import _build_params
    from ray_tpu.parallel.mesh import MeshSpec, device_triple

    device = device_triple()
    devs = jax.devices()
    log(f"4chip: this process holds {device}; compile cache at "
        f"{jax.config.jax_compilation_cache_dir}")
    require(len(devs) >= 4, f"need 4 devices, have {len(devs)}")

    # (a) the sharded train step against one device.
    runs = {}
    for name, mesh in (("fsdp2xtp2", MeshSpec(fsdp=2, tp=2).build(devs[:4])),
                       ("one-device", MeshSpec().build(devs[:1]))):
        sinit, sstep, cfg = _gpt2_train(TRAIN["model"], TRAIN["seq"],
                                        TRAIN["lr"], mesh)
        run = _run_steps(sinit, sstep, cfg, TRAIN["batch"], TRAIN["seq"],
                         3, seed)
        log(f"4chip: train {name}: init {run['init_s']:.1f}s compile "
            f"{run['compile_s']:.1f}s steps "
            f"{[round(s, 3) for s in run['step_s']]} s, losses "
            f"{[round(x, 4) for x in run['losses']]}, "
            f"{run['pallas_calls']} Pallas calls")
        if mesh.size > 1:
            _log_device_memory("3 sharded train steps")
            _require_spread(_bytes_by_device(run["state"]), 4,
                            "train params + optimizer state")
        _check_losses(run["losses"], cfg.vocab_size, name)
        require(run["pallas_calls"] >= 2, f"{name}: no Pallas calls")
        runs[name] = run["losses"]
        del run, sinit, sstep
        gc.collect()
    # bf16 activations, reduction order differs across the mesh.
    diff = max(abs(a - b) for a, b in
               zip(runs["fsdp2xtp2"], runs["one-device"]))
    log(f"4chip: losses over the mesh agree with one device within "
        f"{diff:.4f}")
    require(diff < 0.05, f"sharded losses differ from one device: {runs}")

    # (b) tp=4 serving against tp=1, bit for bit.
    params, cfg = _build_params(SERVE["model"], seed)
    rng = np.random.default_rng(seed)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size,
                                           size=PROMPT_LEN)]
    tokens = {}
    for tp in (4, 1):
        mesh = MeshSpec(tp=tp).build(devs[:tp]) if tp > 1 else None
        t0 = time.monotonic()
        eng = SlotEngine(params, cfg, num_slots=SERVE["num_slots"],
                         chunk=SERVE["chunk"], seed=seed,
                         decode_block=SERVE["decode_block"], mesh=mesh)
        eng.warmup()
        t1 = time.monotonic()
        h = eng.submit(prompt, max_new=MAX_NEW)
        while not h._done.is_set():
            require(eng.step(), "engine idle with a request unfinished")
        tokens[tp] = h.result(timeout=0).tokens
        log(f"4chip: engine tp={tp}: build + warmup {t1 - t0:.1f}s, "
            f"{PROMPT_LEN}+{MAX_NEW} tokens in {time.monotonic() - t1:.3f}s")
        if tp > 1:
            _log_device_memory(f"serving at tp={tp}")
            _require_spread(_bytes_by_device(eng._params), tp,
                            "serving params")
            _require_spread(_bytes_by_device(eng._cache), tp, "KV pages")
        del eng
        gc.collect()
    require(len(tokens[4]) == MAX_NEW and tokens[4] == tokens[1],
            f"tp=4 tokens differ from tp=1:\n{tokens[4]}\n{tokens[1]}")
    log(f"4chip: tp=4 greedy tokens equal tp=1, all {MAX_NEW}")
    return device


# --------------------------------------------------------------------------

def run(args) -> dict:
    from ray_tpu.core.config import export_compile_cache_dir

    log(f"compile cache: {export_compile_cache_dir()}")
    found = probe(args.chips)
    if args.chips == 4:
        device = four_chip_phase(args.seed)
        require(device["count"] == 4, f"device count: {device}")
    else:
        device = serve_phase(found, args.seed)
        trained_on = train_phase(found, args.seed)
        require(trained_on == device,
                f"the phases ran on different devices: {device} / "
                f"{trained_on}")
        _no_backend_here()
        require(device["count"] == found["count"], f"{device} vs {found}")
    require(device["platform"] != "cpu", f"ran on {device}")
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        device = run(args)
    except Exception as e:  # noqa: BLE001 — reported, then exit 1
        traceback.print_exc()
        sys.stderr.flush()
        log(f"FAILED after {time.monotonic() - T0:.1f}s")
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:400]}),
              flush=True)
        return 1
    log(f"all phases passed in {time.monotonic() - T0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
