"""Benchmark: GPT-2 training MFU + PPO env-steps/s on the local accelerator.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Primary metric (BASELINE.md north star 1): Train-equivalent GPT-2 MFU,
target >=45% — ``vs_baseline`` = measured MFU / 0.45.

Extra keys cover north star 2 (PPO Atari env-steps/s/chip, target 50k):
``ppo_env_steps_per_s`` measures the on-device PPO path (rollout + GAE +
SGD fused into one TPU program, conv policy on Atari-shaped 84x84x4
uint8 frames — see ray_tpu/rllib/ondevice.py; this image has no ALE, so
the env is the synthetic Atari-shaped twin) and ``ppo_vs_target`` =
steps_per_s / 50_000.

Peak FLOPs: TPU v5e chip = 197 TFLOP/s bf16. On non-TPU hosts (driver dry
runs) the script still runs a tiny config and reports, with vs_baseline
computed against the same formula (meaningless off-TPU, but well-formed).
"""

import json
import os
import sys
import time


def percentiles(samples, ps=(50, 99), unit=None):
    """Nearest-rank percentiles of a sample list — THE latency/stat
    helper for every bench section (serve HTTP/handle/mixed, core
    microbench summaries). Returns {"p50": ..., "p99": ...}; keys get
    ``_<unit>`` suffixed when a unit is given."""
    tag = f"_{unit}" if unit else ""
    if not samples:
        return {f"p{p}{tag}": None for p in ps}
    xs = sorted(samples)
    out = {}
    for p in ps:
        k = max(0, min(len(xs) - 1, round(p / 100 * (len(xs) - 1))))
        out[f"p{p}{tag}"] = round(xs[k], 3)
    return out


def median_of_windows(rates):
    """(median, spread) across measurement windows; spread is
    (max-min)/median so a swingy host is visible in the result instead
    of silently biasing it."""
    xs = sorted(rates)
    med = xs[len(xs) // 2]
    return round(med, 1), round((xs[-1] - xs[0]) / max(med, 1e-9), 3)


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ray_tpu.core.config import export_compile_cache_dir

    export_compile_cache_dir()  # before jax is imported
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.optim import adamw_lowmem
    from ray_tpu.train.step import build_sharded_train, default_optimizer

    on_tpu = jax.default_backend() == "tpu"
    n_dev = len(jax.devices())

    if on_tpu:
        # Primary: 774M with full mixed precision (fp32 master + bf16
        # Adam moments + "mem2" remat + chunked CE). The 1.5B north-star
        # config is ALSO measured on this one chip (bench_15b: pure-bf16
        # + Adafactor — Adam-class state doesn't fit 16GB).
        model_name = os.environ.get("BENCH_MODEL", "gpt2-774m")
        batch = int(os.environ.get("BENCH_BATCH", "8"))
        seq = int(os.environ.get("BENCH_SEQ", "1024"))
        steps = int(os.environ.get("BENCH_STEPS", "10"))
        peak_flops_per_chip = 197e12  # v5e bf16
    else:
        model_name = "gpt2-124m"
        batch, seq, steps = 2, 256, 3
        peak_flops_per_chip = 1e12  # nominal; off-TPU numbers are smoke-only

    base_cfg = gpt2.CONFIGS[model_name]
    cfg = gpt2.GPT2Config(
        vocab_size=base_cfg.vocab_size,
        max_seq=seq,
        num_layers=base_cfg.num_layers,
        num_heads=base_cfg.num_heads,
        d_model=base_cfg.d_model,
        dtype=jnp.bfloat16,
        attention_impl=os.environ.get(
            "BENCH_ATTN", "flash" if on_tpu else "reference"),
        remat=True,
        remat_policy=os.environ.get(
            "BENCH_REMAT", "mem2" if on_tpu else "dots_attn"),
    )

    mesh = MeshSpec(dp=n_dev).build()
    init_fn = lambda key: gpt2.init_params(key, cfg)

    def loss_fn(params, batch_):
        return gpt2.loss_fn(params, batch_, cfg)

    # bf16 Adam moments (fp32 math) halve optimizer-state HBM — the
    # difference between 774M fitting one 16GB chip or not.
    if os.environ.get("BENCH_OPT", "lowmem") == "lowmem":
        import optax
        schedule = optax.warmup_cosine_decay_schedule(
            0.0, 1e-4, 100, 1000, end_value=1e-5)
        optimizer = adamw_lowmem(schedule)
    else:
        optimizer = default_optimizer(lr=1e-4, total_steps=1000)

    sinit, sstep, _ = build_sharded_train(
        init_fn, loss_fn, mesh, optimizer=optimizer,
        master_fp32=os.environ.get("BENCH_MASTER", "1") == "1",
    )
    params, opt_state, step = sinit(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq + 1)), jnp.int32
    )
    batch_data = {"tokens": tokens}

    # Warmup (compile) then timed steps.
    for _ in range(2):
        params, opt_state, step, metrics = sstep(
            params, opt_state, step, batch_data
        )
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, step, metrics = sstep(
            params, opt_state, step, batch_data
        )
    jax.block_until_ready(metrics)  # the whole step chain
    elapsed = time.perf_counter() - t0
    final_loss = float(metrics["loss"])

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / elapsed
    flops_token = gpt2.flops_per_token(cfg, seq)
    achieved = tokens_per_sec * flops_token
    mfu = achieved / (peak_flops_per_chip * n_dev)

    result = {
        "metric": f"{model_name} train MFU (batch={batch}, seq={seq}, "
                  f"{'tpu' if on_tpu else 'cpu-smoke'} x{n_dev})",
        "value": round(mfu * 100, 2),
        "unit": "percent_mfu",
        "vs_baseline": round(mfu / 0.45, 4),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "step_time_ms": round(1000 * elapsed / steps, 2),
        "loss": round(final_loss, 4),
    }
    # Free the 774M device state (params + Adam master/moments ≈ 8GB HBM)
    # before the 1.5B and PPO sections — they need the chip to themselves.
    import gc

    del params, opt_state, metrics, tokens, batch_data
    gc.collect()
    if on_tpu:
        try:
            result["gpt2_15b"] = bench_15b()
        except Exception as e:  # 1.5B must never break the 774M line
            result["gpt2_15b_error"] = repr(e)[:300]
        gc.collect()
    try:
        result.update(bench_ppo(on_tpu))
    except Exception as e:  # PPO bench must never break the MFU line
        result["ppo_error"] = repr(e)[:200]
    gc.collect()
    try:
        result["serve_llm"] = bench_llm(on_tpu)
    except Exception as e:  # LLM bench must never break the MFU line
        result["serve_llm_error"] = repr(e)[:300]
    gc.collect()
    try:
        result["llm_sessions"] = bench_llm_sessions(on_tpu)
    except Exception as e:
        result["llm_sessions_error"] = repr(e)[:300]
    gc.collect()
    try:
        result["llm_longgen"] = bench_llm_longgen(on_tpu)
    except Exception as e:
        result["llm_longgen_error"] = repr(e)[:300]
    gc.collect()
    try:
        result["long_context"] = bench_long_context(on_tpu)
    except Exception as e:
        result["long_context_error"] = repr(e)[:300]
    # Host-plane benches (core runtime, serve) run in a FRESH subprocess
    # pinned to the CPU by JAX_PLATFORMS, which its workers inherit: this
    # process holds the chip, and a chip belongs to one process.
    for key, fn_name in (("core_microbench", "bench_core"),
                         ("serve_bench", "bench_serve"),
                         ("serve_mixed", "bench_serve_mixed"),
                         ("serve_chaos", "bench_serve_chaos"),
                         ("llm_drain", "bench_llm_drain"),
                         ("envelope", "bench_envelope"),
                         ("ring_parity", "bench_ring_parity"),
                         ("head_failover", "bench_head_failover")):
        try:
            result[key] = _run_host_bench_subprocess(fn_name)
        except Exception as e:
            result[key + "_error"] = repr(e)[:200]
    print(json.dumps(result))


def _run_host_bench_subprocess(fn_name: str) -> dict:
    import subprocess
    import tempfile

    code = (
        "import json, sys\n"
        "sys.path.insert(0, %r)\n"
        "import bench\n"
        "if __name__ == '__main__':\n"
        "    print('RESULT::' + json.dumps(getattr(bench, %r)()))\n"
        % (os.path.dirname(os.path.abspath(__file__)), fn_name)
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # Virtual 8-device CPU mesh: bench_ring_parity (and any host bench
    # touching jax.sharding) needs more than the 1 real core.
    prev = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in prev:
        env["XLA_FLAGS"] = (
            prev + " --xla_force_host_platform_device_count=8").strip()
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as f:
        f.write(code)
        script = f.name
    try:
        proc = subprocess.run(
            [sys.executable, script], capture_output=True, text=True,
            timeout=900, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    finally:
        try:
            os.unlink(script)
        except OSError:
            pass
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT::"):
            return json.loads(line[len("RESULT::"):])
    raise RuntimeError(
        f"{fn_name} subprocess failed rc={proc.returncode}: "
        f"{proc.stderr[-400:]}")


def bench_core(duration: float = 1.0) -> dict:
    """Core runtime microbenchmarks (reference: ray_perf.py scenarios).
    Host-bound numbers — see scenario names. Ratios (actor-vs-task,
    put-vs-memcpy) come from PAIRED alternating windows inside the
    microbenchmark and are the load-robust figures; absolute rates are
    context only on a contended host."""
    import ray_tpu as rt
    from ray_tpu.scripts.microbenchmark import main as micro_main

    try:
        rows = micro_main(duration=duration)
    finally:
        try:
            rt.shutdown()
        except Exception:
            pass
    out = {}
    for row in rows:
        key = row["name"].replace(" ", "_").replace(":", "_")
        if "GB_per_s" in row:
            # Explicit units: a bare number here was misread as ops/s
            # in round 2 (4.6 *GB/s* looked like 4.6 puts/s).
            out[key + "_GBps"] = row["GB_per_s"]
            out[key + "_ops_per_s"] = row["ops_per_s"]
            if "vs_memcpy" in row:
                out[key + "_vs_memcpy"] = row["vs_memcpy"]
            if "vs_memcpy_spread" in row:
                out[key + "_vs_memcpy_spread"] = row["vs_memcpy_spread"]
        else:
            out[key] = row["ops_per_s"]
        if "window_spread" in row:
            # Median-of-windows measurement (see median_of_windows).
            out[key + "_spread"] = row["window_spread"]
        for extra in ("copies_per_op", "flatten_copies_per_op",
                      "ctx_switches_per_op", "dst"):
            if extra in row:
                out[key + "_" + extra] = row[extra]
    return out


def bench_envelope() -> dict:
    """Scalability envelope, scaled to one box (reference:
    release/benchmarks/README.md envelope — test_many_actors 10k on a
    multi-node cluster, test_many_tasks, test_many_pgs, 1 GiB
    broadcast). Here: 1000 live shared-process actors (multiplexed
    hosts — process-per-actor cannot reach 1k on one core), 100k queued
    tasks drained, 500 placement groups, and a 1 GiB object fetched on
    4 daemon-process nodes over the chunked transfer plane."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu import (NodeAffinitySchedulingStrategy, placement_group,
                         remove_placement_group)
    from ray_tpu.cluster_utils import Cluster

    out = {}
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 4})
    try:
        # ---- 1000 live actors (shared-process hosts)
        @rt.remote(shared_process=True)
        class Hold:
            def ping(self):
                return 1

        n_act = 1000
        t0 = time.perf_counter()
        actors = [Hold.remote() for _ in range(n_act)]
        assert sum(rt.get([a.ping.remote() for a in actors],
                          timeout=900)) == n_act
        dt = time.perf_counter() - t0
        out["many_actors_n"] = n_act
        out["many_actors_create_ping_s"] = round(dt, 1)
        out["many_actors_per_s"] = round(n_act / dt, 1)
        t0 = time.perf_counter()
        rt.get([a.ping.remote() for a in actors], timeout=900)
        out["alive_actor_pings_per_s"] = round(
            n_act / (time.perf_counter() - t0), 1)
        for a in actors:
            rt.kill(a)
        del actors

        # ---- 100k queued tasks drained
        @rt.remote
        def noop():
            return None

        n_tasks = 100_000
        t0 = time.perf_counter()
        refs = [noop.remote() for _ in range(n_tasks)]
        t_submit = time.perf_counter() - t0
        rt.get(refs, timeout=1800)
        t_total = time.perf_counter() - t0
        out["many_tasks_n"] = n_tasks
        out["many_tasks_submit_per_s"] = round(n_tasks / t_submit, 1)
        out["many_tasks_e2e_per_s"] = round(n_tasks / t_total, 1)
        del refs

        # ---- 500 placement groups created + removed
        n_pg = 500
        t0 = time.perf_counter()
        pgs = [placement_group([{"CPU": 0.001}]) for _ in range(n_pg)]
        for pg in pgs:
            assert pg.wait(60)
        t_create = time.perf_counter() - t0
        for pg in pgs:
            remove_placement_group(pg)
        out["many_pgs_n"] = n_pg
        out["many_pgs_create_per_s"] = round(n_pg / t_create, 1)

        # ---- 1 GiB broadcast to 4 daemon-process nodes
        daemons = [cluster.add_node(num_cpus=1, remote=True)
                   for _ in range(4)]
        cluster.wait_for_nodes(timeout=120)
        blob = np.ones((1 << 30,), np.uint8)  # 1 GiB
        ref = rt.put(blob)

        @rt.remote
        def touch(x):
            # Touch every page: len() alone would measure the zero-copy
            # mmap attach, not a real read of the broadcast bytes.
            import numpy as _np

            return int(x[::4096].astype(_np.int64).sum()) + len(x)

        t0 = time.perf_counter()
        fetches = [
            touch.options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    node_id=nid.binary(), soft=False)).remote(ref)
            for nid in daemons
        ]
        sizes = rt.get(fetches, timeout=600)
        dt = time.perf_counter() - t0
        assert all(s == (1 << 30) + (1 << 18) for s in sizes)
        out["broadcast_nodes"] = len(daemons)
        out["broadcast_gib_total"] = len(daemons)
        out["broadcast_aggregate_GBps"] = round(len(daemons) / dt, 2)
    finally:
        cluster.shutdown()
    return out


def bench_15b() -> dict:
    """THE north-star config measured, not just compiled: GPT-2 1.5B
    trains on ONE 16GB v5e chip. Recipe: pure-bf16 params (fp32 params
    would double the weight HBM AND make the layer-scan's backward
    accumulate grads in fp32 — +6GB), Adafactor (factored second moment:
    ~KBs of optimizer state vs Adam's 6.2GB), "mem2" remat, flash
    attention, chunked CE. Measured 49% MFU at batch 4 (target >=45%)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.step import build_sharded_train

    batch = int(os.environ.get("BENCH_15B_BATCH", "4"))
    steps = int(os.environ.get("BENCH_15B_STEPS", "5"))
    base = gpt2.CONFIGS["gpt2-1.5b"]
    cfg = gpt2.GPT2Config(
        vocab_size=base.vocab_size, max_seq=1024,
        num_layers=base.num_layers, num_heads=base.num_heads,
        d_model=base.d_model, dtype=jnp.bfloat16,
        attention_impl="flash", remat=True, remat_policy="mem2",
    )

    def bf16_init(key):
        params, axes = gpt2.init_params(key, cfg)
        params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        return params, axes

    mesh = MeshSpec(dp=1).build()
    sinit, sstep, _ = build_sharded_train(
        bf16_init, lambda p, b: gpt2.loss_fn(p, b, cfg), mesh,
        optimizer=optax.adafactor(learning_rate=1e-4), master_fp32=False)
    params, opt_state, step = sinit(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, 1025)), jnp.int32)
    bd = {"tokens": tokens}
    for _ in range(2):  # compile + warm
        params, opt_state, step, metrics = sstep(params, opt_state, step, bd)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, step, metrics = sstep(params, opt_state, step, bd)
    jax.block_until_ready(metrics)
    dt = (time.perf_counter() - t0) / steps
    loss = float(metrics["loss"])
    tok_s = batch * 1024 / dt
    mfu = tok_s * gpt2.flops_per_token(cfg, 1024) / 197e12
    return {
        "mfu_percent": round(mfu * 100, 2),
        "vs_north_star": round(mfu / 0.45, 4),
        "tokens_per_sec": round(tok_s, 1),
        "step_time_ms": round(dt * 1000, 2),
        "loss": round(loss, 4),
        "detail": f"1.5B bf16+adafactor, batch={batch}, seq=1024, "
                  f"mem2 remat, flash attn, ONE v5e chip",
    }


def bench_serve(smoke: bool = False) -> dict:
    """Serve noop HTTP req/s, 1 and 8 replicas (reference baselines:
    serve/benchmarks ~629 req/s 1 replica / ~1918 req/s 8 replicas —
    measured there on a multi-core dev box; this host has ONE core).
    Ceiling data for this box: raw asyncio HTTP echo ~13.6k req/s; one
    warmed 1:1 actor round trip ~3k/s. The serve path beats the
    8-replica reference number on one core because the proxy COALESCES
    concurrent requests into batched replica RPCs (one actor hop per
    batch) and sticky-with-slack routing keeps bursts on a hot replica
    instead of bouncing worker processes.

    The 8-vs-1 direct-handle ratio is measured with PAIRED alternating
    windows against both deployments live at once — sequential sections
    minutes apart are incomparable under external load (that artifact
    was the r5 "inversion" signal's noise floor)."""
    import http.client

    import ray_tpu as rt
    from ray_tpu import serve

    # Explicit logical CPUs (see microbenchmark.main): auto-sizing gives
    # 1 CPU on single-core bench hosts, starving the controller +
    # replica actors of scheduling headroom. Not more than 4: the pool
    # PRESTARTS num_cpus worker processes, and a 1-core host thrashes
    # spawning 16 python interpreters at once.
    rt.init(ignore_reinit_error=True, num_cpus=4)
    serve.start(http_port=18199)
    out = {}
    handles = {}

    def measure(tag, n_replicas, n_clients, duration=6.0,
                http_windows=3):
        import threading

        @serve.deployment(name=f"noop{n_replicas}",
                          num_replicas=n_replicas,
                          max_concurrent_queries=100)
        def noop(payload=None):
            return "ok"

        handle = serve.run(noop.bind())
        handles[n_replicas] = handle
        # Warm EVERY replica to STEADY STATE, not just "touched": a
        # spawned replica interpreter keeps importing/JIT-specializing
        # for seconds after its first reply, and with 8 replicas that
        # background churn saturates the single core straight through
        # the timed windows (r4's 8-replica numbers were depressed ~3x
        # by exactly this). Direct per-replica calls force each worker
        # through init AND the CPython specialization ramp.
        from ray_tpu.serve.api import _controller

        deadline = time.perf_counter() + 120
        replicas = []
        while time.perf_counter() < deadline:
            # Fresh controller snapshot each poll — the router's local
            # set only grows via its long-poll listener and its
            # _ensure_replicas early-returns once non-empty.
            replicas = rt.get(
                _controller().get_replica_snapshot.remote(
                    f"noop{n_replicas}"), timeout=30)[1]
            if len(replicas) >= n_replicas:
                break
            time.sleep(0.5)
        for r in replicas:
            for _ in range(3):
                rt.get([r.handle_request.remote((), {})
                        for _ in range(100)], timeout=120)
        path = f"/noop{n_replicas}"
        # Warm the HTTP path too: the proxy's first requests pay
        # one-time costs (handle/router bootstrap, controller name
        # lookup, long-poll listener start) that don't belong in the
        # steady-state window.
        warm = http.client.HTTPConnection("127.0.0.1", 18199, timeout=30)
        for _ in range(100):
            warm.request("GET", path)
            warm.getresponse().read()
        warm.close()

        def run_window(window_s: float) -> float:
            counts = [0] * n_clients
            stop_box = [0.0]

            def client(i):
                # Persistent connection (keep-alive), like the
                # reference bench's HTTP client — a new TCP connection
                # per request (urllib.request) benchmarks the kernel's
                # connect path, not the proxy.
                conn = http.client.HTTPConnection("127.0.0.1", 18199,
                                                  timeout=30)
                try:
                    while time.perf_counter() < stop_box[0]:
                        conn.request("GET", path)
                        resp = conn.getresponse()
                        resp.read()
                        # http.client never raises on status (urllib
                        # did): without this, a broken instance
                        # returning fast 500s would inflate req/s.
                        assert resp.status == 200, f"HTTP {resp.status}"
                        counts[i] += 1
                finally:
                    conn.close()

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_clients)]
            t0 = time.perf_counter()
            stop_box[0] = t0 + window_s
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return sum(counts) / (time.perf_counter() - t0)

        # Median of windows: single short windows land on the
        # interpreter/scheduler warmup ramp and under-report steady
        # state by ~30% on 1-core hosts.
        out[tag], out[tag + "_spread"] = median_of_windows(
            [run_window(duration) for _ in range(http_windows)])
        # python-handle path (no HTTP parse) for comparison
        t0 = time.perf_counter()
        m = 0
        while time.perf_counter() - t0 < duration:
            rt.get([handle.remote() for _ in range(20)], timeout=30)
            m += 20
        out[tag + "_handle_async"] = round(m / (time.perf_counter() - t0), 1)

    def handle_window(handle, window_s: float, lat_ms=None):
        """One direct-handle window: bursts of 20, returns req/s."""
        t0 = time.perf_counter()
        m = 0
        while time.perf_counter() - t0 < window_s:
            b0 = time.perf_counter()
            rt.get([handle.remote() for _ in range(20)], timeout=30)
            if lat_ms is not None:
                lat_ms.append((time.perf_counter() - b0) * 1000 / 20)
            m += 20
        return m / (time.perf_counter() - t0)

    try:
        if smoke:
            measure("serve_http_reqs_per_s_1_replica", 1, 1,
                    duration=1.5, http_windows=1)
            out["vs_ref_1_replica"] = round(
                out["serve_http_reqs_per_s_1_replica"] / 629.0, 3)
            return out
        measure("serve_http_reqs_per_s_1_replica", 1, 1)
        measure("serve_http_reqs_per_s_8_replicas", 8, 8)
        out["vs_ref_1_replica"] = round(
            out["serve_http_reqs_per_s_1_replica"] / 629.0, 3)
        out["vs_ref_8_replicas"] = round(
            out["serve_http_reqs_per_s_8_replicas"] / 1918.0, 3)
        # Replica-linear check: PAIRED alternating handle windows with
        # noop1 (1 replica) and noop8 (8 replicas) both deployed and
        # warm. ratio >= 1.0 means adding replicas does not invert the
        # direct-handle path.
        h1, h8 = handles[1], handles[8]
        for _ in range(5):  # rewarm noop1 after the 8-replica section
            handle_window(h1, 0.2)
        rates1, rates8, ratios = [], [], []
        lat1, lat8 = [], []
        for _ in range(5):
            r1 = handle_window(h1, 0.6, lat1)
            r8 = handle_window(h8, 0.6, lat8)
            rates1.append(r1)
            rates8.append(r8)
            ratios.append(r8 / max(r1, 1e-9))
        out["handle_async_1_replica"], out["handle_async_1_spread"] = \
            median_of_windows(rates1)
        out["handle_async_8_replicas"], out["handle_async_8_spread"] = \
            median_of_windows(rates8)
        out["handle_async_8v1_ratio"] = round(
            sorted(ratios)[len(ratios) // 2], 3)
        out["handle_async_8v1_ratio_spread"] = median_of_windows(ratios)[1]
        out.update({"handle_1_" + k: v for k, v in
                    percentiles(lat1, unit="ms").items()})
        out.update({"handle_8_" + k: v for k, v in
                    percentiles(lat8, unit="ms").items()})
    finally:
        serve.shutdown()
    return out


def bench_serve_mixed(smoke: bool = False) -> dict:
    """Sustained MIXED workload against autoscaled replicas: concurrent
    HTTP + direct-handle + streaming-token traffic for one shared
    deployment set, with p50/p99 latency per traffic class — the
    end-to-end proof that the hot-path fixes (actor-call fast path,
    replica-linear router) compose under production-shaped load, not
    just in per-path microbenches."""
    import http.client
    import threading

    import ray_tpu as rt
    from ray_tpu import serve

    rt.init(ignore_reinit_error=True, num_cpus=4)
    port = 18227
    serve.start(http_port=port)
    duration = 3.0 if smoke else 10.0
    max_replicas = 2 if smoke else 4
    n_http = 1 if smoke else 2
    n_handle = 1 if smoke else 2
    out = {"duration_s": duration, "max_replicas": max_replicas}

    @serve.deployment(name="mix", max_concurrent_queries=100,
                      autoscaling_config={
                          "min_replicas": 1,
                          "max_replicas": max_replicas,
                          "target_num_ongoing_requests_per_replica": 8.0,
                          "upscale_delay_s": 0.5,
                      })
    async def mix(payload=None):
        return {"ok": True}

    @serve.deployment(name="mixstream", num_replicas=1,
                      max_concurrent_queries=32)
    def mixstream(n=16):
        def gen():
            for i in range(int(n) if not isinstance(n, dict) else 16):
                yield {"token": i}
        return gen()

    try:
        handle = serve.run(mix.bind())
        stream_handle = serve.run(mixstream.bind())
        # Warm every class once before the timed phase.
        rt.get(handle.remote(), timeout=60)
        list(stream_handle.stream(4))
        warm = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        for _ in range(20):
            warm.request("GET", "/mix")
            warm.getresponse().read()
        warm.close()

        stop = [0.0]
        errors = []
        counts = {"http": 0, "handle": 0, "stream_tokens": 0,
                  "stream_reqs": 0}
        lats = {"http": [], "handle": [], "stream_first": []}
        lock = threading.Lock()

        def http_client(i):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=30)
            try:
                n, ls = 0, []
                while time.perf_counter() < stop[0]:
                    t0 = time.perf_counter()
                    conn.request("GET", "/mix")
                    resp = conn.getresponse()
                    resp.read()
                    if resp.status != 200:
                        raise RuntimeError(f"HTTP {resp.status}")
                    ls.append((time.perf_counter() - t0) * 1000)
                    n += 1
                with lock:
                    counts["http"] += n
                    lats["http"].extend(ls)
            except Exception as e:  # noqa: BLE001
                errors.append(f"http: {e!r}")
            finally:
                conn.close()

        def handle_client(i):
            try:
                n, ls = 0, []
                while time.perf_counter() < stop[0]:
                    t0 = time.perf_counter()
                    rt.get(handle.remote(), timeout=30)
                    ls.append((time.perf_counter() - t0) * 1000)
                    n += 1
                with lock:
                    counts["handle"] += n
                    lats["handle"].extend(ls)
            except Exception as e:  # noqa: BLE001
                errors.append(f"handle: {e!r}")

        def stream_client():
            try:
                toks = reqs = 0
                firsts = []
                while time.perf_counter() < stop[0]:
                    t0 = time.perf_counter()
                    first = None
                    for _chunk in stream_handle.stream(16):
                        if first is None:
                            first = (time.perf_counter() - t0) * 1000
                        toks += 1
                    firsts.append(first if first is not None else 0.0)
                    reqs += 1
                with lock:
                    counts["stream_tokens"] += toks
                    counts["stream_reqs"] += reqs
                    lats["stream_first"].extend(firsts)
            except Exception as e:  # noqa: BLE001
                errors.append(f"stream: {e!r}")

        threads = ([threading.Thread(target=http_client, args=(i,))
                    for i in range(n_http)]
                   + [threading.Thread(target=handle_client, args=(i,))
                      for i in range(n_handle)]
                   + [threading.Thread(target=stream_client)])
        t0 = time.perf_counter()
        stop[0] = t0 + duration
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        out["http_reqs_per_s"] = round(counts["http"] / elapsed, 1)
        out["handle_reqs_per_s"] = round(counts["handle"] / elapsed, 1)
        out["stream_tokens_per_s"] = round(
            counts["stream_tokens"] / elapsed, 1)
        out["stream_reqs_per_s"] = round(counts["stream_reqs"] / elapsed, 2)
        out.update({"http_" + k: v for k, v in
                    percentiles(lats["http"], unit="ms").items()})
        out.update({"handle_" + k: v for k, v in
                    percentiles(lats["handle"], unit="ms").items()})
        out.update({"stream_first_chunk_" + k: v for k, v in
                    percentiles(lats["stream_first"], unit="ms").items()})
        if errors:
            out["errors"] = errors[:5]
        # Autoscaling actually engaged?
        try:
            out["mix_replicas_final"] = serve.list_deployments()[
                "mix"]["num_replicas"]
        except Exception:
            pass
    finally:
        serve.shutdown()
    return out


def bench_serve_chaos(smoke: bool = False) -> dict:
    """Chaos stage (fault tolerance): sustained HTTP + handle traffic
    against a replicated deployment while a ReplicaKiller SIGKILLs
    replica workers mid-wave. The contract under fire: every request
    ends as a success, a typed 503, or a typed deadline error — never a
    hang and never a raw 500. Reports replacement latency (SIGKILL ->
    controller evicts the corpse and reconciliation brings a fresh
    replica up) and the p99 of requests completing during kill windows.
    Full mode also SIGKILLs a daemon node mid-traffic."""
    import http.client
    import socket
    import threading

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.cluster_utils import ReplicaKiller
    from ray_tpu.core import runtime as runtime_mod
    from ray_tpu.core.exceptions import (DeadlineExceededError,
                                         GetTimeoutError, OverloadedError,
                                         TaskError)

    rt.init(ignore_reinit_error=True, num_cpus=4)
    port = 18241
    serve.start(http_port=port)
    fast = smoke and os.environ.get("BENCH_SMOKE_FAST") == "1"
    n_replicas = 2 if smoke else 3
    kills_planned = 1 if smoke else 3
    n_http = 1 if smoke else 2
    n_handle = 1 if smoke else 2
    out = {"replicas": n_replicas, "kills_planned": kills_planned}

    @serve.deployment(name="chaos", num_replicas=n_replicas,
                      max_concurrent_queries=32, max_pending=256,
                      queue_timeout_s=5.0, request_deadline_s=10.0,
                      health_check_period_s=0.25,
                      health_check_timeout_s=1.0,
                      health_check_failure_threshold=2)
    async def chaos(payload=None):
        import asyncio

        await asyncio.sleep(0.002)
        return {"ok": True}

    counts = {"ok": 0, "typed_503": 0, "deadline": 0, "raw_500": 0,
              "other": 0, "hung": 0}
    lats_ms = []
    during_ms = []
    kill_window = [False]
    stop = [time.perf_counter() + 120.0]
    lock = threading.Lock()

    def note(kind, t0=None):
        with lock:
            counts[kind] += 1
            if t0 is not None:
                ms = (time.perf_counter() - t0) * 1000
                lats_ms.append(ms)
                if kill_window[0]:
                    during_ms.append(ms)

    def http_client(i):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            while time.perf_counter() < stop[0]:
                t0 = time.perf_counter()
                try:
                    conn.request("GET", "/chaos")
                    resp = conn.getresponse()
                    body = resp.read()
                except socket.timeout:
                    note("hung")
                    break
                except Exception:  # conn dropped: reconnect, count it
                    note("other")
                    conn.close()
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", port, timeout=30)
                    continue
                if resp.status == 200:
                    note("ok", t0)
                elif resp.status == 503 and b"overloaded" in body:
                    note("typed_503", t0)
                elif resp.status == 504 and b"deadline" in body:
                    note("deadline", t0)
                elif resp.status >= 500:
                    note("raw_500")
                else:
                    note("other")
        finally:
            conn.close()

    def handle_client(i, handle):
        while time.perf_counter() < stop[0]:
            t0 = time.perf_counter()
            try:
                rt.get(handle.remote(), timeout=30)
                note("ok", t0)
            except GetTimeoutError:
                note("hung")
                break
            except Exception as e:  # noqa: BLE001
                root = e
                while isinstance(root, TaskError) and root.cause is not None:
                    root = root.cause
                if isinstance(root, OverloadedError):
                    note("typed_503", t0)
                elif isinstance(root, DeadlineExceededError):
                    note("deadline", t0)
                else:
                    note("other")

    replaced_ms = []
    notes = []
    try:
        handle = serve.run(chaos.bind())
        rt.get(handle.remote(), timeout=60)
        warm = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        for _ in range(5):
            warm.request("GET", "/chaos")
            warm.getresponse().read()
        warm.close()

        threads = ([threading.Thread(target=http_client, args=(i,))
                    for i in range(n_http)]
                   + [threading.Thread(target=handle_client,
                                       args=(i, handle))
                      for i in range(n_handle)])
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(0.3 if fast else 0.6)  # traffic established

        killer = ReplicaKiller("chaos")
        for _k in range(kills_planned):
            t_kill = time.perf_counter()
            victim = killer.kill_one()
            if victim is None:
                notes.append("no killable replica")
                continue
            kill_window[0] = True
            # Replacement = corpse evicted AND target count restored
            # with live worker pids (health sweep + reconciliation).
            while time.perf_counter() - t_kill < 30.0:
                pids = killer.replica_pids()
                if victim not in pids and len(pids) >= n_replicas:
                    replaced_ms.append(
                        (time.perf_counter() - t_kill) * 1000)
                    break
                time.sleep(0.01)
            else:
                notes.append("replacement timed out (30s)")
            kill_window[0] = False
            time.sleep(0.2 if fast else 0.4)

        if not smoke:
            # Daemon-death phase: SIGKILL a remote-node daemon process
            # mid-traffic; serve traffic on head-local replicas must be
            # unaffected and the runtime must absorb the node loss.
            try:
                runtime = runtime_mod.get_head_runtime()
                node_id = runtime.add_node({"CPU": 1.0}, remote=True)
                time.sleep(0.5)
                node = runtime.scheduler.get_node(node_id)
                if node is not None and getattr(node, "is_remote", False):
                    node.process.kill()
                    out["daemon_killed"] = True
                    time.sleep(1.0)
                else:
                    notes.append("daemon node not remote; skipped")
            except Exception as e:  # noqa: BLE001
                notes.append(f"daemon phase skipped: {e!r}"[:200])

        stop[0] = time.perf_counter() + (0.3 if fast else 0.6)  # tail
        for t in threads:
            t.join(timeout=45)
        with lock:
            counts["hung"] += sum(1 for t in threads if t.is_alive())
        elapsed = time.perf_counter() - t0
        out["duration_s"] = round(elapsed, 2)
        out["kills"] = len(killer.killed)
        out["counts"] = dict(counts)
        pr = percentiles(replaced_ms)
        out["replaced_ms_p50"] = pr["p50"]
        out["replaced_ms_p99"] = pr["p99"]
        out["during_kill_p99_ms"] = (percentiles(during_ms)["p99"]
                                     if during_ms else 0.0)
        out.update({"req_" + k: v for k, v in
                    percentiles(lats_ms, unit="ms").items()})
        if notes:
            out["notes"] = notes[:5]
    finally:
        serve.shutdown()
    return out


def bench_llm_drain(smoke: bool = False) -> dict:
    """Stateful-session robustness stage (ISSUE 19): multi-turn chat
    sessions — greedy AND seeded sampling — against a replicated LLM
    deployment, then (a) DRAIN the replica hosting them mid-traffic
    (sessions migrate via KV page export/import, in-flight generations
    finish), and (b) SIGKILL the replica hosting a session while its
    generation is in flight (safe retry completes it elsewhere; the
    next turn re-pins and recovers by re-prefilling the head-side
    transcript log). The contract: zero raw 500s, zero hung requests,
    zero drain-caused 503s, and every post-drain/post-crash turn
    bit-for-bit identical to an undisturbed reference conversation.
    Commits migration latency p50/p99 and recovery-by-re-prefill
    latency p50/p99."""
    import os as _os
    import signal as _signal
    import threading
    import urllib.error
    import urllib.request

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.cluster_utils import ReplicaKiller
    from ray_tpu.llm.serve import build_llm_app
    from ray_tpu.serve.api import _controller

    rt.init(ignore_reinit_error=True, num_cpus=4)
    port = 18251
    serve.start(http_port=port)
    fast = smoke and os.environ.get("BENCH_SMOKE_FAST") == "1"
    name = "llmdrain"
    n_replicas = 2
    n_filler = 0 if fast else (1 if smoke else 4)
    kills_planned = 1 if smoke else 2
    counts = {"ok": 0, "typed_503": 0, "deadline": 0, "raw_500": 0,
              "other": 0, "hung": 0}
    in_drain = [False]
    drain_503 = [0]
    lock = threading.Lock()
    url = f"http://127.0.0.1:{port}/{name}"

    def turn(sid, prompt, temperature=0.0, seed=None, max_new=4,
             timeout=120.0):
        """One conversation turn over HTTP with the sticky-session
        header; classifies the outcome and returns the token list (or
        None on a non-200)."""
        body = {"prompt": list(prompt), "max_tokens": max_new,
                "temperature": temperature}
        if seed is not None:
            body["seed"] = seed
        req = urllib.request.Request(
            url, data=json.dumps(body).encode(),
            headers={"content-type": "application/json",
                     "x-serve-session": sid})
        try:
            resp = json.loads(urllib.request.urlopen(
                req, timeout=timeout).read())
            with lock:
                counts["ok"] += 1
            return resp.get("tokens")
        except urllib.error.HTTPError as e:
            body = e.read()
            with lock:
                if e.code == 503 and b"overloaded" in body:
                    counts["typed_503"] += 1
                    if in_drain[0]:
                        drain_503[0] += 1
                elif e.code == 504:
                    counts["deadline"] += 1
                elif e.code >= 500:
                    counts["raw_500"] += 1
                else:
                    counts["other"] += 1
        except TimeoutError:
            with lock:
                counts["hung"] += 1
        except Exception:  # noqa: BLE001 — dropped conn etc.
            with lock:
                counts["other"] += 1
        return None

    # Conversation shape (llama-tiny max_seq=128): shared 24-token
    # system prompt + 1-token user turns, 4 new tokens per turn, 4
    # turns -> the final prompt stays well inside the budget.
    sysp = list(range(2, 26))
    n_turns = 4
    modes = [("greedy", 0.0, None), ("seeded", 1.0, 77)]

    def converse(sid, temperature, seed, hooks=None):
        """Run the canonical conversation; ``hooks[t]`` (if set) runs
        BEFORE turn t. Returns per-turn token lists."""
        hist = list(sysp)
        turns = []
        for t in range(n_turns):
            if hooks and t in hooks:
                hooks[t]()
            toks = turn(sid, hist + [30 + t], temperature, seed)
            turns.append(toks)
            hist = hist + [30 + t] + (toks or [])
        return turns

    def replica_sessions():
        """actor-hex -> resident session ids, per live replica."""
        reps = rt.get(_controller().get_replicas.remote(name),
                      timeout=15)
        out = {}
        for r in reps:
            try:
                out[r._actor_id.hex()] = rt.get(
                    r.call_method.remote("sessions", (), {}),
                    timeout=15)
            except Exception:  # noqa: BLE001 — replica mid-replacement
                out[r._actor_id.hex()] = []
        return out

    out = {"replicas": n_replicas, "turns": n_turns,
           "kills_planned": kills_planned}
    migrate_ms = []
    recovery_ms = []
    parity = {m: True for m, _, _ in modes}
    bg_stop = threading.Event()

    def bg_traffic():
        # Live multi-session traffic riding through both chaos phases:
        # its own sticky session, pinned wherever the hash lands — so
        # drains and kills always happen UNDER load.
        hist = list(sysp)
        i = 0
        while not bg_stop.is_set():
            toks = turn("bg-keep", hist + [60 + (i % 40)], 0.0, None)
            if toks:
                hist = list(sysp)  # keep the prompt bounded
            i += 1
            bg_stop.wait(0.05)

    try:
        app = build_llm_app(
            model="llama-tiny", num_slots=4, chunk=8, page_size=8,
            seed=0, name=name, num_replicas=n_replicas,
            health_check_period_s=0.25, health_check_timeout_s=1.0,
            health_check_failure_threshold=2)
        serve.run(app)
        turn("warm", sysp, timeout=180.0)  # replicas compiled + routable

        # Reference pass: undisturbed conversations, one per sampling
        # mode — the parity baseline every chaos-phase turn must match.
        ref = {m: converse("ref-" + m, tp, sd)
               for m, tp, sd in modes}
        for m, _, _ in modes:
            if any(t is None for t in ref[m]):
                raise RuntimeError(f"reference pass failed: {ref[m]}")

        bg = threading.Thread(target=bg_traffic, daemon=True)
        bg.start()

        # -- Phase A: graceful drain between turns 2 and 3 ---------------
        # Filler sessions fatten the victim's resident set so the
        # migration latency sample is more than a single page batch.
        for i in range(n_filler):
            converse(f"fill-{i}", 0.0, None)
        mig = {}
        overlap_box = {}

        def drain_now():
            sess = replica_sessions()
            victim = max(sess, key=lambda h: sum(
                1 for s in sess[h] if s.startswith(("mig-", "fill-"))))
            # Overlapped generation: fired at the drain instant, in
            # flight ON the deployment while the victim quiesces — must
            # complete, never 503/sever.
            ov = threading.Thread(target=lambda: overlap_box.update(
                r=turn("overlap", sysp + [40], 0.0, None, max_new=16)))
            in_drain[0] = True
            ov.start()
            rep = serve.drain(name, replica=victim, timeout_s=60.0)
            in_drain[0] = False
            ov.join(timeout=120)
            out["drain"] = {k: rep.get(k) for k in
                            ("sessions_migrated", "migrate_errors",
                             "timed_out", "drained_ms", "error")}
            migrate_ms.extend(rep.get("migrate_ms") or [])

        hooks = {2: drain_now}
        for m, tp, sd in modes:
            mig[m] = converse("mig-" + m, tp, sd, hooks=hooks)
            hooks = None  # drain once, on the first mode's turn 3
        for m, _, _ in modes:
            parity[m] = parity[m] and mig[m] == ref[m]
        if overlap_box.get("r") is None:
            counts["other"] += 1  # overlapped turn must have completed

        # -- Phase B: SIGKILL mid-generation + re-prefill recovery -------
        killer = ReplicaKiller(name, seed=0)
        kills_done = 0
        crash = {m: [] for m, _, _ in modes}
        hists = {m: list(sysp) for m, _, _ in modes}
        for m, tp, sd in modes:
            for t in range(2):
                toks = turn("cr-" + m, hists[m] + [30 + t], tp, sd)
                crash[m].append(toks)
                hists[m] += [30 + t] + (toks or [])
        for _k in range(kills_planned):
            sess = replica_sessions()
            pids = killer.replica_pids()
            victim_hex = max(sess, key=lambda h: sum(
                1 for s in sess[h] if s.startswith("cr-")))
            victim_bin = bytes.fromhex(victim_hex)
            if victim_bin not in pids:
                out.setdefault("notes", []).append(
                    "crash victim had no live pid")
                continue
            if _k == 0:
                # Turn 3 in flight on the victim when the SIGKILL
                # lands: safe retry must finish it on a survivor,
                # bit-for-bit (client-pinned seed).
                boxes = {}
                ths = []
                for m, tp, sd in modes:
                    th = threading.Thread(
                        target=lambda m=m, tp=tp, sd=sd: boxes.update(
                            {m: turn("cr-" + m, hists[m] + [32], tp,
                                     sd)}))
                    th.start()
                    ths.append(th)
                time.sleep(0.1)
            t_kill = time.perf_counter()
            _os.kill(pids[victim_bin], _signal.SIGKILL)
            kills_done += 1
            if _k == 0:
                for th in ths:
                    th.join(timeout=120)
                for m, _, _ in modes:
                    crash[m].append(boxes.get(m))
                    hists[m] += [32] + (boxes.get(m) or [])
            # Replacement: corpse evicted + target count restored.
            while time.perf_counter() - t_kill < 30.0:
                pids_now = killer.replica_pids()
                if (victim_bin not in pids_now
                        and len(pids_now) >= n_replicas):
                    break
                time.sleep(0.05)
            time.sleep(0.5)  # router long-poll settles on the new set
        # Turn 4: the crashed sessions re-pin and recover via the
        # head-side transcript re-prefill — continuation stays exact.
        for m, tp, sd in modes:
            toks = turn("cr-" + m, hists[m] + [33], tp, sd)
            crash[m].append(toks)
        for m, _, _ in modes:
            parity[m] = parity[m] and crash[m] == ref[m]

        bg_stop.set()
        bg.join(timeout=30)
        for st in (rt.get(r.call_method.remote("stats", (), {}),
                          timeout=15)
                   for r in rt.get(
                       _controller().get_replicas.remote(name),
                       timeout=15)):
            recovery_ms.extend(st.get("session_recovery_ms") or [])
        out["kills"] = kills_done + len(killer.killed)
        out["counts"] = dict(counts)
        out["drain_503"] = drain_503[0]
        out["parity_greedy"] = parity["greedy"]
        out["parity_seeded"] = parity["seeded"]
        out.update({"migrate_ms_" + k: v for k, v in
                    percentiles(migrate_ms).items()})
        out.update({"recovery_ms_" + k: v for k, v in
                    percentiles(recovery_ms).items()})
        out["recovery_samples"] = len(recovery_ms)
        out["detail"] = (
            "llama-tiny 2-replica serve app; per-mode (greedy + "
            "seeded) 4-turn sessions; drain migrates resident "
            "sessions' KV pages between turns under live traffic; "
            "SIGKILL mid-generation exercises safe retry + transcript "
            "re-prefill re-pin; parity = chaos turns identical to an "
            "undisturbed reference conversation")
    finally:
        bg_stop.set()
        serve.shutdown()
    return out


def bench_llm(on_tpu: bool) -> dict:
    """On-TPU LLM serving: continuous-batching tokens/s + req/s at
    concurrency 1/4/8 (VERDICT r4 item 1). Engine-level measurement in
    THIS process — the one TPU chip is already attached here, and a
    Serve replica subprocess cannot attach it concurrently; the HTTP
    replica path is proven separately (tests/test_serve_llm.py). The
    reference has no on-device serving loop to compare against, so the
    numbers are absolute."""
    import gc

    import jax
    import numpy as np

    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import llama

    if on_tpu:
        model, slots, chunk = "llama-1b", 8, 128
        prompt_len, max_new = 128, 128
        block = int(os.environ.get("BENCH_LLM_BLOCK", "16"))
    else:
        model, slots, chunk = "llama-tiny", 8, 8
        prompt_len, max_new = 8, 8
        block = 4
    cfg = llama.CONFIGS[model]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda x: x.astype(cfg.dtype), params)
    engine = SlotEngine(params, cfg, num_slots=slots, chunk=chunk,
                        decode_block=block)
    engine.warmup()  # compiles prefill + decode programs
    rng = np.random.default_rng(0)
    out = {}
    for conc in (1, 4, 8):
        handles = [
            engine.submit(
                rng.integers(1, cfg.vocab_size, size=prompt_len).tolist(),
                max_new=max_new)
            for _ in range(conc)
        ]
        t0 = time.perf_counter()
        while engine.step():
            pass
        dt = time.perf_counter() - t0
        assert all(h.result(timeout=0).finish_reason == "length"
                   for h in handles)
        out[f"tokens_per_s_c{conc}"] = round(conc * max_new / dt, 1)
        out[f"req_per_s_c{conc}"] = round(conc / dt, 3)
    # Sustained load: a queue deeper than the slot pool, so continuous
    # batching runs at steady state (requests join freed slots
    # mid-flight) — the scenario slot engines exist for. The cN numbers
    # above are burst latency-bound (ramp + prefill dominate 128-token
    # generations); this is the serving-throughput figure.
    n_req = 4 * slots
    handles = [
        engine.submit(
            rng.integers(1, cfg.vocab_size, size=prompt_len).tolist(),
            max_new=max_new)
        for _ in range(n_req)
    ]
    t0 = time.perf_counter()
    while engine.step():
        pass
    dt = time.perf_counter() - t0
    assert all(h.result(timeout=0).finish_reason == "length"
               for h in handles)
    out["tokens_per_s_sustained"] = round(n_req * max_new / dt, 1)
    out["req_per_s_sustained"] = round(n_req / dt, 3)
    out["sustained_requests"] = n_req
    # Long generations (chat-length outputs): decode blocks dominate
    # and per-request prefill amortizes away — the decode loop's
    # steady-state throughput. (Each prefill costs a full params read,
    # so short 128-token generations pay ~50% prefill overhead.)
    if on_tpu:
        long_new, n_long = 512, 16
        handles = [
            engine.submit(
                rng.integers(1, cfg.vocab_size,
                             size=prompt_len).tolist(),
                max_new=long_new)
            for _ in range(n_long)
        ]
        t0 = time.perf_counter()
        while engine.step():
            pass
        dt = time.perf_counter() - t0
        assert all(h.result(timeout=0).finish_reason == "length"
                   for h in handles)
        out["tokens_per_s_long"] = round(n_long * long_new / dt, 1)
        out["long_new_tokens"] = long_new
    out["detail"] = (
        f"{model} slot-engine, {slots} KV slots, prefill chunk {chunk}, "
        f"decode block {block}, prompt {prompt_len} + {max_new} new "
        "tokens, greedy; end-to-end incl. chunked prefill; sustained = "
        f"{n_req} queued requests through {slots} slots")
    del engine, params
    gc.collect()
    return out


def bench_llm_longgen(on_tpu: bool, smoke: bool = False) -> dict:
    """Long-generation decode throughput. All slots prefill up front,
    then the engine sits in the pure ``decode_only_fn`` loop for the
    whole generation. Commits tok/s, the decode block size and what the
    engine's own step counters say the steps were made of (how far a
    step is from the device's limits is a device-trace question:
    ``benchmark/``). A tp2 parity sub-stage reruns a short
    greedy generation on a 2-device tp mesh and asserts bit-for-bit
    token parity vs tp1; skipped cleanly when the host only has one
    device."""
    import gc

    import jax
    import numpy as np

    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import llama

    fast = smoke and os.environ.get("BENCH_SMOKE_FAST") == "1"
    if on_tpu:
        model, slots, chunk, ps = "llama-1b", 8, 128, 16
        prompt_len, max_new = 128, 1024
        block = int(os.environ.get("BENCH_LLM_LONGGEN_BLOCK", "32"))
    else:
        model, slots, chunk, ps = "llama-tiny", 4, 8, 8
        prompt_len, max_new = 12, 32 if fast else 64
        block = int(os.environ.get("BENCH_LLM_LONGGEN_BLOCK", "4"))
    cfg = llama.CONFIGS[model]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda x: x.astype(cfg.dtype), params)
    engine = SlotEngine(params, cfg, num_slots=slots, chunk=chunk,
                        decode_block=block, page_size=ps)
    engine.warmup()
    rng = np.random.default_rng(0)
    handles = [
        engine.submit(
            rng.integers(1, cfg.vocab_size, size=prompt_len).tolist(),
            max_new=max_new)
        for _ in range(slots)
    ]
    # Phase 1: drive until every slot has produced its first token —
    # all prefill chunks and the fused-program dispatches are behind us.
    guard = 0
    while not all(h._tokens for h in handles):
        engine.step()
        guard += 1
        assert guard < 100_000, "longgen prefill phase did not converge"
    # Phase 2: pure long-gen decode, counted from here.
    steps0 = engine.steps_block + engine.steps_decode_only
    active0 = engine.slot_steps_active
    produced0 = sum(len(h._tokens) for h in handles)
    t0 = time.perf_counter()
    while engine.step():
        pass
    dt = time.perf_counter() - t0
    assert all(h.result(timeout=0).finish_reason == "length"
               for h in handles)
    produced = sum(len(h.result(timeout=0).tokens) for h in handles)
    steps = engine.steps_block + engine.steps_decode_only - steps0
    out = {
        "model": model,
        "tokens_per_s_longgen": round((produced - produced0) / dt, 1),
        "decode_block": block,
        "long_new_tokens": max_new,
        "concurrent_slots": slots,
        "decode_steps": steps * block,
        "steps_per_s": round(steps * block / dt, 2),
        "avg_step_ms": round(dt / max(1, steps * block) * 1e3, 4),
        # slot-steps that decoded a token, and tokens the device computed
        # past a request's end (lag-1 dispatch: one block a request)
        "slot_steps_active": engine.slot_steps_active - active0,
        "overshoot_tokens": engine.overshoot_tokens,
    }
    del engine
    gc.collect()
    # tp2 parity sub-stage: greedy tokens over a 2-device tp mesh must
    # be bit-for-bit the tp1 sequence (ROADMAP item 2's proof). Always
    # on the tiny model — parity is a correctness property, not a perf
    # number — and skipped cleanly on single-device hosts (a lone TPU
    # chip or a CPU host without forced virtual devices).
    if len(jax.devices()) >= 2:
        from ray_tpu.parallel.mesh import MeshSpec

        tiny = llama.CONFIGS["llama-tiny"]
        tparams, _ = llama.init_params(jax.random.PRNGKey(0), tiny)
        prompt = rng.integers(1, tiny.vocab_size, size=17).tolist()

        def _run(mesh):
            eng = SlotEngine(tparams, tiny, num_slots=2, chunk=8,
                             page_size=8, decode_block=2, mesh=mesh)
            h = eng.submit(prompt, max_new=12)
            guard = 0
            while not h._done.is_set():
                eng.step()
                guard += 1
                assert guard < 10_000
            sharding = eng._cache["kv"].sharding
            kv_spec = getattr(sharding, "spec", None)
            return h.result(timeout=0).tokens, kv_spec

        t1, _ = _run(None)
        mesh = MeshSpec(tp=2).build(jax.devices()[:2])
        t2, kv_spec = _run(mesh)
        out["tp2_token_parity"] = t1 == t2
        out["tp2_kv_spec"] = str(kv_spec)
        gc.collect()
    else:
        out["tp2"] = "skipped (single host device)"
    return out


def bench_llm_sessions(on_tpu: bool, smoke: bool = False) -> dict:
    """Multi-turn chat serving over a SHARED system prompt (ISSUE 15 /
    ROADMAP item 3): N sessions x M turns, every turn's prompt = system
    prompt + the session's full history + a new user message — the
    prefill-dominated regime production chat traffic lives in. The warm
    pass lets the paged engine's radix prefix cache skip resident
    prefill; the cold pass clears the index before every admission so
    each request re-prefills from token zero. Reports submit-to-first-
    token (TTFT) p50/p99 for both, the warm/cold speedup, and the warm
    pass's prefix hit-rate out of the engine's own counters."""
    import gc
    import time as _t

    import jax
    import numpy as np

    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import llama

    if on_tpu:
        model, slots, chunk, ps = "llama-1b", 8, 128, 16
        sys_len, user_len, max_new = 512, 32, 64
        n_sessions, m_turns = 8, 4
        block = int(os.environ.get("BENCH_LLM_BLOCK", "16"))
    else:
        fast = smoke and os.environ.get("BENCH_SMOKE_FAST") == "1"
        model, slots, chunk, ps = "llama-tiny", 4, 8, 8
        sys_len, user_len, max_new = 48, 4, 4
        n_sessions, m_turns = (2, 2) if fast else (3, 2)
        block = 1
    cfg = llama.CONFIGS[model]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda x: x.astype(cfg.dtype), params)
    # Pool sized with headroom over the slot footprint so the radix can
    # keep every session's history resident across turns.
    num_pages = (n_sessions + slots) * (cfg.max_seq // ps) + 1
    engine = SlotEngine(params, cfg, num_slots=slots, chunk=chunk,
                        decode_block=block, page_size=ps,
                        num_pages=num_pages).start()
    rng = np.random.default_rng(0)
    sys_prompt = rng.integers(1, cfg.vocab_size, size=sys_len).tolist()
    user_msgs = [[rng.integers(1, cfg.vocab_size,
                               size=user_len).tolist()
                  for _ in range(m_turns)] for _ in range(n_sessions)]

    def run_pass(cold: bool) -> dict:
        histories = [[] for _ in range(n_sessions)]
        ttfts_ms, toks = [], 0
        hits0, total0 = engine.prefix_hits, (engine.prefix_hits
                                             + engine.prefix_misses)
        saved0 = engine.prefix_tokens_saved
        t_pass = _t.perf_counter()
        for turn in range(m_turns):
            for sess in range(n_sessions):
                if cold:
                    engine.clear_prefix_cache()
                prompt = (sys_prompt + histories[sess]
                          + user_msgs[sess][turn])
                t0 = _t.perf_counter()
                h = engine.submit(prompt, max_new=max_new)
                out = []
                for tok in h:
                    if not out:
                        ttfts_ms.append((_t.perf_counter() - t0) * 1e3)
                    out.append(tok)
                toks += len(out)
                histories[sess] += user_msgs[sess][turn] + out
        dt = _t.perf_counter() - t_pass
        total = (engine.prefix_hits + engine.prefix_misses) - total0
        return {
            "ttft_ms": percentiles(ttfts_ms),
            "tokens_per_s": round(toks / dt, 1),
            "hit_rate": round((engine.prefix_hits - hits0)
                              / max(total, 1), 3),
            "tokens_saved": engine.prefix_tokens_saved - saved0,
        }

    try:
        engine.warmup()
        cold = run_pass(cold=True)
        warm = run_pass(cold=False)
    finally:
        engine.stop()
    out = {
        "sessions": n_sessions, "turns": m_turns,
        "sys_prompt_len": sys_len, "max_new": max_new,
        "ttft_cold_ms_p50": cold["ttft_ms"]["p50"],
        "ttft_cold_ms_p99": cold["ttft_ms"]["p99"],
        "ttft_warm_ms_p50": warm["ttft_ms"]["p50"],
        "ttft_warm_ms_p99": warm["ttft_ms"]["p99"],
        "warm_ttft_speedup": round(
            cold["ttft_ms"]["p50"] / max(warm["ttft_ms"]["p50"], 1e-9),
            2),
        "prefix_hit_rate": warm["hit_rate"],
        "prefix_tokens_saved": engine.prefix_tokens_saved,
        "prefix_tokens_saved_cold": cold["tokens_saved"],
        "prefix_tokens_saved_warm": warm["tokens_saved"],
        "tokens_per_s_cold": cold["tokens_per_s"],
        "tokens_per_s_warm": warm["tokens_per_s"],
        "pages_total": engine.pages_total,
        "detail": (
            f"{model} paged engine (page {ps}), {n_sessions} sessions x "
            f"{m_turns} turns, shared {sys_len}-token system prompt + "
            f"{user_len}-token user turns, {max_new} new tokens/turn, "
            "greedy; cold = radix cleared before every admission, warm "
            "= prefix cache live"),
    }
    del engine, params
    gc.collect()
    return out


def bench_flight(on_tpu: bool, smoke: bool = False) -> dict:
    """Flight-recorder stage (ISSUE 16): exercise both recorder paths
    and commit their numbers to the bench JSON. Task half — run a spin
    workload on the live runtime and report the head-side per-stage
    (queue/sched/exec/transfer) p50/p99 plus the stage-sum/total
    fraction, which is ~1.0 by construction and asserted by the smoke
    test. LLM half — drive a paged engine, report per-request stage
    p50s from the response ``timing`` metadata and the engine's step
    counters."""
    import gc

    import ray_tpu as rt
    from ray_tpu.observability import flight_summary, recent_flight_tasks

    fast = smoke and os.environ.get("BENCH_SMOKE_FAST") == "1"
    rt.init(ignore_reinit_error=True, num_cpus=4)

    @rt.remote
    def _spin(ms):
        end = time.perf_counter() + ms / 1e3
        while time.perf_counter() < end:
            pass
        return ms

    n_tasks = 16 if fast else 48
    rt.get([_spin.remote(2) for _ in range(n_tasks)], timeout=120)

    # The exec deltas ride the worker metrics flush (~1s interval);
    # poll until every spin task's exec stage has joined head-side.
    out: dict = {"task_n": n_tasks}
    spin_row = None
    deadline = time.time() + 20
    while time.time() < deadline:
        summ = flight_summary()
        row = next((v for k, v in summ.items() if "_spin" in k), None)
        if (row is not None and "exec" in row["stages"]
                and row["stages"]["exec"]["count"] >= n_tasks):
            spin_row = row
            break
        time.sleep(0.25)
    if spin_row is None:
        out["task_join_timeout"] = True
        spin_row = next((v for k, v in flight_summary().items()
                         if "_spin" in k), None)
    if spin_row is not None:
        for stage, d in spin_row["stages"].items():
            out[f"task_{stage}_ms_p50"] = d["p50_ms"]
            out[f"task_{stage}_ms_p99"] = d["p99_ms"]
    rows = [r for r in recent_flight_tasks(limit=500)
            if "_spin" in r["name"] and r["total_s"] > 0]
    out["task_rows_joined"] = len(rows)
    if rows:
        fracs = [(r["queue_s"] + r["sched_s"] + r["exec_s"]
                  + r["transfer_s"]) / r["total_s"] for r in rows]
        out["task_stage_sum_frac_mean"] = round(
            sum(fracs) / len(fracs), 4)

    # -- LLM half: per-request stage timing + step counters. Engine
    # lives in THIS process, so its rt_llm_* series land in the local
    # registry the scrape stage reads.
    import jax
    import numpy as np

    from ray_tpu.llm.engine import SlotEngine
    from ray_tpu.models import llama

    if on_tpu:
        model, slots, chunk, ps, block = "llama-1b", 8, 128, 16, 16
        prompt_len, max_new, n_reqs = 256, 64, 16
    else:
        model, slots, chunk, ps, block = "llama-tiny", 4, 8, 8, 2
        prompt_len, max_new = 24, 8
        n_reqs = 4 if fast else 8
    cfg = llama.CONFIGS[model]
    params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda x: x.astype(cfg.dtype), params)
    engine = SlotEngine(params, cfg, num_slots=slots, chunk=chunk,
                        decode_block=block, page_size=ps).start()
    rng = np.random.default_rng(0)
    try:
        engine.warmup()
        handles = [engine.submit(
            rng.integers(1, cfg.vocab_size, size=prompt_len).tolist(),
            max_new=max_new) for _ in range(n_reqs)]
        timings = [h.result(timeout=300).timing for h in handles]
    finally:
        engine.stop()
    timings = [t for t in timings if t]
    out["llm_requests"] = len(timings)
    for key in ("admission_s", "queue_s", "prefix_match_s", "prefill_s",
                "decode_s", "decode_per_token_s", "total_s"):
        pct = percentiles([t[key] * 1e3 for t in timings])
        out[f"llm_{key[:-2]}_ms_p50"] = pct["p50"]
    out["llm_decode_steps"] = (engine.steps_block
                               + engine.steps_decode_only) * block
    out["llm_slot_steps_active"] = engine.slot_steps_active
    del engine, params
    gc.collect()
    return out


def bench_long_context(on_tpu: bool) -> dict:
    """Long-context training MFU on one chip: GPT-2 355M with flash
    attention at seq 4k/8k/16k, constant 16k tokens per step (VERDICT r4
    item 5 — the MFU-vs-seq curve is the whole point of the flash
    kernel: attention grows O(S^2) while the matmul backbone is linear,
    so sustained MFU across the curve proves the kernel keeps the MXU
    fed as the quadratic term takes over)."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.step import build_sharded_train

    out = {}
    base = gpt2.CONFIGS["gpt2-355m"]
    points = ((4096, 4), (8192, 2), (16384, 1)) if on_tpu \
        else ((512, 1),)
    steps = 4 if on_tpu else 2
    peak = 197e12 if on_tpu else 1e12
    for seq, batch in points:
        cfg = gpt2.GPT2Config(
            vocab_size=base.vocab_size, max_seq=seq,
            num_layers=base.num_layers, num_heads=base.num_heads,
            d_model=base.d_model, dtype=jnp.bfloat16,
            attention_impl="flash" if on_tpu else "reference",
            remat=True, remat_policy="mem2" if on_tpu else "dots_attn",
        )

        def bf16_init(key, cfg=cfg):
            params, axes = gpt2.init_params(key, cfg)
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
            return params, axes

        mesh = MeshSpec(dp=1).build()
        sinit, sstep, _ = build_sharded_train(
            bf16_init, lambda p, b, cfg=cfg: gpt2.loss_fn(p, b, cfg),
            mesh, optimizer=optax.adafactor(learning_rate=1e-4),
            master_fp32=False)
        params, opt_state, step = sinit(jax.random.PRNGKey(0))
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch, seq + 1)), jnp.int32)
        bd = {"tokens": tokens}
        for _ in range(2):
            params, opt_state, step, metrics = sstep(params, opt_state,
                                                     step, bd)
        jax.block_until_ready(metrics)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, step, metrics = sstep(params, opt_state,
                                                     step, bd)
        jax.block_until_ready(metrics)
        dt = (time.perf_counter() - t0) / steps
        tok_s = batch * seq / dt
        mfu = tok_s * gpt2.flops_per_token(cfg, seq) / peak
        out[f"mfu_seq{seq}"] = round(mfu * 100, 2)
        out[f"tokens_per_s_seq{seq}"] = round(tok_s, 1)
        del params, opt_state, metrics, tokens, bd, sstep, sinit
        gc.collect()
    out["detail"] = ("gpt2-355m bf16+adafactor, flash attention, mem2 "
                     "remat, constant 16k tokens/step, ONE v5e chip")
    return out


def bench_ring_parity() -> dict:
    """Ring attention (einsum AND flash-block bodies) vs full reference
    at long sequence lengths on the virtual sp=4 CPU mesh — numeric
    proof the sequence-parallel path computes the same attention the
    single-chip flash kernel does (tolerance 1e-2 per the r4 target;
    observed errors are ~1e-5)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.parallel.ring import ring_attention

    out = {}
    mesh = MeshSpec(sp=4).build(jax.devices()[:4])
    for seq in (4096, 8192):
        ks = jax.random.split(jax.random.PRNGKey(seq), 3)
        q, k, v = (jax.random.normal(kk, (1, 2, seq, 64), jnp.float32)
                   for kk in ks)
        ref = mha_reference(q, k, v, causal=True)
        for impl in ("einsum", "flash"):
            got = ring_attention(q, k, v, mesh, causal=True,
                                 batch_axes=(), heads_axis=None,
                                 impl=impl)
            err = float(jnp.max(jnp.abs(got - ref)))
            out[f"ring_{impl}_vs_full_seq{seq}_max_err"] = round(err, 8)
            assert err < 1e-2, f"{impl}@{seq}: {err}"
        del q, k, v, ref
    return out


def bench_ppo(on_tpu: bool) -> dict:
    """On-device PPO throughput: conv policy on Atari-shaped frames."""
    import jax

    from ray_tpu.rllib.ondevice import OnDevicePPO, jax_atari_sim

    if on_tpu:
        num_envs, rollout, iters = 256, 128, 5
    else:
        num_envs, rollout, iters = 8, 16, 2

    algo = OnDevicePPO(jax_atari_sim(num_envs), rollout_length=rollout,
                       minibatches=8, num_sgd_iter=4)
    algo.train_iteration()  # compile + warmup
    params, opt_state = algo.params, algo.opt_state
    env_state, obs, rng = algo.env_state, algo._obs, algo._rng
    t0 = time.perf_counter()
    for _ in range(iters):
        rng, sub = jax.random.split(rng)
        params, opt_state, env_state, obs, metrics = algo._iterate(
            params, opt_state, env_state, obs, sub)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0
    steps_per_s = iters * rollout * num_envs / dt
    return {
        "ppo_env_steps_per_s": round(steps_per_s, 0),
        "ppo_vs_target": round(steps_per_s / 50_000, 3),
        "ppo_detail": f"on-device PPO, conv(Nature-CNN) policy, "
                      f"AtariSim 84x84x4 uint8, {num_envs} envs x "
                      f"{rollout} steps x {iters} iters",
    }


def scrape_telemetry(port: int = 18269) -> dict:
    """Mid-bench ``/metrics`` scrape: start the dashboard against the
    live runtime, pull the Prometheus text, and record selected
    runtime/serve series into the bench JSON — so the telemetry plane
    (worker->head shipping + instrumentation) can't bitrot silently
    between rounds."""
    import urllib.request

    from ray_tpu.core.config import config
    from ray_tpu.observability import start_dashboard, stop_dashboard

    # One worker flush interval (+margin) so the latest worker-side
    # series land — derived from config, not hardcoded, so a non-default
    # RT_METRICS_REPORT_INTERVAL_MS doesn't make the scrape race ahead
    # of the flushers.
    time.sleep(config().metrics_report_interval_ms / 1000.0 + 0.5)
    start_dashboard(port=port)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=15) as r:
            text = r.read().decode()
    finally:
        stop_dashboard()

    def total(metric: str) -> float:
        s = 0.0
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name = line.split("{", 1)[0].split(" ", 1)[0]
            if name == metric:
                s += float(line.rsplit(" ", 1)[1])
        return round(s, 6)

    return {
        "rt_tasks_submitted_total": total("rt_tasks_submitted"),
        "rt_tasks_finished_total": total("rt_tasks_finished"),
        "rt_task_latency_seconds_count": total(
            "rt_task_latency_seconds_count"),
        "rt_workers_alive": total("rt_workers_alive"),
        "rt_actors_alive": total("rt_actors_alive"),
        "rt_serve_requests_total": total("rt_serve_requests"),
        "rt_serve_replicas": total("rt_serve_replicas"),
        "rt_serve_request_latency_count": total(
            "rt_serve_request_latency_seconds_count"),
        "rt_task_stage_seconds_count": total(
            "rt_task_stage_seconds_count"),
        "rt_llm_stage_seconds_count": total("rt_llm_stage_seconds_count"),
    }


def _tracing_overhead_child(windows: int, batch: int) -> None:
    """Hidden child mode for :func:`bench_tracing_overhead`: boots its
    own runtime (tracing fixed by RT_TRACING_ENABLED in the inherited
    env), drives timed windows of sync no-op tasks, and prints one
    ``CHILD::`` JSON line with the per-window rates plus the driver's
    recorded span count (so an A/B that silently compared off-vs-off
    would be caught by the parent)."""
    import ray_tpu as rt
    from ray_tpu.observability import tracing

    rt.init(num_workers=2)

    @rt.remote
    def noop():
        return None

    rt.get([noop.remote() for _ in range(50)])  # warm the worker pool
    rates = []
    for _ in range(windows + 1):
        t0 = time.perf_counter()
        rt.get([noop.remote() for _ in range(batch)])
        rates.append(batch / (time.perf_counter() - t0))
    spans = len(tracing.get_tracer().spans("task."))
    rt.shutdown()
    # First window still rides pool/allocator ramp — discard it.
    print("CHILD::" + json.dumps({"rates": rates[1:], "spans": spans}))


def bench_tracing_overhead(smoke: bool = False) -> dict:
    """Tracing-overhead A/B (ISSUE 20 acceptance): the same no-op task
    workload in paired subprocess runtimes — ``RT_TRACING_ENABLED=1``
    at the default sample rate vs ``=0`` — alternating modes across
    reps so host drift hits both sides, ratio of pooled median window
    rates. Budget: <5% like every other telemetry plane (PR-13
    precedent); the smoke assertion is deliberately looser so a loaded
    CI host can't flake it while a hot-path regression (per-task span
    cost blowing up) still trips."""
    import subprocess

    # Smoke trims to the minimum that still yields >= 2 pair ratios —
    # each rep boots TWO subprocess runtimes, and the tier-1 suite has
    # a hard wall-clock budget. The committed overhead figure comes
    # from the full-size run (see BASELINE.md), not the smoke gate.
    windows = 3 if smoke else 7
    batch = 200 if smoke else 1000
    reps = 2 if smoke else 4
    here = os.path.abspath(__file__)
    samples = {"on": [], "off": []}
    spans = {"on": 0, "off": 0}
    ratios = []
    for _ in range(reps):
        pair = {}
        for mode, flag in (("on", "1"), ("off", "0")):
            env = dict(os.environ)
            env.setdefault("JAX_PLATFORMS", "cpu")
            env["RT_TRACING_ENABLED"] = flag
            proc = subprocess.run(
                [sys.executable, here, "--tracing-overhead-child",
                 str(windows), str(batch)],
                capture_output=True, text=True, timeout=300, env=env)
            line = next((ln for ln in proc.stdout.splitlines()
                         if ln.startswith("CHILD::")), None)
            if line is None:
                return {"error": f"child ({mode}) produced no result: "
                                 f"rc={proc.returncode} "
                                 f"{proc.stderr[-300:]}"}
            child = json.loads(line[len("CHILD::"):])
            samples[mode].extend(child["rates"])
            spans[mode] += child["spans"]
            pair[mode], _ = median_of_windows(child["rates"])
        # Per-pair ratio: the two children ran back to back, so slow
        # host drift cancels inside the pair; the median across pairs
        # shrugs off a spike hitting one pair.
        ratios.append(pair["on"] / max(pair["off"], 1e-9))
    ratios.sort()
    ratio = ratios[len(ratios) // 2]
    on_med, on_spread = median_of_windows(samples["on"])
    off_med, off_spread = median_of_windows(samples["off"])
    return {
        "tasks_per_s_traced": on_med,
        "tasks_per_s_untraced": off_med,
        "traced_spread": on_spread,
        "untraced_spread": off_spread,
        # Positive = tracing costs throughput. Committed figure: median
        # of PAIRED per-rep ratios (load-robust), not the pooled-median
        # ratio — window spreads on a shared host dwarf the real cost.
        "overhead_frac": round(1.0 - ratio, 4),
        "pair_ratios": [round(r, 4) for r in ratios],
        "spans_traced": spans["on"],
        "spans_untraced": spans["off"],
        "windows_per_mode": windows * reps,
    }


def bench_head_failover(smoke: bool = False) -> dict:
    """Head-failover chaos loop (ROADMAP item 1 'done' criterion): run
    the driver/head on a durable WAL, SIGKILL it mid-actor-workload
    every cycle, and measure how long the replacement head takes to
    recover — WAL replay + named-actor re-resolution + ``max_restarts``
    re-run + the queued call completing. Reports per-cycle recovery
    latency p50/p99 (``recover_ms``: init-to-recovered-call;
    ``total_ms``: process spawn to READY, imports included)."""
    import shutil
    import tempfile

    from ray_tpu.cluster_utils import HeadKiller
    from ray_tpu.core.gcs_socket import build_native

    if not build_native():
        return {"error": "native toolchain unavailable"}
    fast = os.environ.get("BENCH_SMOKE_FAST") == "1"
    # First cycle creates the chaos actor; every later one is a recovery.
    cycles = 2 if fast else (3 if smoke else 6)
    tmp = tempfile.mkdtemp(prefix="rt_headchaos_")
    killer = HeadKiller(os.path.join(tmp, "gcs.wal"),
                        kill_after_s=0.3 if smoke else 1.0)
    try:
        samples = killer.run(cycles)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    recoveries = [s for s in samples if not s.get("created")]
    out = {
        "cycles": cycles,
        "kills": len(killer.killed),
        "recoveries": len(recoveries),
        "actors_restarted_total": int(sum(
            s.get("restarted", 0) for s in recoveries)),
    }
    for key in ("recover_ms", "total_ms"):
        pct = percentiles([s[key] for s in recoveries], unit=None)
        out[f"{key}_p50"] = pct["p50"]
        out[f"{key}_p99"] = pct["p99"]
    return out


def smoke() -> dict:
    """``bench.py --smoke``: tiny-N versions of the host-plane bench
    scenarios (seconds, not minutes) so the bench code paths — core
    microbench, serve HTTP, and the mixed HTTP+handle+streaming stage —
    can't bitrot between full runs. Exercised by a non-slow test
    (tests/test_bench_smoke.py). Prints one RESULT:: JSON line."""
    # BENCH_SMOKE_FAST=1 (the CI/tier-1 test) trims to the minimum that
    # still exercises every scenario code path: the mixed stage already
    # covers HTTP + handle + streaming through one serve instance, so
    # the standalone serve HTTP section is skipped there.
    fast = os.environ.get("BENCH_SMOKE_FAST") == "1"
    result = {"smoke": True}
    try:
        result["core_microbench"] = bench_core(
            duration=0.1 if fast else 0.25)
    except Exception as e:  # noqa: BLE001
        result["core_microbench_error"] = repr(e)[:300]
    if not fast:
        try:
            result["serve_bench"] = bench_serve(smoke=True)
        except Exception as e:  # noqa: BLE001
            result["serve_bench_error"] = repr(e)[:300]
    try:
        result["serve_mixed"] = bench_serve_mixed(smoke=True)
    except Exception as e:  # noqa: BLE001
        result["serve_mixed_error"] = repr(e)[:300]
    # Fault-tolerance chaos stage: replica SIGKILL under live traffic —
    # zero hung / raw-500 requests and bounded replacement latency are
    # asserted by the smoke test so the recovery path can't bitrot.
    try:
        result["serve_chaos"] = bench_serve_chaos(smoke=True)
    except Exception as e:  # noqa: BLE001
        result["serve_chaos_error"] = repr(e)[:300]
    # Paged-KV multi-turn session stage: warm turns must beat cold ones
    # on TTFT via the radix prefix cache (asserted by the smoke test so
    # the scenario — and the cache — can't bitrot).
    try:
        result["llm_sessions"] = bench_llm_sessions(False, smoke=True)
    except Exception as e:  # noqa: BLE001
        result["llm_sessions_error"] = repr(e)[:300]
    # Session-migration chaos stage (ISSUE 19): drain + SIGKILL under
    # live session traffic — zero drops and bit-for-bit continuation
    # parity are asserted by the smoke test.
    try:
        result["llm_drain"] = bench_llm_drain(smoke=True)
    except Exception as e:  # noqa: BLE001
        result["llm_drain_error"] = repr(e)[:300]
    # Long-gen decode stage (ISSUE 17), incl. the tp2 parity
    # sub-stage when the host exposes >= 2 (possibly virtual) devices.
    try:
        result["llm_longgen"] = bench_llm_longgen(False, smoke=True)
    except Exception as e:  # noqa: BLE001
        result["llm_longgen_error"] = repr(e)[:300]
    # Flight-recorder stage BEFORE the scrape: it observes the stage
    # histograms this process's /metrics must then contain.
    try:
        result["bench_flight"] = bench_flight(False, smoke=True)
    except Exception as e:  # noqa: BLE001
        result["bench_flight_error"] = repr(e)[:300]
    # Mid-bench scrape while the runtime is still up: the stages above
    # must have left their marks in the cluster /metrics.
    try:
        result["telemetry_scrape"] = scrape_telemetry()
    except Exception as e:  # noqa: BLE001
        result["telemetry_scrape_error"] = repr(e)[:300]
    # Tracing-overhead A/B (ISSUE 20): paired subprocess runtimes with
    # RT_TRACING_ENABLED=1 vs =0 — the per-request span plane must stay
    # inside the telemetry overhead budget.
    try:
        result["tracing_overhead"] = bench_tracing_overhead(smoke=True)
    except Exception as e:  # noqa: BLE001
        result["tracing_overhead_error"] = repr(e)[:300]
    # Head-failover recovery stage: subprocess heads on their own WAL —
    # independent of this process's runtime, so it runs last either way.
    try:
        result["head_failover"] = bench_head_failover(smoke=True)
    except Exception as e:  # noqa: BLE001
        result["head_failover_error"] = repr(e)[:300]
    try:
        import ray_tpu as rt

        rt.shutdown()
    except Exception:
        pass
    print("RESULT::" + json.dumps(result))
    return result


if __name__ == "__main__":
    if "--tracing-overhead-child" in sys.argv:
        _i = sys.argv.index("--tracing-overhead-child")
        _tracing_overhead_child(int(sys.argv[_i + 1]),
                                int(sys.argv[_i + 2]))
    elif "--smoke" in sys.argv:
        smoke()
    else:
        main()
