"""Real multi-process SPMD: Train WorkerGroup actors -> Bootstrap
rendezvous on the NATIVE control store -> jax.distributed CPU mesh ->
one build_sharded_train step.

VERDICT round-1 item 8: N>=2 real OS processes (rt worker actors, not
threads) each claim a rank through the C++ control store, form one
jax.distributed world whose devices span processes, and run one fsdp/dp
sharded train step through the Train path (session + WorkerGroup), i.e.
the flow a real TPU pod uses with one process per host.
"""

import numpy as np
import pytest

from ray_tpu.core.gcs_socket import ControlStoreProcess, build_native

pytestmark = pytest.mark.skipif(
    not build_native(), reason="native control store unavailable")


WORLD = 2


def _spmd_train_fn(config):
    """Runs inside each Train worker actor (its own OS process)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from ray_tpu.core.gcs_socket import ControlStoreClient
    from ray_tpu.parallel.bootstrap import Bootstrap
    from ray_tpu.train.session import get_session

    ctx = get_session().ctx
    kv = ControlStoreClient(tuple(config["gcs_addr"]))
    bs = Bootstrap(kv, world_size=WORLD, session="spmd-test",
                   host_id=f"host-{ctx.world_rank}")
    rank = bs.claim_rank()
    bs.coordinator_address()
    bs.initialize_jax()

    assert jax.process_count() == WORLD
    assert jax.device_count() == 2 * WORLD  # devices span processes

    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.step import build_sharded_train, default_optimizer

    cfg = gpt2.GPT2Config(
        vocab_size=128, max_seq=16, num_layers=2, num_heads=2, d_model=32,
        dtype=jnp.float32, attention_impl="reference", remat=False)
    mesh = MeshSpec(dp=2, fsdp=2).build(jax.devices())
    sinit, sstep, rules = build_sharded_train(
        lambda key: gpt2.init_params(key, cfg),
        lambda p, b: gpt2.loss_fn(p, b, cfg),
        mesh, optimizer=default_optimizer(total_steps=4))
    params, opt_state, step = sinit(jax.random.PRNGKey(0))

    from jax.sharding import NamedSharding, PartitionSpec as P

    batch_sharding = NamedSharding(mesh, P(("dp", "fsdp"), None))
    rng = np.random.default_rng(0)  # same on every process
    global_tokens = rng.integers(
        0, cfg.vocab_size, (4, cfg.max_seq + 1)).astype(np.int32)
    tokens = jax.make_array_from_process_local_data(
        batch_sharding, global_tokens)
    params, opt_state, step, metrics = sstep(
        params, opt_state, step, {"tokens": tokens})
    loss = float(metrics["loss"])
    assert np.isfinite(loss)
    return {"rank": rank, "loss": loss,
            "devices": jax.device_count(),
            "processes": jax.process_count()}


def test_workergroup_spmd_two_processes():
    store = ControlStoreProcess()
    try:
        import ray_tpu as rt
        from ray_tpu.train.worker_group import WorkerGroup

        rt.init(num_cpus=4, ignore_reinit_error=True)
        group = WorkerGroup(num_workers=WORLD)
        try:
            results = group.execute(
                _spmd_train_fn, {"gcs_addr": store.address})
        finally:
            group.shutdown()
            rt.shutdown()
        assert len(results) == WORLD
        assert {r["rank"] for r in results} == set(range(WORLD))
        assert all(r["processes"] == WORLD for r in results)
        assert all(r["devices"] == 2 * WORLD for r in results)
        # SPMD: every process computes the same global loss
        losses = [r["loss"] for r in results]
        assert abs(losses[0] - losses[1]) < 1e-5, losses
    finally:
        store.stop()
