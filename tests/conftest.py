"""Test config: force CPU JAX with 8 virtual devices (multi-chip simulation).

Mirrors the reference's single-machine multi-node testing strategy
(``ray.cluster_utils.Cluster``, SURVEY §4): sharding/collective tests run on
an 8-device CPU mesh exactly as they would over a TPU slice.
"""

import os

# Before anything imports jax: the platform and the virtual 8-device
# host. Worker and test subprocesses inherit both from the environment.
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


@pytest.fixture
def rt_init():
    """A fresh 1-node runtime per test, torn down after.

    Do not mix with ``rt_shared`` in the same module: this fixture tears the
    process-wide runtime down.
    """
    import ray_tpu as rt

    if rt.is_initialized():
        rt.shutdown()
    rt.init(num_cpus=4)
    yield rt
    rt.shutdown()


@pytest.fixture(scope="module")
def rt_shared():
    """Module-shared runtime for stateless API tests (fast path).

    Analogous to the reference's ``ray_start_regular_shared``.
    """
    import ray_tpu as rt

    # An earlier module may have left an auto-inited runtime alive with
    # machine-sized num_cpus (=1 on this box) — too small for the gang
    # tests. Always start from a known 4-CPU runtime.
    if rt.is_initialized():
        rt.shutdown()
    rt.init(num_cpus=4)
    # Warm two workers so latency-sensitive tests see a hot pool.
    @rt.remote
    def _noop():
        return None

    rt.get([_noop.remote() for _ in range(2)])
    yield rt
    rt.shutdown()


@pytest.fixture
def rt_cluster():
    """Multi-node simulated cluster (one head + helper to add nodes)."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()
