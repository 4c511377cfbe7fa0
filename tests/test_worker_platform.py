"""Workers land on the same JAX backend as the driver by inheritance.

Nothing in the runtime pins a worker's platform: ``JAX_PLATFORMS`` and
``XLA_FLAGS`` travel in the environment, a spawned worker inherits both,
and JAX reads them when the worker first imports it. A worker on another
backend than the driver's, or with another device count, breaks every
multi-device mesh build (reference analog: ``python/ray/cluster_utils.py``
Cluster fixtures asserting homogeneous worker environments).
"""

import os

import jax

import ray_tpu as rt


def _probe_backend():
    import os

    import jax

    return {
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
        "XLA_FLAGS": os.environ.get("XLA_FLAGS"),
    }


def _assert_inherited(out):
    assert out["JAX_PLATFORMS"] == os.environ["JAX_PLATFORMS"] == "cpu"
    assert out["XLA_FLAGS"] == os.environ["XLA_FLAGS"]
    assert out["backend"] == jax.default_backend(), (
        f"worker initialized backend {out['backend']!r} but driver runs "
        f"on {jax.default_backend()!r}")
    assert out["n_devices"] == len(jax.devices()), (
        f"worker sees {out['n_devices']} devices, driver "
        f"{len(jax.devices())}")


def test_worker_backend_matches_driver(rt_init):
    probe = rt.remote(_probe_backend)
    _assert_inherited(rt.get(probe.remote(), timeout=60))


def test_worker_backend_matches_driver_in_actor(rt_init):
    @rt.remote
    class Probe:
        def backend(self):
            return _probe_backend()

    a = Probe.remote()
    _assert_inherited(rt.get(a.backend.remote(), timeout=60))
