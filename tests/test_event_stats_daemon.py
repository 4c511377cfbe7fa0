"""Daemon-process event stats aggregate to the head (own module:
standalone Cluster must not share a module with rt_shared fixtures)."""

import ray_tpu as rt


def test_daemon_event_stats_reach_head():
    """daemon.* handler rows from the daemon's OWN process aggregate
    into the head's event_loop_stats with a node column."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.observability import event_loop_stats

    if rt.is_initialized():  # left by whichever file this worker ran last
        rt.shutdown()
    cluster = Cluster(head_node_args={"num_cpus": 1})
    try:
        nid = cluster.add_node(num_cpus=2, resources={"zone_d": 1.0},
                               remote=True)
        cluster.wait_for_nodes()

        @rt.remote(resources={"zone_d": 0.1})
        def f(x):
            return x * 2

        assert rt.get([f.remote(i) for i in range(8)], timeout=60) == \
            [2 * i for i in range(8)]
        rows = event_loop_stats(top=0)
        daemon_rows = [r for r in rows
                       if r["handler"].startswith("daemon.")]
        assert daemon_rows, [r["handler"] for r in rows][:10]
        assert all(r["node"] != "head" for r in daemon_rows)
        assert any(r["node"] == "head" for r in rows)
    finally:
        cluster.shutdown()


