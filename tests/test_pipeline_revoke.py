"""Pipelined same-key tasks must not starve behind a blocked worker.

The scheduler eagerly fills a leased worker's pipe to PIPELINE_DEPTH with
same-key tasks. If the head-of-line task blocks indefinitely in get/wait
(e.g. on a gate actor), the queued tasks used to starve — even with idle
workers — because nothing could pull them back out of the pipe. The owner
now sends a "revoke" on worker-block; the worker returns the
never-started subset, which is rescheduled (reference analog: raylet
worker-lease cancellation, ``direct_task_transport.h`` OnWorkerIdle).
"""

import threading

import ray_tpu as rt


def test_blocked_worker_pipeline_no_starvation():
    rt.init(num_cpus=1, ignore_reinit_error=True)
    try:
        @rt.remote(max_concurrency=2)
        class Gate:
            def __init__(self):
                self.ev = threading.Event()

            def open(self):
                self.ev.set()
                return True

            def wait(self):
                self.ev.wait(60)
                return self.ev.is_set()

        gate = Gate.remote()

        @rt.remote
        def task(i, gate):
            if i == 0:
                # Blocks in rt.get inside the worker until the gate
                # opens — the head-of-line task of the pipelined lease.
                assert rt.get(gate.wait.remote(), timeout=90)
                return -1
            return i

        refs = [task.remote(i, gate) for i in range(4)]
        # Tasks 1..3 must complete while task 0 is still blocked: the
        # revoke path reschedules them onto the worker the pool grew.
        done, pending = rt.wait(refs[1:], num_returns=3, timeout=30)
        assert len(done) == 3, (
            f"pipelined tasks starved behind blocked worker "
            f"({len(done)}/3 completed)")
        assert sorted(rt.get(done, timeout=30)) == [1, 2, 3]
        rt.get(gate.open.remote(), timeout=30)
        assert rt.get(refs[0], timeout=30) == -1
    finally:
        rt.shutdown()
