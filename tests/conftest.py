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


@pytest.fixture(autouse=True, scope="module")
def no_runtime_outlives_its_module(request):
    """A runtime a module leaves up is shut down (and named) when the
    module ends: the next file on this worker would adopt it
    (``ignore_reinit_error``, the auto-init) with its CPU count and its
    warm workers, whose ``sys.path`` is the leaver's, and which file
    comes next on a worker differs from run to run."""
    yield
    import sys
    import warnings

    rt = sys.modules.get("ray_tpu")
    if rt is not None and rt.is_initialized():
        warnings.warn(f"{request.module.__name__} left a runtime up")
        rt.shutdown()


@pytest.fixture
def rt_cluster():
    """Multi-node simulated cluster (one head + helper to add nodes)."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()


class EveryTestEnds:
    """A limit for each test's set-up, call and tear-down together.

    Past ``seconds`` an alarm in the main thread fails the test by name
    with every thread's stack in its report; its fixtures are torn down
    and the worker goes on. Past ``worker_seconds`` (a wait the alarm
    could not break: the main thread in native code, the signal blocked)
    a watchdog thread prints the stacks, kills the process's children
    and ends the process, so that xdist fails the test by name and hands
    the rest of its file to a replacement worker (the ended test with it:
    a note in the run's own temporary file fails that one at set-up, not
    run again). The children a timed-out test started in its call are
    killed on both paths.
    """

    def __init__(self, seconds, worker_seconds):
        self.seconds, self.worker_seconds = seconds, worker_seconds
        self._before_call = None

    @staticmethod
    def _children():
        import psutil

        return set(psutil.Process().children(recursive=True))

    @staticmethod
    def _kill(procs):
        import psutil

        for p in procs:
            try:
                p.kill()
            except psutil.NoSuchProcess:
                pass
        psutil.wait_procs(procs, timeout=5)

    @staticmethod
    def _stacks():
        import faulthandler
        import tempfile

        with tempfile.TemporaryFile() as f:
            faulthandler.dump_traceback(f, all_threads=True)
            f.seek(0)
            return f.read().decode(errors="replace")

    @staticmethod
    def _ended(config):
        """The file that names the tests which ended a worker of this
        xdist run; none without xdist, where no worker is replaced."""
        import pathlib
        import tempfile

        run = getattr(config, "workerinput", {}).get("testrunuid")
        return run and pathlib.Path(tempfile.gettempdir(),
                                    f"pytest-ended-{run}")

    def _end_worker(self, item):
        capture = item.config.pluginmanager.getplugin("capturemanager")
        if capture is not None:  # so that the stacks reach the run's log
            capture.suspend_global_capture(in_=False)
        os.write(2, (
            f"\n{item.nodeid} ran past {self.worker_seconds} s and the "
            f"alarm at {self.seconds} s did not break its wait: ending "
            f"this worker.\n{self._stacks()}"
        ).encode())
        ended = self._ended(item.config)
        if ended:
            with ended.open("a") as f:
                f.write(item.nodeid + "\n")
        self._kill(self._children())
        os._exit(70)

    @pytest.hookimpl(tryfirst=True)
    def pytest_runtest_setup(self, item):
        ended = self._ended(item.config)
        if (ended and ended.exists()
                and item.nodeid in ended.read_text().splitlines()):
            pytest.fail(f"{item.nodeid} ended a worker of this run (see the "
                        f"run's log for its stacks): not run again.")

    @pytest.hookimpl(wrapper=True, tryfirst=True)
    def pytest_runtest_protocol(self, item):
        import faulthandler
        import signal
        import threading

        at_alarm = []  # the children alive when the alarm fired, if it did

        def on_alarm(signum, frame):
            # Taken here: the test's own clean-up may kill a child first
            # and so orphan that child's children out of our sight.
            at_alarm.append(self._children())
            pytest.fail(
                f"{item.nodeid} ran past its limit of {self.seconds} s "
                f"(tests/conftest.py). Every thread's stack:\n"
                f"{self._stacks()}"
            )

        self._before_call = None
        watchdog = threading.Timer(
            self.worker_seconds, self._end_worker, [item]
        )
        watchdog.daemon = True
        watchdog.start()
        # Should the watchdog itself never run (the GIL held in native
        # code), this one needs no Python to end the process.
        faulthandler.dump_traceback_later(
            self.worker_seconds + self.seconds, exit=True
        )
        previous = signal.signal(signal.SIGALRM, on_alarm)
        signal.setitimer(signal.ITIMER_REAL, self.seconds)
        try:
            return (yield)
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, previous)
            faulthandler.cancel_dump_traceback_later()
            watchdog.cancel()
            if at_alarm and self._before_call is not None:
                self._kill((at_alarm[0] | self._children())
                           - self._before_call)

    @pytest.hookimpl(wrapper=True, tryfirst=True)
    def pytest_runtest_call(self, item):
        self._before_call = self._children()
        return (yield)


# One limit for every tier-1 test: twice the longest honest test on a
# loaded six-worker run. A test that needs more is made shorter.
TEST_SECONDS, WORKER_SECONDS = 300, 360


def pytest_configure(config):
    config.pluginmanager.register(EveryTestEnds(TEST_SECONDS, WORKER_SECONDS))
