"""The suite's limit (``tests/conftest.py EveryTestEnds``) ends a test that
waits for good: each case is a ``pytest`` child on a three-test file with
the limit at 2 s and the worker's at 5 s."""

import os
import pathlib
import re
import subprocess
import sys

import psutil
import pytest

CONFTEST = f"""
import importlib.util
spec = importlib.util.spec_from_file_location(
    "suite_conftest", {str(pathlib.Path(__file__).with_name("conftest.py"))!r})
suite = importlib.util.module_from_spec(spec)
spec.loader.exec_module(suite)

def pytest_configure(config):
    config.pluginmanager.register(suite.EveryTestEnds(2, 5))
"""

# What the middle test of the file does, by the kind of wait.
PYTHON_WAIT = "threading.Event().wait()"
WAITS = {
    "call": f"""
def test_blocked():
    start_child()
    {PYTHON_WAIT}
""",
    "teardown": f"""
@pytest.fixture
def blocked_teardown():
    yield
    {PYTHON_WAIT}

def test_blocked(blocked_teardown):
    start_child()
""",
    # The alarm is never delivered to the main thread, as under native code.
    "unbreakable": f"""
def test_blocked():
    start_child()
    signal.pthread_sigmask(signal.SIG_BLOCK, [signal.SIGALRM])
    {PYTHON_WAIT}
""",
}

TEST_FILE = """
import pathlib, signal, subprocess, sys, threading
import pytest

def start_child():
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(600)"])
    pathlib.Path(__file__).with_name("child.pid").write_text(str(child.pid))

def test_before():
    pass
{wait}
def test_after():
    pass
"""


def _run(tmp_path, wait, *args):
    (tmp_path / "conftest.py").write_text(CONFTEST)
    (tmp_path / "test_three.py").write_text(TEST_FILE.format(wait=WAITS[wait]))
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "test_three.py", "-v", "-rA",
         "-p", "no:cacheprovider", *args],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, TMPDIR=str(tmp_path)),  # its notes, with it
    )
    out = done.stdout + done.stderr
    summary = re.search(r" in ([0-9.]+)s", out)  # none if the process was ended
    seconds = float(summary.group(1)) if summary else None
    return done.returncode, out, seconds


@pytest.mark.parametrize("wait, verdict, counts", [
    pytest.param("call", "FAILED test_three.py::test_blocked",
                 "1 failed, 2 passed", id="call"),
    pytest.param("teardown", "ERROR test_three.py::test_blocked",
                 "3 passed, 1 error", id="teardown"),
])
def test_a_wait_in_python_fails_by_name_and_the_file_goes_on(
        tmp_path, wait, verdict, counts):
    rc, out, seconds = _run(tmp_path, wait)
    assert rc == 1, out
    assert seconds < 10, out
    assert verdict in out and "ran past its limit of 2 s" in out, out
    assert PYTHON_WAIT in out, out  # the blocked frame, in the report
    assert "PASSED test_three.py::test_after" in out and counts in out, out


def test_a_wait_python_cannot_break_ends_the_worker_and_the_file_goes_on(
        tmp_path):
    rc, out, seconds = _run(
        tmp_path, "unbreakable", "-p", "xdist", "-n", "2", "--dist", "loadfile")
    assert rc == 1, out
    assert 5 <= seconds < 20, out
    assert "crashed while running 'test_three.py::test_blocked'" in out, out
    assert "did not break its wait: ending this worker" in out, out
    assert re.search(r'test_three.py", line \d+ in test_blocked', out), out
    assert "PASSED test_three.py::test_after" in out, out
    # xdist hands the ended test to the replacement worker too: not run again.
    assert "ended a worker of this run" in out, out
    assert "1 failed, 2 passed, 1 error" in out, out


@pytest.mark.parametrize("wait", ["call", "unbreakable"])
def test_a_child_of_the_hung_test_is_gone_when_the_run_ends(tmp_path, wait):
    rc, out, _ = _run(tmp_path, wait)
    assert rc != 0, out
    pid = int((tmp_path / "child.pid").read_text())
    assert not psutil.pid_exists(pid), out
