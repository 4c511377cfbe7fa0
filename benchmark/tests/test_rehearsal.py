"""Both drivers end to end on the CPU at tiny sizes (the replica and the
train worker are real processes started through ``serve.run`` and
``DataParallelTrainer.fit``), and the refusals: the measuring path reports
no device metric off the chip, and the command prints no result."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.manifest import Manifest, compute_metrics

from conftest import ROOT


def _run(root, cell_name, seconds, trace):
    m = Manifest(root)
    cell = m.cell(cell_name)
    driver = m.load_module("drivers", cell["config"]["driver"])
    out = driver.run(m, cell, seed=2**31 + 17, seconds=seconds, trace=trace,
                     t0=time.time(), log=lambda s: None, rehearsal=True)
    ctx = dict(out["ctx"], config=cell["config"], traffic=cell["traffic"],
               chips=cell["chips"], seconds=seconds,
               peaks=m.peaks("TPU v5 lite"))
    return m, cell, out, ctx


def test_serving_open_loop_rehearsal(tiny_root):
    m, cell, out, ctx = _run(tiny_root, "tiny.chat", 3.0, False)
    assert out["correct"], out["notes"]
    assert out["attempted"] == 18 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"  # and so no result off-chip
    got = compute_metrics(m, cell["metrics"]["end_to_end"], ctx)
    assert set(got) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert all(v["value"] > 0 for v in got.values())
    layers = compute_metrics(m, cell["metrics"]["per_layer"], ctx)
    # untraced: only the load generator's lateness has something to read
    assert set(layers) == {"loadgen.late_p90_ms"}


def test_serving_closed_loop_rehearsal_and_traced_refusal(tiny_root):
    m, cell, out, ctx = _run(tiny_root, "tiny.batch", 2.0, False)
    assert out["correct"], out["notes"]
    got = compute_metrics(m, cell["metrics"]["end_to_end"], ctx)
    assert got["out_tokens_per_s"]["value"] > 0
    # a traced run off the chip has no device plane: the reduction refuses
    with pytest.raises(Exception, match="no device plane"):
        _run(tiny_root, "tiny.batch", 2.0, True)


def test_training_rehearsal_on_four_virtual_devices(tiny_root):
    m, cell, out, ctx = _run(tiny_root, "tiny.train", 2.0, False)
    assert out["correct"], out["notes"]
    assert out["device"]["count"] == 4
    got = compute_metrics(m, cell["metrics"]["end_to_end"], ctx)
    assert got["train_tokens_per_s"]["value"] > 0
    assert ctx["counters"]["window_s"] >= 2.0
    assert ctx["counters"]["train_tokens"] == ctx["counters"]["steps"] * 256
    assert "fsdp=4" in out["notes"][0]


def test_the_command_prints_no_result_off_the_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark/run.py"), "--workload",
         "gpt2-large.pretrain_1k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=ROOT)
    assert p.returncode != 0 and time.time() - t0 < 30
    last = p.stdout.strip().splitlines()[-1]
    with pytest.raises(ValueError):
        json.loads(last)
    assert "pinned to the CPU" in p.stderr


def test_the_command_fails_without_the_program(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no result."""
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "smollm2-1.7b.chat_steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip().splitlines()[-1].startswith("{")
