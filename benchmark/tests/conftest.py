"""The benchmark's own tests run on the CPU at tiny sizes: four virtual
devices for the mesh path, set before anything imports JAX."""

import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["PYTHONPATH"] = os.pathsep.join(
    [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
              if p and p != ROOT])

import pytest  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
RENAME = {"smollm2-1.7b.chat_steady": "tiny.chat",
          "smollm2-1.7b.batch_closed": "tiny.batch",
          "gpt2-large.pretrain_1k": "tiny.train",
          "gpt2-xl.pretrain_1k_fsdp4": "tiny.train"}


def make_root(tmp: str) -> str:
    """A checkout in ``tmp``: a copy of ``benchmark/`` plus, ADDED and
    nothing edited, the tiny configurations, mixes and cells of
    ``fixtures/`` and a manifest that lists them."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "tools"))
    for name in os.listdir(FIXTURES):
        if name.startswith("tiny-"):
            shutil.copy(os.path.join(FIXTURES, name),
                        os.path.join(tmp, "benchmark", "configs", name))
        elif name.startswith("tiny_"):
            shutil.copy(os.path.join(FIXTURES, name),
                        os.path.join(tmp, "benchmark", "traffic", name))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] = [
        {"name": n, "source": "test fixture",
         "file": f"benchmark/configs/{n}.json", "reduced": [], "why": "tiny"}
        for n in ("tiny-llama", "tiny-gpt2")]
    bench["workloads"] = [
        {"name": "tiny.chat", "config": "tiny-llama", "traffic": "tiny_chat",
         "chips": 1, "why": "tiny"},
        {"name": "tiny.batch", "config": "tiny-llama",
         "traffic": "tiny_batch", "chips": 1, "why": "tiny"},
        {"name": "tiny.train", "config": "tiny-gpt2",
         "traffic": "tiny_pretrain", "chips": 4, "why": "tiny"}]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = sorted({RENAME[w] for w in m["workloads"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(str(tmp_path))
