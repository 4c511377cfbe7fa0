"""The manifest loader: unknown names are errors, names and units keep to
their alphabet, and a later PR adds a cell, a configuration, a generator
and a metric with its reader by adding files and entries alone."""

import json
import os

import pytest

from benchmark.manifest import Manifest, ManifestError, compute_metrics

from conftest import ROOT


def _edit(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    fn(bench)
    with open(path, "w") as fh:
        json.dump(bench, fh)


def test_the_repos_manifest_loads_every_cell():
    m = Manifest(ROOT)
    assert len(m.workloads) == 4
    for name in m.workloads:
        cell = m.cell(name)
        e2e = [x["name"] for x in cell["metrics"]["end_to_end"]]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["metrics"]["per_layer"]
        for pl in cell["metrics"]["per_layer"]:
            assert pl["moves"] in e2e  # reported wherever this one is
    assert sum(w["chips"] == 4 for w in m.workloads.values()) == 1


def test_serving_configuration_passes_no_scheduling_option():
    cfg = Manifest(ROOT).config("smollm2-1.7b")
    assert not set(cfg["deployment"]) & {
        "chunk", "decode_block", "prefix_cache", "max_pending",
        "queue_timeout_s"}


@pytest.mark.parametrize("what", ["reader", "generator", "config", "driver",
                                  "reference", "workload"])
def test_unknown_names_are_errors(tiny_root, what):
    if what == "reader":
        with open(os.path.join(tiny_root, "benchmark/metrics/setup_s.json"),
                  "w") as fh:
            json.dump({"reader": "nonesuch", "args": {}}, fh)
    elif what == "generator":
        with open(os.path.join(tiny_root,
                               "benchmark/traffic/tiny_chat.json"), "w") as fh:
            json.dump({"generator": "nonesuch"}, fh)
    elif what == "config":
        _edit(tiny_root, lambda b: b["workloads"][0].update(config="nope"))
    elif what in ("driver", "reference"):
        path = os.path.join(tiny_root, "benchmark/configs/tiny-llama.json")
        with open(path) as fh:
            cfg = json.load(fh)
        cfg[what] = "nonesuch"
        with open(path, "w") as fh:
            json.dump(cfg, fh)
    with pytest.raises(ManifestError, match="unknown"):
        Manifest(tiny_root).cell("nope" if what == "workload"
                                 else "tiny.chat")


@pytest.mark.parametrize("key,bad", [
    ("name", "ttft p90"), ("name", "a/b"), ("name", "x" * 65),
    ("unit", "tokens per second"), ("unit", "µs"), ("unit", "")])
def test_names_and_units_keep_to_their_alphabet(tiny_root, key, bad):
    _edit(tiny_root, lambda b: b["end_to_end"][0].update({key: bad}))
    with pytest.raises(ManifestError):
        Manifest(tiny_root)


def test_unknown_device_kind_has_no_peaks(tiny_root):
    with pytest.raises(ManifestError, match="no published peaks"):
        Manifest(tiny_root).peaks("TPU v9")


def test_a_later_pr_adds_only_files_and_entries(tiny_root):
    """One new configuration, mix (with a new generator), cell and
    per-layer metric (with a new reader); nothing that was there changes."""
    bdir = os.path.join(tiny_root, "benchmark")
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bdir) for p in fs}
    with open(os.path.join(bdir, "configs/tiny-llama.json")) as fh:
        cfg = json.load(fh)
    cfg.update(name="tiny-llama-wide", num_hidden_layers=3)
    with open(os.path.join(bdir, "configs/tiny-llama-wide.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(bdir, "traffic/bursty.py"), "w") as fh:
        fh.write("MODE = 'open'\n"
                 "def plan(traffic, seed, seconds, vocab, deployment=None):\n"
                 "    return {'mode': MODE, 'requests': [], 'grace_s': 0}\n")
    with open(os.path.join(bdir, "traffic/tiny_bursty.json"), "w") as fh:
        json.dump({"generator": "bursty"}, fh)
    with open(os.path.join(bdir, "readers/longest.py"), "w") as fh:
        fh.write("def read(ctx, series):\n"
                 "    return max(ctx['series'].get(series) or [None])\n")
    with open(os.path.join(bdir, "metrics/loadgen.late_max_ms.json"),
              "w") as fh:
        json.dump({"reader": "longest", "args": {"series": "late_ms"}}, fh)

    def add(b):
        b["configs"].append({"name": "tiny-llama-wide", "source": "t",
                             "file": "benchmark/configs/tiny-llama-wide.json",
                             "reduced": [], "why": "t"})
        b["workloads"].append({"name": "wide.bursty", "chips": 1, "why": "t",
                               "config": "tiny-llama-wide",
                               "traffic": "tiny_bursty"})
        for m in b["end_to_end"]:
            if m["name"] == "ttft_p90_ms":
                m["workloads"].append("wide.bursty")
        b["per_layer"].append({
            "name": "loadgen.late_max_ms", "unit": "ms", "better": "lower",
            "source": "host_clock", "layer": "load generator",
            "moves": "ttft_p90_ms", "workloads": ["wide.bursty"]})

    _edit(tiny_root, add)
    m = Manifest(tiny_root)
    cell = m.cell("wide.bursty")
    assert cell["config"]["num_hidden_layers"] == 3
    assert m.load_module("traffic", "bursty").plan({}, 0, 1, 2) == {
        "mode": "open", "requests": [], "grace_s": 0}
    got = compute_metrics(m, cell["metrics"]["per_layer"],
                          {"series": {"late_ms": [1.0, 4.0, 2.0]}})
    assert got == {"loadgen.late_max_ms": {"value": 4.0, "unit": "ms"}}
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(bdir) for p in fs}
    assert all(after[p] == before[p] for p in before)  # nothing edited
    assert m.cell("tiny.chat")["config"]["num_hidden_layers"] == 2
