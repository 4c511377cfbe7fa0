"""Loads ``BENCHMARK.json`` and finds everything else by name.

The harness knows no model, cell or metric: a cell names a configuration
and a traffic mix, a configuration names its driver and its reference, a
traffic mix names its generator, a metric names its reader. Each of those
is a file under ``benchmark/`` that a later PR can add without editing one
that is there:

    benchmark/configs/<config>.json     (path given by BENCHMARK.json)
    benchmark/traffic/<traffic>.json    -> "generator": benchmark/traffic/<g>.py
    benchmark/metrics/<metric>.json     -> "reader":    benchmark/readers/<r>.py
    benchmark/drivers/<driver>.py       (named by the configuration)
    benchmark/reference/<arch>.py       (named by the configuration)

Anything unknown is an error, never a default.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _name(value: Any, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ManifestError(f"{what}: {value!r} is not a name (letters, "
                            "digits, '_', '.', '-'; at most 64)")
    return value


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"no such file: {path}")
    with open(path) as fh:
        try:
            data = json.load(fh)
        except json.JSONDecodeError as e:
            raise ManifestError(f"{path}: {e}") from e
    if not isinstance(data, dict):
        raise ManifestError(f"{path}: not a JSON object")
    return data


class Manifest:
    """``BENCHMARK.json`` of the checkout at ``root``, validated."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.data = _read_json(os.path.join(self.root, "BENCHMARK.json"))
        paths = self.data.get("paths") or []
        if len(paths) != 1:
            raise ManifestError("this harness lives in exactly one "
                                f"directory, 'paths' is {paths!r}")
        self.dir = os.path.join(self.root, paths[0])
        self.configs = {_name(c.get("name"), "config"): c
                        for c in self.data.get("configs", [])}
        self.workloads = {_name(w.get("name"), "workload"): w
                          for w in self.data.get("workloads", [])}
        self.metrics: Dict[str, dict] = {}
        for kind in ("end_to_end", "per_layer"):
            for m in self.data.get(kind, []):
                name = _name(m.get("name"), f"{kind} metric")
                if name in self.metrics:
                    raise ManifestError(f"metric {name!r} appears twice")
                unit = m.get("unit")
                if not isinstance(unit, str) or not UNIT_RE.match(unit):
                    raise ManifestError(
                        f"metric {name!r}: unit {unit!r} has a character "
                        "outside letters, digits, '_', '/', '%', '.', '-'")
                if m.get("better") not in ("lower", "higher"):
                    raise ManifestError(f"metric {name!r}: 'better' is "
                                        f"{m.get('better')!r}")
                if m.get("source") not in SOURCES:
                    raise ManifestError(f"metric {name!r}: source "
                                        f"{m.get('source')!r}")
                for cell in m.get("workloads", []):
                    if cell not in self.workloads:
                        raise ManifestError(
                            f"metric {name!r} lists unknown cell {cell!r}")
                self.metrics[name] = dict(m, kind=kind)
        for name, m in self.metrics.items():
            if m["kind"] == "per_layer":
                moved = self.metrics.get(m.get("moves"))
                if moved is None or moved["kind"] != "end_to_end":
                    raise ManifestError(f"metric {name!r} moves "
                                        f"{m.get('moves')!r}, which is no "
                                        "end-to-end metric")
        for name, w in self.workloads.items():
            _name(w.get("traffic"), f"traffic of {name}")
            if w.get("config") not in self.configs:
                raise ManifestError(f"cell {name!r}: unknown config "
                                    f"{w.get('config')!r}")
            if w.get("chips") not in (1, 4):
                raise ManifestError(f"cell {name!r}: chips {w.get('chips')}")

    # -- lookups by name ---------------------------------------------------

    def load_module(self, kind: str, name: str):
        """``benchmark/<kind>/<name>.py`` as a module; unknown is an error."""
        _name(name, kind)
        path = os.path.join(self.dir, kind, name + ".py")
        if not os.path.isfile(path):
            raise ManifestError(f"unknown {kind} module {name!r}: no {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark.{kind}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def config(self, name: str) -> dict:
        entry = self.configs.get(name)
        if entry is None:
            raise ManifestError(f"unknown config {name!r}")
        cfg = _read_json(os.path.join(self.root, entry["file"]))
        cfg["_file"] = entry["file"]
        return cfg

    def traffic(self, name: str) -> dict:
        return _read_json(os.path.join(self.dir, "traffic", name + ".json"))

    def metric_file(self, name: str) -> dict:
        return _read_json(os.path.join(self.dir, "metrics", name + ".json"))

    def peaks(self, device_kind: str) -> dict:
        table = _read_json(os.path.join(self.dir, "trace", "peaks.json"))
        if device_kind not in table:
            raise ManifestError(f"no published peaks for device kind "
                                f"{device_kind!r} in trace/peaks.json")
        return table[device_kind]

    def cell(self, name: str) -> dict:
        """Everything one run of one cell needs, resolved and checked."""
        w = self.workloads.get(name)
        if w is None:
            raise ManifestError(f"unknown workload {name!r}; BENCHMARK.json "
                                f"has {sorted(self.workloads)}")
        config = self.config(w["config"])
        traffic = self.traffic(w["traffic"])
        for what, key, kind in ((config, "driver", "drivers"),
                                (config, "reference", "reference"),
                                (traffic, "generator", "traffic")):
            if not os.path.isfile(os.path.join(
                    self.dir, kind, str(what.get(key)) + ".py")):
                raise ManifestError(
                    f"cell {name!r}: unknown {key} {what.get(key)!r}")
        metrics = {"end_to_end": [], "per_layer": []}
        for mname, m in self.metrics.items():
            if "workloads" in m and name not in m["workloads"]:
                continue
            spec = self.metric_file(mname)
            if not os.path.isfile(os.path.join(
                    self.dir, "readers", str(spec.get("reader")) + ".py")):
                raise ManifestError(f"metric {mname!r}: unknown reader "
                                    f"{spec.get('reader')!r}")
            metrics[m["kind"]].append(dict(m, **spec))
        return {"name": name, "chips": w["chips"], "config": config,
                "traffic": traffic, "metrics": metrics}


def compute_metrics(manifest: Manifest, specs: List[dict], ctx: dict) -> dict:
    """``{name: {"value", "unit"}}`` for the metrics whose reader found
    something to read; a reader that returns None leaves its metric out."""
    out = {}
    for spec in specs:
        reader = manifest.load_module("readers", spec["reader"])
        value: Optional[float] = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out
