"""Find a cell's pieces by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own, found from the names in
``BENCHMARK.json``:

  * configuration: the ``file`` its entry names (``bench/configs/``);
  * traffic mix: ``bench/traffic/<traffic>.json``, data only; its
    ``generator`` names a family ``bench/families/<generator>.py``
    (``rates``) and its ``job`` a job kind ``bench/kinds/<job>.py``
    (the timed call, its inputs and summary, the reference's view of
    the same job, the compared numbers and the end-to-end values);
  * limits of the correctness comparison: ``bench/limits/<cell>.json``;
  * per-layer metric: ``bench/metrics/<metric>.py``, whose ``read(rec)``
    returns the value or None when the run holds nothing to read;
  * peaks: ``bench/peaks.json``, keyed by JAX's ``device_kind``.

Modules are loaded once per path, so a test can patch the one the
harness runs.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from types import ModuleType
from typing import Dict

from bench import gen

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _json(root: str, *parts: str) -> dict:
    path = os.path.join(root, *parts)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"missing benchmark file {path}")
    with open(path) as fh:
        return json.load(fh)


def config(man: dict, name: str, root: str = ROOT) -> dict:
    """A configuration as run, with its carbon knots attached."""
    entry = next((c for c in man["configs"] if c["name"] == name), None)
    if entry is None:
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")
    cfg = _json(root, entry["file"])
    cfg["carbon_points"] = gen.carbon_points(
        cfg["carbon"]["shape"], float(cfg["carbon"]["mean_kg_per_kwh"]))
    return cfg


_MODULES: Dict[str, ModuleType] = {}


def module(kind: str, name: str, root: str = ROOT) -> ModuleType:
    """``bench/<kind>/<name>.py``, loaded once."""
    path = os.path.join(root, "bench", kind, name + ".py")
    if path not in _MODULES:
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no module {path} for {name!r}")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def family(name: str, root: str = ROOT) -> ModuleType:
    return module("families", name, root)


def job_kind(name: str, root: str = ROOT) -> ModuleType:
    return module("kinds", name, root)


def cell(name: str, root: str = ROOT) -> dict:
    """{"cell", "config", "traffic", "limits", "manifest", "family",
    "kind"} of a cell."""
    man = load(root)
    wl = next((w for w in man["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in man['workloads']]}")
    traffic = _json(root, "bench", "traffic", wl["traffic"] + ".json")
    return {"cell": wl, "manifest": man,
            "config": config(man, wl["config"], root),
            "traffic": traffic,
            "limits": _json(root, "bench", "limits", name + ".json"),
            "family": family(traffic["generator"], root),
            "kind": job_kind(traffic["job"], root)}


def peaks(kind: str, root: str = ROOT) -> dict:
    table = _json(root, "bench", "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def metric_reader(name: str, root: str = ROOT):
    return module("metrics", name, root).read


def cell_metrics(man: dict, workload: str, kind: str) -> list:
    """The end-to-end or per-layer metric entries a cell reports."""
    out = []
    for m in man[kind]:
        cells = m.get("workloads")
        if cells is None or workload in cells:
            out.append(m)
    return out
