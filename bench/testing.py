"""Helpers for the benchmark's own tests: a copy of the benchmark in a
temporary directory, cut to a size the CPU runs in seconds."""
from __future__ import annotations

import json
import os
import shutil

from bench import manifest

TINY = {"fleet": "2xh100+2xa100+2xl40s", "n_routes": 12,
        "horizon_s": 6 * 3600.0}


def tiny_root(path: str) -> str:
    """Copy BENCHMARK.json and bench/ to ``path`` with every
    configuration cut to six devices, 12 routes and 6 h."""
    src = manifest.ROOT
    shutil.copytree(os.path.join(src, "bench"), os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(src, "BENCHMARK.json"), path)
    for c in manifest.load(path)["configs"]:
        f = os.path.join(path, c["file"])
        with open(f) as fh:
            cfg = json.load(fh)
        cfg.update(TINY)
        with open(f, "w") as fh:
            json.dump(cfg, fh)
    return path
