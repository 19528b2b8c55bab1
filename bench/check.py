"""The comparison that decides ``correct``.

A job kind (``bench/kinds/<job>.py``) names the numbers it compares
(``NUMBERS``) and reads them off one program answer and the reference's
(``gaps``), with the helpers below.  A job's reading of a number is its
worst answer.  Counts must be equal (their limit is 0); the rest are
relative gaps, ``|got - want| / |want|`` (absolute where ``want`` is
0), worst over the elements of a vector.  The limits, one per number,
are in ``bench/limits/<cell>.json``; ``PERF.md`` gives the readings
each was set from.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def rel(got, want) -> float:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape:
        return float("inf")
    if got.size == 0:
        return 0.0
    d = np.abs(got - want)
    scale = np.abs(want)
    return float(np.max(np.where(scale > 0, d / np.where(scale > 0, scale,
                                                         1.0), d)))


def per_key(got: dict, want: dict) -> float:
    """``rel`` over the values of two dicts with the same keys."""
    if set(got) != set(want):
        return float("inf")
    keys = sorted(want)
    return rel([got[k] for k in keys], [want[k] for k in keys])


def worst(readings: List[Dict[str, float]], numbers: Sequence[str]
          ) -> Dict[str, float]:
    """The worst reading of each number over several answers."""
    return {k: max(r[k] for r in readings) for k in numbers}


def verdict(reading: Dict[str, float], limits: Dict[str, float],
            numbers: Sequence[str]) -> Dict[str, list]:
    """{number: [reading, limit]} in a fixed order; every number has a
    limit, and a missing or non-finite reading fails."""
    missing = [k for k in numbers if k not in limits]
    if missing:
        raise KeyError(f"limits file lacks {missing}")
    return {k: [reading.get(k, float("inf")), float(limits[k])]
            for k in numbers}


def passed(table: Dict[str, list]) -> bool:
    return all(np.isfinite(v) and v <= lim for v, lim in table.values())
