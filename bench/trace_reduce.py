"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

From the ``.xplane.pb`` of one traced job:

  * ``window_s``: the length of the traced call into the program, from
    the benchmark's own ``bench.call`` span on the host: the time the
    rate counts, without the input generation before it;
  * ``busy_s``: the union of the intervals inside that window in which
    an operation ran on a device, averaged over the devices that ran any;
  * ``ops``: device seconds per operation name (all events of the op
    line, summed), from which kernel times are read;
  * ``gaps``: the longest idle gaps of the device inside the window,
    each named by the innermost host span running at its middle.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
_OP_LINES = ("XLA Ops",)
CALL_SPAN = "bench.call"


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def reduce_planes(planes, n_gaps: int = 10) -> dict:
    """``planes``: [(name, [(line name, [(event, start_ns, dur_ns)])])],
    the shape ``load`` makes of a trace (tests build it by hand)."""
    per_dev: List[List[Tuple[float, float]]] = []
    ops: Dict[str, float] = {}
    dev_iv: List[Tuple[float, float]] = []
    host: List[Tuple[str, float, float]] = []
    for pname, lines in planes:
        if _DEVICE.match(pname):
            names = {ln for ln, _ in lines}
            use = [ln for ln in _OP_LINES if ln in names] or [
                ln for ln in names if ln not in ("Steps", "XLA Modules")]
            iv = []
            for ln, events in lines:
                if ln not in use:
                    continue
                for name, s, d in events:
                    iv.append((s, s + d))
                    ops[name] = ops.get(name, 0.0) + d * 1e-9
            if iv:
                per_dev.append(_union(iv))
                dev_iv.extend(per_dev[-1])
        elif pname.startswith("/host:"):
            for _, events in lines:
                host.extend((n, s, s + d) for n, s, d in events)
    calls = [(s, e) for n, s, e in host if n == CALL_SPAN]
    if not calls:
        raise ValueError(f"trace holds no {CALL_SPAN!r} span")
    w0, w1 = min(s for s, _ in calls), max(e for _, e in calls)
    busy = [sum(min(b, w1) - max(a, w0) for a, b in iv if b > w0 and a < w1)
            for iv in per_dev]
    busy_iv = _union([(max(a, w0), min(b, w1)) for a, b in dev_iv
                      if b > w0 and a < w1])
    spans = []
    cur = w0
    for a, b in busy_iv + [(w1, w1)]:
        if a > cur:
            spans.append((a - cur, cur, a))
        cur = max(cur, b)
    gaps = []
    for length, a, b in sorted(spans, reverse=True)[:n_gaps]:
        mid = (a + b) / 2
        inner = [(e - s, n) for n, s, e in host if s <= mid <= e]
        gaps.append([min(inner)[1] if inner else "host", length * 1e-9])
    return {"busy_s": sum(busy) / len(busy) * 1e-9 if busy else 0.0,
            "window_s": (w1 - w0) * 1e-9,
            "ops": ops, "gaps": gaps}


def load(path: str):
    """The planes of a trace file in ``reduce_planes``' shape."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for p in pd.planes:
        if not (_DEVICE.match(p.name) or p.name.startswith("/host:")):
            continue
        lines = []
        for ln in p.lines:
            lines.append((ln.name, [(e.name, float(e.start_ns),
                                     float(e.duration_ns))
                                    for e in ln.events]))
        out.append((p.name, lines))
    return out


def breakdown(red: dict) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [list(g) for g in red["gaps"][:10]]}
