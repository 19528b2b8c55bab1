"""Job kind ``serve-day``: one call of ``run_mega(backend="jax")`` on one
seeded day whose requests take service time, timed until its
``FleetResult`` is on the host.

The job kind ``day`` with three differences:

  * the scenario serves: ``RooflineServiceTime`` at the traffic's
    request shape (``prompt_tokens``, ``output_tokens``) and the
    configuration's ``mfu`` and ``overhead_s``, with ``max_batch``
    decode slots a replica;
  * the reference is ``bench/reference_serve.py``, and the compared
    numbers add ``slot_waits``, the requests that took a slot at a
    completion (the program's counter ``serve.slot_waits``);
  * a job's record keeps the program's event-loop and service-path
    seconds and its service counters, for the per-layer metrics.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

from bench import gen, manifest
from bench import reference as plain
from bench import reference_serve as plain_serve

_day = manifest.job_kind("day", os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
traced, end_to_end = _day.traced, _day.end_to_end

NUMBERS = _day.NUMBERS + ("slot_waits",)
COUNTERS = ("serve.admissions", "serve.slot_waits", "serve.completions")


def inputs(seeds, c):
    """What the program is handed for one job (built off the clock)."""
    from repro.serving.service_model import (RequestShape,
                                             RooflineServiceTime)
    config, traffic = c["config"], c["traffic"]
    svc = RooflineServiceTime(
        RequestShape(int(traffic["prompt_tokens"]),
                     int(traffic["output_tokens"])),
        mfu=float(config["mfu"]), overhead_s=float(config["overhead_s"]))
    return [dataclasses.replace(sc, service_model=svc,
                                max_batch=int(config["max_batch"]))
            for sc in _day.inputs(seeds, c)]


def call(scenarios):
    """The timed call: the program's FleetResults, on the host."""
    return _day.call(scenarios)


def summarize(res) -> dict:
    """``day``'s numbers of one simulated day, with the service path's
    seconds and counters."""
    out = _day.summarize(res)
    pt, ct = res.phase_timings, res.counters
    out.update(event_loop_s=float(pt.get("event_loop_s", 0.0)),
               serve_s=float(pt.get("serve_s", 0.0)),
               slot_waits=int(ct.get("serve.slot_waits", -1)),
               counters={k: int(ct.get(k, 0)) for k in COUNTERS})
    return out


def record(sums) -> dict:
    """What the window keeps of one job for the per-layer metrics."""
    out = _day.record(sums)
    out.update(event_loop_s=sum(s["event_loop_s"] for s in sums),
               serve_s=sum(s["serve_s"] for s in sums),
               counters={k: sum(s["counters"][k] for s in sums)
                         for k in COUNTERS})
    return out


def reference(seeds, c, dtype=np.float64):
    """The reference's (or, in float32, the control's) numbers for each
    day of a job."""
    out = []
    for s in seeds:
        routes = gen.day_routes(s, c["config"], c["traffic"], c["family"])
        if np.dtype(dtype) != np.float64:
            routes = [(r, np.asarray(a, dtype=dtype).astype(np.float64), ck)
                      for r, a, ck in routes]
        raw = plain_serve.simulate(routes, c["config"], c["traffic"])
        out.append(dict(plain.account(raw, c["config"], dtype),
                        slot_waits=raw["slot_waits"]))
    return out


def gaps(prog: dict, ref: dict) -> dict:
    """``day``'s compared numbers and the count of slot waits."""
    return dict(_day.gaps(prog, ref),
                slot_waits=float(abs(prog["slot_waits"]
                                     - ref["slot_waits"])))
