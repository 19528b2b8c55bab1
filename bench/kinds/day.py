"""Job kind ``day``: one call of ``run_mega(backend="jax")`` on one
seeded day, timed until its ``FleetResult`` is on the host.

The benchmark generates the day (``gen.day_routes``) and builds its
``FleetScenario`` off the clock; the reference regenerates the same
day from the same seed.  ``summarize`` keeps, of each ``FleetResult``,
only the numbers the comparison and the per-layer metrics read, so the
results can be freed before the reference runs.  The work of a job is
its simulated requests; the end-to-end rate is all of them over all
the measured time.
"""
from __future__ import annotations

import numpy as np

from bench import check, gen
from bench import reference as plain

# the compared numbers, in the order they are printed
NUMBERS = ("requests", "cold_starts", "waits", "wait_rel", "energy_rel",
           "device_energy_rel", "cost_rel", "latency_rel", "p99_rel",
           "carbon_rel", "device_carbon_rel", "timeline_rel")


def inputs(seeds, c):
    """What the program is handed for one job (built off the clock)."""
    from repro.core.scheduler import Breakeven
    from repro.fleet.carbon import CarbonTrace
    from repro.fleet.mega.traces import FleetTrace, RouteTrace

    config, traffic = c["config"], c["traffic"]
    trace = CarbonTrace(config["carbon"]["shape"],
                        tuple(config["carbon_points"]))
    scenarios = []
    for s in seeds:
        ft = FleetTrace(name=traffic["generator"], fleet=config["fleet"],
                        horizon_s=float(config["horizon_s"]),
                        routes=tuple(RouteTrace(r, a, ck) for r, a, ck in
                                     gen.day_routes(s, config, traffic,
                                                    c["family"])),
                        seed=s)
        scenarios.append(ft.to_scenario(
            Breakeven, config["router"], carbon_trace=trace,
            zone=config["zone"], price_tier=config["price_tier"]))
    return scenarios


def call(scenarios):
    """The timed call: the program's FleetResults, on the host."""
    from repro.fleet import run_mega
    return [run_mega(sc, compute_bound=False, backend="jax")
            for sc in scenarios]


def summarize(res) -> dict:
    """The numbers of one simulated day that the check and the per-layer
    metrics read."""
    lat = np.asarray(res.latencies_s, dtype=np.float64)
    return {
        "requests": int(res.requests),
        "cold_starts": int(res.cold_starts),
        "waits": np.sort(lat[lat > 0.0]),
        "energy_wh": float(res.energy_wh),
        "device_energy_wh": {r.instance_id: float(r.energy_wh["total"])
                             for r in res.devices},
        "cost_usd": float(res.cost_usd),
        "latency_total_s": float(res.added_latency_s_total),
        "p99_s": float(res.p99_added_latency_s),
        "carbon_kg": float(res.carbon_kg),
        "device_carbon_kg": {r.instance_id: float(r.carbon_kg)
                             for r in res.devices},
        "timeline_kg": np.array([c for _, c in res.carbon_timeline]),
        "bulk_s": float(res.phase_timings.get("bulk_scan_s", 0.0)),
    }


def record(sums) -> dict:
    """What the window keeps of one job for the per-layer metrics."""
    return {"requests": sum(s["requests"] for s in sums),
            "bulk_s": sum(s["bulk_s"] for s in sums)}


def traced(results, c) -> dict:
    """What the kernel's roofline reads of the traced job: the metered
    power segments ([n, 2] bounds per day) and the carbon knots."""
    return {"traced_segments": [
                np.asarray(r.power_timeline,
                           dtype=np.float64).reshape(-1, 3)[:, :2]
                for r in results],
            "carbon_points": c["config"]["carbon_points"],
            "carbon_period_s": gen.DAY_S}


def end_to_end(jobs, measured_s: float) -> dict:
    """Simulated requests of every job over all the measured time."""
    return {"sim_req_per_s": sum(j["requests"] for j in jobs) / measured_s}


def reference(seeds, c, dtype=np.float64):
    """The reference's (or, in float32, the control's) numbers for each
    day of a job."""
    out = []
    for s in seeds:
        routes = gen.day_routes(s, c["config"], c["traffic"], c["family"])
        if np.dtype(dtype) != np.float64:
            routes = [(r, np.asarray(a, dtype=dtype).astype(np.float64), ck)
                      for r, a, ck in routes]
        out.append(plain.account(plain.simulate(routes, c["config"]),
                                 c["config"], dtype))
    return out


def gaps(prog: dict, ref: dict) -> dict:
    """Every compared number of one day.  Billed seconds are held
    through ``cost_rel``: the dollars are the on-demand rate times the
    billed hours plus the energy's."""
    return {
        "requests": float(abs(prog["requests"] - ref["requests"])),
        "cold_starts": float(abs(prog["cold_starts"] - ref["cold_starts"])),
        "waits": float(abs(len(prog["waits"]) - len(ref["waits"]))),
        "wait_rel": check.rel(prog["waits"], ref["waits"]),
        "energy_rel": check.rel(prog["energy_wh"], ref["energy_wh"]),
        "device_energy_rel": check.per_key(prog["device_energy_wh"],
                                           ref["device_energy_wh"]),
        "cost_rel": check.rel(prog["cost_usd"], ref["cost_usd"]),
        "latency_rel": check.rel(prog["latency_total_s"],
                                 ref["latency_total_s"]),
        "p99_rel": check.rel(prog["p99_s"], ref["p99_s"]),
        "carbon_rel": check.rel(prog["carbon_kg"], ref["carbon_kg"]),
        "device_carbon_rel": check.per_key(prog["device_carbon_kg"],
                                           ref["device_carbon_kg"]),
        "timeline_rel": check.rel(prog["timeline_kg"], ref["timeline_kg"]),
    }
