"""Fleet bench: cluster-scale parking tax across heterogeneous GPUs.

The headline table of the fleet subsystem: a mixed H100/A100/L40S fleet
serving 10 models under a diurnal + bursty + heavy-tail traffic mix,
comparing always-on warm-everywhere against routing x eviction x
consolidation, with the clairvoyant lower bound as the floor.

Run standalone:  PYTHONPATH=src python -m benchmarks.bench_fleet [--fast]
(--fast is the CI smoke mode: 4 models x 3 devices x 6 h.)
"""
from __future__ import annotations

import sys

import math
import time

from benchmarks.common import emit
from repro.core.scheduler import AlwaysOn, Breakeven
from repro.fleet import (CarbonAwareRouter, CarbonBreakeven, Consolidator,
                         MIXES, ReplicaAutoscaler, SLOAwareRouter,
                         flash_crowd, mixed_fleet_scenario, run_fleet,
                         run_mega, trace_for_zone)
from repro.serving import RooflineServiceTime

SLO_BUDGET_S = 90.0
# every scenario below derives its traffic from this seed, so bench
# numbers are reproducible run-to-run (deflake contract)
SEED = 100


def _floor_kg(res) -> float:
    """Bare-idle floor of the fleet's emissions under the bench's trace.

    The floor is sum(p_base) integrated over the run's intensity curve
    -- the part of kgCO2e no scheduler can move while the devices stay
    powered.  The delta carbon-aware scheduling CAN win lives in
    (total - floor).  Integrated over the ACTUAL horizon: a partial-day
    window does not average the trace to its daily mean (the 6 h fast
    smoke sits on the morning shoulder at ~0.41, not 0.39)."""
    from repro.fleet import get_mix, get_sku, make_trace
    p_base = sum(get_sku(d.sku).profile.p_base_w for d in res.devices)
    trace = make_trace("solar-duck", get_mix("USA").gwp_kg_per_kwh)
    return trace.carbon_kg(p_base, 0.0, res.horizon_s)


def run_all(fast: bool = False, seed: int = SEED) -> None:
    kw = dict(n_models=4, fleet="h100+a100+l40s", horizon_s=6 * 3600.0,
              seed=seed) if fast else dict(seed=seed)
    tag = "fleet6h" if fast else "fleet24h"
    base = run_fleet(mixed_fleet_scenario(AlwaysOn, "warm-first",
                                          consolidate=False, **kw))
    print(f"== Fleet ({'fast smoke' if fast else '10 models x 6 GPUs, 24 h'};"
          f" {base.requests} requests) ==")
    hdr = (f"   {'configuration':38s} {'Wh':>9s} {'save%':>6s} {'cold':>5s}"
           f" {'migr':>5s} {'req/s':>6s} {'p99_s':>7s}")
    print(hdr)

    def report(name: str, res) -> None:
        save = 100.0 * res.savings_vs(base)
        print(f"   {name:38s} {res.energy_wh:9.1f} {save:6.1f}"
              f" {res.cold_starts:5d} {res.migrations:5d}"
              f" {res.requests_per_s:6.3f} {res.p99_added_latency_s:7.2f}")
        emit(f"{tag}.{name}.wh", f"{res.energy_wh:.1f}")
        emit(f"{tag}.{name}.savings_pct", f"{save:.1f}")
        emit(f"{tag}.{name}.cold_starts", str(res.cold_starts))
        emit(f"{tag}.{name}.mean_added_latency_s",
             f"{res.mean_added_latency_s:.2f}")
        emit(f"{tag}.{name}.requests_per_s", f"{res.requests_per_s:.3f}")
        emit(f"{tag}.{name}.p99_added_latency_s",
             f"{res.p99_added_latency_s:.2f}")

    report("always-on_warm-everywhere", base)
    for router in ("warm-first", "least-loaded", "energy-greedy",
                   "breakeven-aware"):
        for cons in (False, True):
            name = f"breakeven_{router}" + ("_consolidate" if cons else "")
            report(name, run_fleet(mixed_fleet_scenario(
                Breakeven, router, consolidate=cons, **kw)))
    report("always-on_consolidate", run_fleet(mixed_fleet_scenario(
        AlwaysOn, "warm-first", consolidate=True, **kw)))

    # concurrent serving: roofline service times (occupancy-dependent),
    # loads overlapping decode, and the energy/latency Pareto the
    # SLO-aware router trades along
    svc = RooflineServiceTime()
    print("   -- concurrent serving (roofline service times, "
          f"max_batch=4, SLO budget {SLO_BUDGET_S:.0f} s) --")
    report("svc_always-on_warm-first", run_fleet(mixed_fleet_scenario(
        AlwaysOn, "warm-first", service_model=svc, **kw)))
    eg_svc = run_fleet(mixed_fleet_scenario(
        Breakeven, "energy-greedy", service_model=svc, **kw))
    report("svc_breakeven_energy-greedy", eg_svc)
    slo_single = run_fleet(mixed_fleet_scenario(
        Breakeven, SLOAwareRouter(SLO_BUDGET_S), service_model=svc, **kw))
    report("svc_breakeven_slo-aware", slo_single)

    # replica auto-scaling: the headline the paper's framing demands --
    # what does a unit of p99 improvement COST in over-provisioned
    # warm-replica energy?
    # fast smoke traffic is too sparse for the default thresholds --
    # use a hair-trigger controller there so the path still exercises
    scaler = ReplicaAutoscaler(tick_s=30.0, pressure_hi=0.25,
                               pressure_lo=0.1, cooldown_s=120.0) \
        if fast else ReplicaAutoscaler()
    slo_auto = run_fleet(mixed_fleet_scenario(
        Breakeven, SLOAwareRouter(SLO_BUDGET_S), service_model=svc,
        autoscaler=scaler, **kw))
    report("svc_breakeven_slo-aware_autoscaled", slo_auto)
    d_wh = slo_auto.energy_wh - slo_single.energy_wh
    d_p99 = slo_single.p99_added_latency_s - slo_auto.p99_added_latency_s
    tax = slo_auto.parking_tax_wh - slo_single.parking_tax_wh
    wh_per_p99 = d_wh / d_p99 if d_p99 > 0 else float("inf")
    print(f"   -- autoscaler: {slo_auto.scale_outs} scale-outs /"
          f" {slo_auto.scale_ins} scale-ins, peak"
          f" {slo_auto.peak_replicas()} replicas --")
    print(f"   over-provisioning parking tax {tax:+9.1f} Wh, p99"
          f" {d_p99:+.2f} s better => {wh_per_p99:.1f} Wh per p99-second")
    emit(f"{tag}.autoscale.overprovision_tax_wh", f"{tax:.1f}")
    emit(f"{tag}.autoscale.energy_delta_wh", f"{d_wh:.1f}")
    emit(f"{tag}.autoscale.p99_improvement_s", f"{d_p99:.2f}")
    emit(f"{tag}.autoscale.wh_per_p99_s", f"{wh_per_p99:.1f}")
    emit(f"{tag}.autoscale.peak_replicas", str(slo_auto.peak_replicas()))

    # carbon-intensity-aware scheduling: the same day under a solar-duck
    # grid trace.  kgCO2e is a trace INTEGRAL over the metered power
    # timeline, so the flat-trace rows match the scalar accounting and
    # the duck rows price WHEN each joule was drawn.  The carbon stack
    # (carbon-breakeven eviction + carbon routing + carbon-aware
    # consolidation) must cut kgCO2e vs energy-greedy at equal-or-better
    # p99 (the acceptance row); the budgeted variants trace the
    # carbon/latency Pareto.
    print("   -- carbon (solar-duck trace, daily mean = USA 0.39 "
          "kgCO2e/kWh) --")
    ckw = dict(service_model=svc, carbon_trace="solar-duck", **kw)
    eg_c = run_fleet(mixed_fleet_scenario(Breakeven, "energy-greedy",
                                          **ckw))
    carbon_runs = [("carbon_energy-greedy", eg_c)]
    for label, budget in (("carbon-aware_b90", SLO_BUDGET_S),
                          ("carbon-greedy", math.inf)):
        res = run_fleet(mixed_fleet_scenario(
            CarbonBreakeven, CarbonAwareRouter(budget),
            consolidate=Consolidator(carbon_aware=True, period_s=300.0),
            **ckw))
        carbon_runs.append((f"carbon_{label}", res))
    for name, res in carbon_runs:
        print(f"   {name:38s} {res.energy_wh:9.1f} {'':6s}"
              f" {res.cold_starts:5d} {res.migrations:5d}"
              f" {res.requests_per_s:6.3f} {res.p99_added_latency_s:7.2f}"
              f"   {res.carbon_kg:.4f} kg")
        emit(f"{tag}.carbon.{name}.kg", f"{res.carbon_kg:.4f}")
        emit(f"{tag}.carbon.{name}.wh", f"{res.energy_wh:.1f}")
        emit(f"{tag}.carbon.{name}.p99_added_latency_s",
             f"{res.p99_added_latency_s:.2f}")
    cg = carbon_runs[-1][1]
    d_kg = eg_c.carbon_kg - cg.carbon_kg
    sched_kg = eg_c.carbon_kg - _floor_kg(eg_c)
    print(f"   -- carbon-aware vs energy-greedy: {d_kg:+.4f} kg "
          f"({100 * cg.carbon_savings_vs(eg_c):.2f}% of total, "
          f"{100 * d_kg / sched_kg if sched_kg > 0 else 0:.1f}% of "
          f"schedulable) at p99 {cg.p99_added_latency_s:.1f} vs "
          f"{eg_c.p99_added_latency_s:.1f} s --")
    emit(f"{tag}.carbon.delta_kg", f"{d_kg:.4f}")
    emit(f"{tag}.carbon.delta_pct", f"{100 * cg.carbon_savings_vs(eg_c):.2f}")
    emit(f"{tag}.carbon.schedulable_kg", f"{sched_kg:.4f}")
    # zone sweep: re-price the SAME schedule on each zone's preset trace
    # (carbon is a post-hoc integral over the recorded power timeline)
    for zone in sorted(MIXES):
        kg = cg.carbon_with(trace_for_zone(zone))
        emit(f"{tag}.carbon.zone.{zone}.kg", f"{kg:.4f}")

    # per-device zones + follow-the-sun: the SAME day on a geo-split
    # fleet (each device priced on its zone's local-time trace), with
    # zone-aware cold placement/consolidation vs the zone-blind router.
    # The delta is what knowing WHERE (not just when) each joule is
    # drawn buys at the same p99 budget.
    zfleet = "h100@DEU+a100@USA+l40s@IND" if fast \
        else "2xh100@DEU+2xa100@USA+2xl40s@IND"
    zkw = dict(kw, fleet=zfleet, carbon_trace="zone", zone="USA")
    print(f"   -- zones: follow-the-sun on {zfleet} --")
    zruns = {}
    for label, aware in (("follow-the-sun", True), ("zone-blind", False)):
        res = run_fleet(mixed_fleet_scenario(
            CarbonBreakeven, CarbonAwareRouter(math.inf, zone_aware=aware),
            consolidate=Consolidator(carbon_aware=True, period_s=300.0),
            **zkw))
        zruns[label] = res
        per_zone = " ".join(f"{z}={kg:.4f}"
                            for z, kg in sorted(res.zone_carbon_kg.items()))
        print(f"   {'zones_' + label:38s} {res.energy_wh:9.1f} {'':6s}"
              f" {res.cold_starts:5d} {res.migrations:5d}"
              f" {res.requests_per_s:6.3f} {res.p99_added_latency_s:7.2f}"
              f"   {res.carbon_kg:.4f} kg [{per_zone}]")
        emit(f"{tag}.zones.{label}.kg", f"{res.carbon_kg:.4f}")
        emit(f"{tag}.zones.{label}.wh", f"{res.energy_wh:.1f}")
        emit(f"{tag}.zones.{label}.p99_added_latency_s",
             f"{res.p99_added_latency_s:.2f}")
        emit(f"{tag}.zones.{label}.migrations", str(res.migrations))
        emit(f"{tag}.zones.{label}.cross_zone_migrations",
             str(res.cross_zone_migrations))
        emit(f"{tag}.zones.{label}.transfer_wh", f"{res.transfer_wh:.2f}")
        for z, zkg in sorted(res.zone_carbon_kg.items()):
            emit(f"{tag}.zones.{label}.zone.{z}.kg", f"{zkg:.4f}")
    fts, blind = zruns["follow-the-sun"], zruns["zone-blind"]
    zd_kg = blind.carbon_kg - fts.carbon_kg
    print(f"   -- follow-the-sun vs zone-blind: {zd_kg:+.4f} kg "
          f"({100 * fts.carbon_savings_vs(blind):.2f}%) at p99 "
          f"{fts.p99_added_latency_s:.1f} vs "
          f"{blind.p99_added_latency_s:.1f} s --")
    emit(f"{tag}.zones.delta_kg", f"{zd_kg:.4f}")
    emit(f"{tag}.zones.delta_pct",
         f"{100 * fts.carbon_savings_vs(blind):.2f}")

    # device power gating: the first mechanism that cuts BELOW p_base.
    # The consolidator's packing drains devices; gate_drained_devices
    # then puts them to SLEEP past the wake-energy breakeven, and the
    # SLO router prices wake latency+energy into cold placement so the
    # p99 budget still holds.  Acceptance: total Wh strictly below the
    # best non-gated policy at p99 within the budget.
    print("   -- device power gating (sleep/wake state machine, "
          f"SLO budget {SLO_BUDGET_S:.0f} s) --")
    # baseline: best non-gated policy under the SAME service model
    # (a service-free run would mix energy bases), INCLUDING a
    # consolidated one -- so the saved_vs row isolates what gating adds
    # on top of packing, not packing itself
    eg_svc_cons = run_fleet(mixed_fleet_scenario(
        Breakeven, "energy-greedy", consolidate=True, service_model=svc,
        **kw))
    report("svc_breakeven_energy-greedy_consolidate", eg_svc_cons)
    nongated = min((eg_svc, eg_svc_cons, slo_single),
                   key=lambda r: r.energy_wh)
    gate_cons = Consolidator(period_s=300.0, gate_drained_devices=True)
    gated = run_fleet(mixed_fleet_scenario(
        Breakeven, SLOAwareRouter(SLO_BUDGET_S), service_model=svc,
        consolidate=gate_cons, **kw))
    report("svc_breakeven_slo-aware_gated", gated)
    sleep_h = gated.state_durations_s.get("sleep", 0.0) / 3600.0
    print(f"   -- gating: {gated.gates} gates / {gated.wakes} wakes, "
          f"{sleep_h:.1f} device-hours asleep, "
          f"{gated.gated_wh_saved:.1f} Wh recovered from the bare-idle "
          f"floor ({gated.energy_wh:.1f} vs best non-gated "
          f"{nongated.energy_wh:.1f} Wh) --")
    emit(f"{tag}.gating.wh", f"{gated.energy_wh:.1f}")
    emit(f"{tag}.gating.best_nongated_wh", f"{nongated.energy_wh:.1f}")
    emit(f"{tag}.gating.saved_vs_best_nongated_wh",
         f"{nongated.energy_wh - gated.energy_wh:.1f}")
    emit(f"{tag}.gating.gated_wh_saved", f"{gated.gated_wh_saved:.1f}")
    emit(f"{tag}.gating.p99_added_latency_s",
         f"{gated.p99_added_latency_s:.2f}")
    emit(f"{tag}.gating.gates", str(gated.gates))
    emit(f"{tag}.gating.wakes", str(gated.wakes))
    emit(f"{tag}.gating.sleep_device_hours", f"{sleep_h:.1f}")
    for state in ("sleep", "bare", "parked", "loading", "active"):
        emit(f"{tag}.gating.state.{state}.wh",
             f"{gated.state_energy_wh.get(state, 0.0):.1f}")

    print(f"   {'clairvoyant non-gated bound':38s}"
          f" {base.lb_nongated_wh:9.1f} {100 * (1 - base.lb_nongated_wh / base.energy_wh):6.1f}")
    print(f"   {'per-model clairvoyant (no sharing)':38s}"
          f" {base.cv_per_model_wh:9.1f}")
    emit(f"{tag}.clairvoyant_lb.wh", f"{base.lb_nongated_wh:.1f}")
    print(f"   infra {base.infra_usd:.0f} USD/day (on-demand), baseline "
          f"energy {base.energy_usd:.2f} USD, {base.carbon_kg:.1f} kgCO2e "
          f"(USA mix; catalog estimates)")

    _run_mega_bench(fast, seed, tag, kw)
    _run_megax_bench(fast, seed, tag)
    _run_pareto_bench(fast, seed, tag)
    _run_plan_bench(fast, seed, tag)


def _run_plan_bench(fast: bool, seed: int, tag: str) -> None:
    """`{tag}.plan.*`: batched vs serial plan_fleet on the 27-point
    tier grid (3 fleets x 3 routers x 3 default tiers of the pinned
    3-zone day) -- wall-clock both ways, throughput, simulation and
    compile counts, and the identity check the batched mode promises
    (point-for-point equal frontiers)."""
    from benchmarks.plan_compare import compare

    print("   -- plan: batched vs serial sweep execution --")
    doc = compare(fast=fast, seed=seed)
    print(f"   {doc['points']} plans: serial {doc['serial']['wall_s']:.2f} s "
          f"({doc['serial']['sims']} sims) vs batched "
          f"{doc['batched']['wall_s']:.2f} s ({doc['batched']['sims']} sims)"
          f" -> {doc['speedup_x']:.2f}x, "
          f"{doc['points_per_s']:.1f} points/s, identical="
          f"{doc['identical']}")
    emit(f"{tag}.plan.points", str(doc["points"]))
    emit(f"{tag}.plan.serial_s", f"{doc['serial']['wall_s']:.2f}",
         us=doc["serial"]["wall_s"] * 1e6)
    emit(f"{tag}.plan.batched_s", f"{doc['batched']['wall_s']:.2f}",
         us=doc["batched"]["wall_s"] * 1e6)
    emit(f"{tag}.plan.speedup_x", f"{doc['speedup_x']:.2f}")
    emit(f"{tag}.plan.points_per_s", f"{doc['points_per_s']:.1f}")
    emit(f"{tag}.plan.sims", str(doc["batched"]["sims"]))
    emit(f"{tag}.plan.compiles", str(doc["warmup"]["compiles"]))
    emit(f"{tag}.plan.identical", str(doc["identical"]))


def _run_pareto_bench(fast: bool, seed: int, tag: str) -> None:
    """`{tag}.pareto.*`: the four-objective fleet planner on the pinned
    3-zone day -- frontier size, the best-cost and best-carbon corner
    points, and the frontier's hypervolume against the all-on-demand
    singleton (0 would mean no plan in the sweep beats always-buying
    on-demand anywhere)."""
    from repro.fleet.planner import pinned_day_axes, pinned_day_base, \
        plan_fleet

    print("   -- pareto: 4-objective fleet planner (cost/energy/carbon/"
          "p99) --")
    horizon = 6 * 3600.0 if fast else 24 * 3600.0
    routers = ("warm-first", "slo-aware") if fast else \
        ("warm-first", "slo-aware", "carbon-aware")
    base = pinned_day_base(horizon_s=horizon, seed=seed)
    axes = pinned_day_axes(routers=routers)
    t0 = time.perf_counter()
    res = plan_fleet(base, axes, backend="numpy" if fast else "jax")
    wall = time.perf_counter() - t0
    ref = res.reference
    best_cost = res.best("cost_usd")
    best_kg = res.best("carbon_kg")
    print(f"   {len(res.points)} plans in {wall:.1f} s -> frontier "
          f"{len(res.frontier)}, hypervolume {res.hypervolume:.4f} vs "
          f"on-demand ${ref.cost_usd:.2f}")
    print(f"   best cost   ${best_cost.cost_usd:8.2f} "
          f"({1 - best_cost.cost_usd / ref.cost_usd:5.0%} under on-demand, "
          f"p99 {best_cost.p99_s:.1f} s)  {best_cost.label()}")
    print(f"   best carbon {best_kg.carbon_kg:9.3f} kg "
          f"(vs {ref.carbon_kg:.3f})  {best_kg.label()}")
    emit(f"{tag}.pareto.plans", str(len(res.points)))
    emit(f"{tag}.pareto.wall_s", f"{wall:.2f}", us=wall * 1e6)
    emit(f"{tag}.pareto.frontier_size", str(len(res.frontier)))
    emit(f"{tag}.pareto.hypervolume", f"{res.hypervolume:.4f}")
    emit(f"{tag}.pareto.best_cost_usd", f"{best_cost.cost_usd:.2f}")
    emit(f"{tag}.pareto.best_cost_p99_s", f"{best_cost.p99_s:.2f}")
    emit(f"{tag}.pareto.best_carbon_kg", f"{best_kg.carbon_kg:.4f}")
    emit(f"{tag}.pareto.on_demand_cost_usd", f"{ref.cost_usd:.2f}")
    emit(f"{tag}.pareto.cost_saving_pct",
         f"{100 * (1 - best_cost.cost_usd / ref.cost_usd):.1f}")


def _run_mega_bench(fast: bool, seed: int, tag: str, kw: dict) -> None:
    """`{tag}.mega.*`: the vectorized simulator's wall-clock story.

    Three legs: (1) speedup vs the event loop on the pinned anchor day
    (same physics, anchored bit-exact in tests/test_mega.py, so the row
    is pure wall-clock); (2) a device-count sweep on generated
    flash-crowd days; (3) full mode only, the ISSUE acceptance -- a
    ~600-device, >1M-request synthetic day, which must complete in
    under 30 s."""
    print("   -- mega: vectorized simulator (trace replay at scale) --")
    sc_kw = {k: v for k, v in kw.items() if k != "seed"}
    t0 = time.perf_counter()
    ref = run_fleet(mixed_fleet_scenario(Breakeven, "warm-first",
                                         seed=seed, **sc_kw))
    t_ref = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = run_mega(mixed_fleet_scenario(Breakeven, "warm-first",
                                        seed=seed, **sc_kw))
    t_mega = time.perf_counter() - t0
    speedup = t_ref / t_mega if t_mega > 0 else float("inf")
    n0 = len(got.devices)
    print(f"   anchor day (n={n0}): event loop {t_ref:.2f} s, mega "
          f"{t_mega:.3f} s => {speedup:.1f}x at {got.energy_wh:.1f} Wh "
          f"(= event loop's {ref.energy_wh:.1f})")
    emit(f"{tag}.mega.speedup.n{n0}", f"{speedup:.1f}")
    emit(f"{tag}.mega.wall_s.n{n0}", f"{t_mega:.3f}", us=t_mega * 1e6)
    emit(f"{tag}.mega.wh.n{n0}", f"{got.energy_wh:.1f}")

    # device-count sweep: generated flash-crowd days, scaled traffic
    sweep = ((6, "2xh100+2xa100+2xl40s", 24),
             (60, "20xh100+20xa100+20xl40s", 80)) if fast else \
            ((6, "2xh100+2xa100+2xl40s", 24),
             (60, "20xh100+20xa100+20xl40s", 80),
             (600, "200xh100+200xa100+200xl40s", 600))
    horizon = 6 * 3600.0 if fast else 24 * 3600.0
    for n_dev, fleet, n_routes in sweep:
        trace = flash_crowd(n_routes=n_routes, fleet=fleet, seed=seed,
                            horizon_s=horizon, base_rate_hr=40.0)
        t0 = time.perf_counter()
        res = run_mega(trace.to_scenario(Breakeven), compute_bound=False)
        wall = time.perf_counter() - t0
        rate = res.requests / wall if wall > 0 else float("inf")
        print(f"   flash-crowd n={n_dev:4d}: {res.requests:8d} requests, "
              f"{res.energy_wh:11.1f} Wh, wall {wall:6.2f} s "
              f"({rate:,.0f} req/s simulated)")
        emit(f"{tag}.mega.wall_s.n{n_dev}", f"{wall:.3f}", us=wall * 1e6)
        emit(f"{tag}.mega.wh.n{n_dev}", f"{res.energy_wh:.1f}")
        emit(f"{tag}.mega.requests.n{n_dev}", str(res.requests))

    if not fast:
        # the ISSUE 6 acceptance row: >=1M-request day, <30 s wall
        trace = flash_crowd(n_routes=600,
                            fleet="200xh100+200xa100+200xl40s",
                            seed=seed, base_rate_hr=130.0, spike_x=60.0)
        t0 = time.perf_counter()
        res = run_mega(trace.to_scenario(Breakeven), compute_bound=False)
        wall = time.perf_counter() - t0
        print(f"   mega day: {res.requests:,} requests on "
              f"{len(res.devices)} devices in {wall:.1f} s "
              f"({res.energy_wh / 1e3:.1f} kWh, "
              f"{res.cold_starts} cold starts)")
        emit(f"{tag}.mega.megaday.requests", str(res.requests))
        emit(f"{tag}.mega.megaday.wall_s", f"{wall:.2f}", us=wall * 1e6)
        emit(f"{tag}.mega.megaday.wh", f"{res.energy_wh:.1f}")


def _run_megax_bench(fast: bool, seed: int, tag: str) -> None:
    """`{tag}.megax.*`: the compiled (jax) bulk-scan backend vs numpy.

    Both backends drive the identical structural event loop (energy
    anchored to <=1e-9, carbon to the f32 kernel's CARBON_REL, in
    tests/test_mega.py), so the rows isolate the
    BULK-SCAN phases -- big-gap scans, deferred billing, energy
    segment-sums, and the carbon trapezoid integral -- which is where
    the jit-compiled array programs (and the fused_meter kernel) do
    their work.  Benched on a solar-duck carbon trace: time-varying
    intensity is the paper's carbon-aware setting, and it is exactly
    where the numpy path pays a per-segment Python integral.  The
    sweep leg shows compile amortization: every compiled program is
    shared across same-shaped points, so point 1 is compile-bound and
    the rest run hot."""
    from repro.fleet import make_trace
    from repro.fleet.mega import run_mega_sweep

    print("   -- megax: compiled (jax) bulk-scan backend --")
    ct = make_trace("solar-duck", 0.39)
    if fast:
        trace = flash_crowd(n_routes=24, fleet="2xh100+2xa100+2xl40s",
                            seed=seed, horizon_s=6 * 3600.0,
                            base_rate_hr=40.0)
    else:
        # the mega-day acceptance trace: ~600 devices, >1M requests
        trace = flash_crowd(n_routes=600,
                            fleet="200xh100+200xa100+200xl40s",
                            seed=seed, base_rate_hr=130.0, spike_x=60.0)
    # first jax run pays the jit compiles; time the warm steady state
    run_mega(trace.to_scenario(Breakeven, carbon_trace=ct),
             compute_bound=False, backend="jax")
    runs = {}
    for backend in ("numpy", "jax"):
        sc = trace.to_scenario(Breakeven, carbon_trace=ct)
        t0 = time.perf_counter()
        res = run_mega(sc, compute_bound=False, backend=backend)
        runs[backend] = (time.perf_counter() - t0, res)
    (w_np, r_np), (w_jx, r_jx) = runs["numpy"], runs["jax"]
    b_np = r_np.phase_timings["bulk_scan_s"]
    b_jx = r_jx.phase_timings["bulk_scan_s"]
    speedup = b_np / b_jx if b_jx > 0 else float("inf")
    drift = abs(r_jx.energy_wh - r_np.energy_wh) / r_np.energy_wh
    print(f"   bulk-scan ({r_np.requests:,} requests, "
          f"{len(r_np.devices)} devices): numpy {b_np:.2f} s, jax "
          f"{b_jx:.2f} s => {speedup:.1f}x (wall {w_np:.1f} vs "
          f"{w_jx:.1f} s; energy drift {drift:.1e})")
    for phase in ("biggap_s", "billing_s", "energy_s", "carbon_s"):
        print(f"      {phase:10s} numpy {r_np.phase_timings[phase]:6.2f} s"
              f"   jax {r_jx.phase_timings[phase]:6.2f} s")
    emit(f"{tag}.megax.bulk_scan.numpy_s", f"{b_np:.3f}", us=b_np * 1e6)
    emit(f"{tag}.megax.bulk_scan.jax_s", f"{b_jx:.3f}", us=b_jx * 1e6)
    emit(f"{tag}.megax.bulk_scan.speedup", f"{speedup:.2f}")
    emit(f"{tag}.megax.wall_s.numpy", f"{w_np:.2f}", us=w_np * 1e6)
    emit(f"{tag}.megax.wall_s.jax", f"{w_jx:.2f}", us=w_jx * 1e6)
    emit(f"{tag}.megax.carbon_s.numpy", f"{r_np.phase_timings['carbon_s']:.3f}")
    emit(f"{tag}.megax.carbon_s.jax", f"{r_jx.phase_timings['carbon_s']:.3f}")

    # vmapped sweep: one compiled trace-generation batch + shared bulk
    # programs across every point
    n_pts = 4 if fast else 24
    skw = dict(n_routes=6, fleet="2xh100+2xa100+2xl40s", base_rate_hr=30.0,
               horizon_s=6 * 3600.0 if fast else 24 * 3600.0,
               scenario_kw=dict(carbon_trace=ct))
    t0 = time.perf_counter()
    results = run_mega_sweep(seeds=range(n_pts), **skw)
    wall = time.perf_counter() - t0
    bulks = [r.phase_timings["bulk_scan_s"] for r in results]
    amort = bulks[0] / bulks[-1] if bulks[-1] > 0 else float("inf")
    print(f"   sweep: {n_pts} points in {wall:.1f} s "
          f"({n_pts / wall:.2f} pts/s); bulk-scan point 1 "
          f"{bulks[0]:.2f} s (compile) -> point {n_pts} {bulks[-1]:.3f} s "
          f"({amort:.0f}x amortized)")
    emit(f"{tag}.megax.sweep.points", str(n_pts))
    emit(f"{tag}.megax.sweep.wall_s", f"{wall:.2f}", us=wall * 1e6)
    emit(f"{tag}.megax.sweep.points_per_s", f"{n_pts / wall:.2f}")
    emit(f"{tag}.megax.sweep.first_bulk_s", f"{bulks[0]:.3f}")
    emit(f"{tag}.megax.sweep.last_bulk_s", f"{bulks[-1]:.3f}")


if __name__ == "__main__":
    from benchmarks.common import print_csv
    run_all(fast="--fast" in sys.argv)
    print_csv()
