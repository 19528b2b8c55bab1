"""Mega-fleet day: 600 GPUs, a million requests, three bad days.

The event-driven fleet simulator prices every request at Python speed;
this example uses the vectorized mega simulator (fleet/mega/, see
docs/SCALE.md) to replay production-shaped days over a 600-device
mixed estate in seconds -- and shows what each day shape does to the
parking tax.

Three synthetic days, all seeded and reproducible:

  * flash-crowd      one route goes viral for 30 minutes at 1pm
  * product-launch   a new model is public at 9am (zero traffic before)
  * regional-outage  an upstream region is dark 11am-noon, then the
                     deferred demand slams back

First, though, the anchor that makes the speed trustworthy: on the
pinned 10-model x 6-GPU day, run_mega reproduces run_fleet's joules
bit-for-bit (tests/test_mega.py pins this; here we just print it).

The closer repeats one day on the compiled backend
(run_mega(backend="jax"), see docs/SCALE.md): same decisions, same
joules, bulk arithmetic jit-compiled -- then sweeps a batch of seeded
days through run_mega_sweep so the compiles amortize across points.

Run:  PYTHONPATH=src python examples/mega_day.py

On a TPU the compiled backend runs on the chip (its metering kernel
compiled by Mosaic); anywhere else JAX falls back to its CPU backend
with the kernel interpreted -- same arithmetic, no device timings.
"""
import time

from repro.compile_cache import use_compile_cache
from repro.core.scheduler import Breakeven
from repro.fleet import (flash_crowd, make_trace, mixed_fleet_scenario,
                         product_launch, regional_outage, run_fleet,
                         run_mega, run_mega_sweep)
from repro.kernels.segment_trapz import CARBON_REL

SEED = 100
FLEET = "200xh100+200xa100+200xl40s"


def main() -> None:
    use_compile_cache()
    # -- the anchor: same day, both simulators, same joules ------------
    t0 = time.perf_counter()
    ref = run_fleet(mixed_fleet_scenario(Breakeven, "warm-first",
                                         seed=SEED))
    t_ref = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = run_mega(mixed_fleet_scenario(Breakeven, "warm-first",
                                        seed=SEED))
    t_mega = time.perf_counter() - t0
    print("== anchor: pinned 10-model x 6-GPU day ==")
    print(f"   event loop  {ref.energy_wh:12.3f} Wh   {t_ref:6.2f} s")
    print(f"   mega        {got.energy_wh:12.3f} Wh   {t_mega:6.2f} s"
          f"   ({t_ref / t_mega:.1f}x)")
    assert got.energy_wh == ref.energy_wh
    assert got.requests == ref.requests

    # -- three production-shaped mega days -----------------------------
    print(f"\n== mega days: 600 routes on {FLEET} ==")
    print(f"   {'day':16s} {'requests':>10s} {'kWh':>8s} {'cold':>6s}"
          f" {'tax kWh':>8s} {'p99_s':>6s} {'wall_s':>7s}")
    for gen in (flash_crowd, product_launch, regional_outage):
        trace = gen(n_routes=600, fleet=FLEET, seed=SEED,
                    base_rate_hr=130.0)
        t0 = time.perf_counter()
        res = run_mega(trace.to_scenario(Breakeven), compute_bound=False)
        wall = time.perf_counter() - t0
        print(f"   {trace.name:16s} {res.requests:10,d}"
              f" {res.energy_wh / 1e3:8.1f} {res.cold_starts:6d}"
              f" {res.parking_tax_wh / 1e3:8.1f}"
              f" {res.p99_added_latency_s:6.1f} {wall:7.1f}")

    print("\n   (same physics as run_fleet -- the anchor above is the "
          "proof -- at ~50k simulated requests/second)")

    # -- the compiled backend ------------------------------------------
    # Price the flash-crowd day against a shaped carbon trace -- the
    # setting where the numpy bulk path pays a per-segment Python
    # integral and the jax backend's compiled programs (including the
    # kernels/segment_trapz metering kernel) earn their keep.
    ct = make_trace("solar-duck", 0.39)
    trace = flash_crowd(n_routes=600, fleet=FLEET, seed=SEED,
                        base_rate_hr=130.0)
    print("\n== compiled backend: flash-crowd day, solar-duck carbon ==")
    results = {}
    for backend in ("numpy", "jax"):
        t0 = time.perf_counter()
        res = run_mega(trace.to_scenario(Breakeven, carbon_trace=ct),
                       compute_bound=False, backend=backend)
        wall = time.perf_counter() - t0
        bulk = res.phase_timings["bulk_scan_s"]
        results[backend] = res
        print(f"   {backend:6s} {res.energy_wh / 1e3:8.1f} kWh"
              f" {res.carbon_kg:8.1f} kgCO2e"
              f"   bulk {bulk:5.1f} s   wall {wall:5.1f} s")
    assert results["jax"].requests == results["numpy"].requests
    # carbon runs through the f32 metering kernel: its derived bound
    assert abs(results["jax"].carbon_kg - results["numpy"].carbon_kg) \
        <= CARBON_REL * results["numpy"].carbon_kg

    # -- sweep: compile once, run the batch hot ------------------------
    n_pts = 8
    t0 = time.perf_counter()
    pts = run_mega_sweep(seeds=range(n_pts), generator="flash-crowd",
                         n_routes=24, fleet="2xh100+2xa100+2xl40s",
                         horizon_s=6 * 3600.0, base_rate_hr=40.0,
                         scenario_kw=dict(carbon_trace=ct))
    wall = time.perf_counter() - t0
    taxes = [p.parking_tax_wh / 1e3 for p in pts]
    print(f"\n== sweep: {n_pts} seeded 6 h days in {wall:.1f} s "
          f"({n_pts / wall:.1f} pts/s) ==")
    print(f"   parking tax {min(taxes):.2f}-{max(taxes):.2f} kWh per day"
          f" (seed spread on one compiled program)")


if __name__ == "__main__":
    main()
