"""Four-objective fleet planning on the pinned 3-zone day: sweep fleet
compositions, purchase tiers, routers, and spot preemption rates, then
print the non-dominated (cost, energy, carbon, p99) frontier and its
hypervolume against the all-on-demand plan.

Run:  PYTHONPATH=src python examples/fleet_planner.py [--fast]

--fast shrinks the day to 6 h and uses the numpy replay backend (the
default sweeps the full 24 h day with the jax backend where plans fit
the compiled scope).  --batched (the default) groups grid points that
share dynamics into one simulation each; --serial evaluates every
point on its own.  Passing BOTH runs both modes and prints the
wall-clock comparison (the frontiers are identical point-for-point).
"""
import argparse

from repro.compile_cache import use_compile_cache
from repro.fleet.planner import pinned_day_axes, pinned_day_base, plan_fleet


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="6 h horizon + numpy backend")
    ap.add_argument("--json", action="store_true",
                    help="emit the frontier as JSON instead of a table")
    ap.add_argument("--batched", action="store_true",
                    help="grouped shared-compile execution (default)")
    ap.add_argument("--serial", action="store_true",
                    help="one simulation per grid point")
    args = ap.parse_args()

    base = pinned_day_base(horizon_s=6 * 3600.0 if args.fast else 24 * 3600.0)
    axes = pinned_day_axes(routers=("warm-first", "slo-aware",
                                    "carbon-aware"))
    backend = "numpy" if args.fast else "jax"

    compare = args.batched and args.serial
    res_serial = None
    if args.serial:
        res_serial = plan_fleet(base, axes, backend=backend, batched=False)
    res = (plan_fleet(base, axes, backend=backend, batched=True)
           if (args.batched or not args.serial) else res_serial)

    if args.json:
        print(res.to_json())
        return

    ref = res.reference
    st = res.stats
    print(f"evaluated {len(res.points)} plans; "
          f"frontier {len(res.frontier)}; "
          f"hypervolume vs all-on-demand {res.hypervolume:.4f}")
    print(f"{st['mode']} execution: {st['sims']} simulations for "
          f"{st['points']} points in {st['wall_s']:.2f} s wall "
          f"({st['compiles']} fresh compiles)")
    if compare:
        ss = res_serial.stats
        same = all(a.objectives() == b.objectives()
                   for a, b in zip(res_serial.points, res.points))
        print(f"serial execution: {ss['sims']} simulations in "
              f"{ss['wall_s']:.2f} s wall -> batched speedup "
              f"{ss['wall_s'] / st['wall_s']:.2f}x "
              f"(frontiers identical: {same})")
    print(f"reference (all on-demand): ${ref.cost_usd:.2f}  "
          f"{ref.energy_wh:.0f} Wh  {ref.carbon_kg:.3f} kg  "
          f"p99 {ref.p99_s:.1f} s")
    print()
    print(f"{'cost $':>9} {'Wh':>8} {'kgCO2e':>8} {'p99 s':>7} "
          f"{'pre':>4}  plan")
    for p in res.frontier:
        print(f"{p.cost_usd:9.2f} {p.energy_wh:8.0f} {p.carbon_kg:8.3f} "
              f"{p.p99_s:7.1f} {p.preemptions:4d}  {p.label()}")
    print()
    best_cost = res.best("cost_usd")
    best_kg = res.best("carbon_kg")
    print(f"best cost:   {best_cost.label()} "
          f"(${best_cost.cost_usd:.2f}, "
          f"{1 - best_cost.cost_usd / ref.cost_usd:.0%} under on-demand)")
    print(f"best carbon: {best_kg.label()} ({best_kg.carbon_kg:.3f} kg)")


if __name__ == "__main__":
    main()
