"""Bring-up smoke of the fleet simulator's main path on one TPU chip.

The main path is ``run_mega(backend="jax")``: the host event loop
driving the compiled bulk phases and the Pallas ``fused_meter`` kernel,
plus ``run_mega_sweep`` and ``plan_fleet`` on top of it.  Each phase
runs a real day on the chip and holds it to the numpy backend:

  (a) the device must be a TPU -- anything else exits non-zero;
  (b) the pinned 10x6 seed-100 day on ``run_fleet`` and both backends;
  (c) the 600-device ~1M-request flash-crowd day under solar-duck
      carbon on both backends, and the kernel (``tpu_custom_call``) in
      the metering program that day ran;
  (d) a ``run_mega_sweep`` of 8 seeded flash-crowd 6 h days, one point
      replayed on the numpy backend;
  (e) ``plan_fleet`` on the pinned 24 h grid, jax against numpy.

Requests, cold starts and the wait count must be equal, energy and
dollars agree to 1e-9 relative, carbon within the metering kernel's f32
bound ``CARBON_REL``.  Earlier lines report wall seconds, compile counts
and each comparison's worst relative delta; the last line is one JSON
object naming the device, printed only when every phase passed.

Run from the repository root, one process, nothing else on the chip:

    python chip_smoke.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ENERGY_REL = 1e-9          # energy, dollars, latency: the f64 anchors
SEED = 100
FLEET600 = "200xh100+200xa100+200xl40s"


class Checks:
    """Named comparisons, each printed with its worst relative delta."""

    def __init__(self):
        self.failed = []

    def equal(self, name, got, want):
        ok = got == want
        if isinstance(want, list):
            miss = sum(g != w for g, w in zip(got, want))
            shown = f"{len(got)} items vs {len(want)}, {miss} differ"
        else:
            shown = f"{got} vs {want}"
        print(f"    {name}: {shown} {'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)

    def close(self, name, got, want, bound, floor=0.0):
        """Worst of |got - want| / |want| over paired sequences (or
        scalars); ``floor`` is an absolute tolerance for ~0 values."""
        if not isinstance(got, (list, tuple)):
            got, want = [got], [want]
        if len(got) != len(want):
            print(f"    {name}: length {len(got)} vs {len(want)} FAIL")
            self.failed.append(name)
            return
        worst, ok = 0.0, True
        for g, w in zip(got, want):
            d = abs(g - w)
            rel = d / abs(w) if w else d
            worst = max(worst, rel)
            ok = ok and d <= max(bound * abs(w), floor)
        print(f"    {name}: worst rel delta {worst:.3e} "
              f"(bound {bound:.0e}) {'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)


def check_device():
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {d.platform} "
                         f"({d.device_kind}); there is no fallback")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def compare_days(chk, label, ref, got):
    """The backend contract between a numpy-backend and a jax-backend
    run of the same day."""
    from repro.kernels.segment_trapz import CARBON_REL
    chk.equal(f"{label} requests", got.requests, ref.requests)
    chk.equal(f"{label} cold starts", got.cold_starts, ref.cold_starts)
    chk.equal(f"{label} waits", len(got.latencies_s), len(ref.latencies_s))
    chk.close(f"{label} energy_wh", got.energy_wh, ref.energy_wh,
              ENERGY_REL)
    chk.close(f"{label} cost_usd", got.cost_usd, ref.cost_usd, ENERGY_REL)
    chk.close(f"{label} p99_s", got.p99_added_latency_s,
              ref.p99_added_latency_s, ENERGY_REL, floor=1e-9)
    chk.close(f"{label} carbon_kg", got.carbon_kg, ref.carbon_kg,
              CARBON_REL)
    chk.close(f"{label} device carbon_kg",
              [d.carbon_kg for d in got.devices],
              [d.carbon_kg for d in ref.devices], CARBON_REL, floor=1e-12)
    chk.close(f"{label} carbon timeline",
              [c for _, c in got.carbon_timeline],
              [c for _, c in ref.carbon_timeline], CARBON_REL, floor=1e-12)


def timed(label, fn, *args, **kw):
    from repro.fleet.mega import jaxback
    c0 = jaxback.compiled_program_count()
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    wall = time.perf_counter() - t0
    print(f"  {label}: {wall:.3f} s wall, "
          f"{jaxback.compiled_program_count() - c0} new compiles")
    return out


def phase_pinned_day(chk):
    from repro.core.scheduler import Breakeven
    from repro.fleet import mixed_fleet_scenario, run_fleet, run_mega

    def day():
        return mixed_fleet_scenario(Breakeven, "warm-first", seed=SEED)

    fleet = timed("run_fleet", run_fleet, day())
    ref = timed("run_mega numpy", run_mega, day(), backend="numpy")
    got = timed("run_mega jax", run_mega, day(), backend="jax")
    chk.close("pinned energy_wh vs run_fleet", got.energy_wh,
              fleet.energy_wh, ENERGY_REL)
    compare_days(chk, "pinned", ref, got)


def phase_mega_day(chk):
    import jax

    from repro.core.scheduler import Breakeven
    from repro.fleet import flash_crowd, make_trace, run_mega
    from repro.fleet.mega import jaxback

    ct = make_trace("solar-duck", 0.39)
    trace = flash_crowd(n_routes=600, fleet=FLEET600, seed=SEED,
                        base_rate_hr=130.0)
    print(f"  day: {trace.requests} requests on {FLEET600}")

    def day():
        return trace.to_scenario(Breakeven, carbon_trace=ct)

    ref = timed("run_mega numpy", run_mega, day(), compute_bound=False,
                backend="numpy")
    # keep the metering program's arguments to show what it compiled
    meter, seen = jaxback._meter_fused, []

    def spy(*args, **kw):
        seen.append((args, kw))
        return meter(*args, **kw)

    spy._cache_size = meter._cache_size      # compile counts still read it
    jaxback._meter_fused = spy
    try:
        got = timed("run_mega jax", run_mega, day(), compute_bound=False,
                    backend="jax")
    finally:
        jaxback._meter_fused = meter
    print(f"  jax phase timings: {got.phase_timings}")
    compare_days(chk, "600-device", ref, got)
    args, kw = seen[-1]
    print(f"  metering program: {args[1].shape[0]} entries "
          f"({got.requests} requests)")
    with jax.enable_x64(True):
        text = meter.lower(*args, **kw).as_text()
    chk.equal("metering program holds tpu_custom_call",
              "tpu_custom_call" in text, True)


def phase_sweep(chk):
    from repro.core.scheduler import Breakeven
    from repro.fleet import make_trace, run_mega, run_mega_sweep
    from repro.fleet.mega import sweep_traces

    ct = make_trace("solar-duck", 0.39)
    kw = dict(generator="flash-crowd", n_routes=24,
              fleet="2xh100+2xa100+2xl40s", horizon_s=6 * 3600.0,
              base_rate_hr=40.0)
    pts = timed("run_mega_sweep 8 points", run_mega_sweep, seeds=range(8),
                scenario_kw=dict(carbon_trace=ct), **kw)
    print(f"  requests per point: {[p.requests for p in pts]}")
    day0 = sweep_traces([0], **kw)[0]
    ref = timed("point 0 numpy", run_mega,
                day0.to_scenario(Breakeven, carbon_trace=ct),
                compute_bound=False, backend="numpy")
    compare_days(chk, "sweep point 0", ref, pts[0])


def phase_plan(chk):
    from repro.fleet.planner import (pinned_day_axes, pinned_day_base,
                                     plan_fleet)
    from repro.kernels.segment_trapz import CARBON_REL

    ref = timed("plan_fleet numpy", plan_fleet, pinned_day_base(),
                pinned_day_axes(), backend="numpy", batched=True)
    got = timed("plan_fleet jax", plan_fleet, pinned_day_base(),
                pinned_day_axes(), backend="jax", batched=True)
    print(f"  jax plan stats: {got.stats}")

    def key(p):
        return (p.fleet, p.router, p.price_tier, p.preemption_rate)

    for p in got.points:
        print(f"    {p.fleet} {p.router} {p.price_tier} "
              f"rate={p.preemption_rate}: {p.engine}")
    chk.equal("plan points", [key(p) for p in got.points],
              [key(p) for p in ref.points])
    chk.equal("plan engines", [p.engine.split("-")[0] for p in got.points],
              [p.engine.split("-")[0] for p in ref.points])
    chk.equal("plan requests", [p.requests for p in got.points],
              [p.requests for p in ref.points])
    for field, bound in (("energy_wh", ENERGY_REL), ("cost_usd", ENERGY_REL),
                         ("p99_s", ENERGY_REL), ("carbon_kg", CARBON_REL)):
        chk.close(f"plan {field}", [getattr(p, field) for p in got.points],
                  [getattr(p, field) for p in ref.points], bound,
                  floor=1e-9)
    chk.equal("plan frontier", [key(p) for p in got.frontier],
              [key(p) for p in ref.frontier])


PHASES = [("b pinned 10x6 day", phase_pinned_day),
          ("c 600-device flash-crowd day", phase_mega_day),
          ("d seeded sweep", phase_sweep),
          ("e pinned plan_fleet", phase_plan)]


def main() -> int:
    from repro.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    device = check_device()
    print(f"(a) device: {device}")
    chk = Checks()
    t_all = time.perf_counter()
    for name, phase in PHASES:
        print(f"({name})")
        t0 = time.perf_counter()
        phase(chk)
        print(f"  phase wall: {time.perf_counter() - t0:.3f} s")
    print(f"total wall: {time.perf_counter() - t_all:.3f} s")
    if chk.failed:
        print(f"FAILED: {chk.failed}")
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
