"""Batched plan_fleet == serial plan_fleet, point for point.

The batched planner's whole contract is that grouping grid points by
structural shape and re-pricing tier variants from one shared
simulation changes NOTHING observable: every PlanPoint's objectives,
cost decomposition, engine label, the frontier, and the hypervolume
must be exactly what the one-simulation-per-point serial sweep
produces.  These tests pin that equivalence -- as a property over
random sub-grids of the pinned axes, and as an explicit full-grid
regression for the shared-trace replay (satellite of the batched
planning PR; see docs/SCALE.md "Batched planning").
"""
import dataclasses

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, st

from repro.fleet.fleetsim import run_fleet
from repro.fleet.planner import (SPOT_ALL_FLEET, SPOT_H100_FLEET,
                                 ZONES3_FLEET, PlanAxes, pinned_day_axes,
                                 pinned_day_base, plan_fleet)

H6 = 6 * 3600.0

# the full pinned-axes coordinate pools the property sub-samples
FLEETS = (ZONES3_FLEET, SPOT_H100_FLEET, SPOT_ALL_FLEET)
ROUTERS = ("warm-first", "slo-aware")
TIERS = ("on_demand", "reserved")
RATES = (0.0, 2.0)

# every PlanPoint field the equivalence must hold EXACTLY on --
# everything except eval_s, which is informational wall-clock
COMPARED = ("fleet", "router", "price_tier", "preemption_rate",
            "cost_usd", "energy_wh", "carbon_kg", "p99_s", "engine",
            "gpu_hours_usd", "energy_usd", "preemptions", "requests")


def _key(p):
    return tuple(getattr(p, f) for f in COMPARED)


def _assert_identical(serial, batched):
    assert len(serial.points) == len(batched.points)
    for a, b in zip(serial.points, batched.points):
        assert _key(a) == _key(b)
    assert ([_key(p) for p in serial.frontier]
            == [_key(p) for p in batched.frontier])
    assert _key(serial.reference) == _key(batched.reference)
    assert serial.hypervolume == batched.hypervolume


_BASE6 = None


def _base6():
    """The 6 h pinned day, built once per test run (the property and
    the regressions all sweep the same base workload)."""
    global _BASE6
    if _BASE6 is None:
        _BASE6 = pinned_day_base(horizon_s=H6)
    return _BASE6


@pytest.fixture(scope="module")
def base6():
    return _base6()


class TestBatchedEqualsSerial:

    @settings(max_examples=5, deadline=None)
    @given(nf=st.integers(min_value=1, max_value=3),
           nr=st.integers(min_value=1, max_value=2),
           nt=st.integers(min_value=1, max_value=2),
           with_faults=st.booleans(),
           reverse=st.booleans())
    def test_random_subgrid_property(self, nf, nr, nt,
                                     with_faults, reverse):
        """Batched == serial on arbitrary sub-grids of the pinned axes:
        same points in the same order, same decompositions, same
        frontier, same hypervolume.  ``reverse`` flips the fleet axis
        so the reference fallback path (grid without the all-on-demand
        corner first) is exercised too."""
        fleets = FLEETS[:nf][::-1] if reverse else FLEETS[:nf]
        axes = PlanAxes(fleets=fleets, routers=ROUTERS[:nr],
                        price_tiers=TIERS[:nt],
                        preemption_rates=RATES if with_faults else (0.0,))
        serial = plan_fleet(_base6(), axes, backend="numpy", batched=False)
        batched = plan_fleet(_base6(), axes, backend="numpy", batched=True)
        _assert_identical(serial, batched)

    def test_full_pinned_grid_shared_trace_replay(self, base6):
        """The explicit regression for hoisted trace generation: the
        full pinned sweep runs FEWER simulations than it has points
        (tier variants replay their group's shared run) and still
        reproduces the serial sweep bit for bit."""
        axes = pinned_day_axes()
        serial = plan_fleet(base6, axes, backend="numpy", batched=False)
        batched = plan_fleet(base6, axes, backend="numpy", batched=True)
        _assert_identical(serial, batched)
        assert batched.stats["sims"] < batched.stats["points"]
        assert serial.stats["sims"] == serial.stats["points"] == 20
        # exact float equality, not approx: tier variants re-price the
        # primary's metered reports, which is the SAME arithmetic the
        # serial engines run
        for a, b in zip(serial.points, batched.points):
            assert a.cost_usd == b.cost_usd
            assert a.energy_wh == b.energy_wh
            assert a.carbon_kg == b.carbon_kg

    def test_engine_labels_match_serial_dispatch(self, base6):
        """Grouping must not change WHICH engine a point reports:
        fault-free warm-first plans ride mega, preemption draws and
        stateful routers ride the event loop, and tier variants carry
        their group primary's engine."""
        sweep = plan_fleet(base6, pinned_day_axes(), backend="numpy",
                           batched=True)
        for p in sweep.points:
            if p.preemption_rate > 0 or p.router != "warm-first":
                assert p.engine == "fleet", p.label()
            else:
                assert p.engine == "mega-numpy", p.label()

    def test_stats_shape(self, base6):
        axes = PlanAxes(fleets=(ZONES3_FLEET,), routers=("warm-first",),
                        price_tiers=TIERS)
        res = plan_fleet(base6, axes, backend="numpy", batched=True)
        st_ = res.stats
        assert st_["mode"] == "batched"
        assert st_["points"] == 2 and st_["sims"] == 1
        assert st_["wall_s"] > 0.0
        assert isinstance(st_["compiles"], int)
        # the primary carries the wall share; the replayed tier variant
        # ran no simulation of its own
        assert res.points[0].eval_s > 0.0
        assert res.points[1].eval_s == 0.0


class TestDetailFlagInvariance:
    """run_fleet's detail=False fast path (no replica logging, no
    timeline assembly) must not perturb any field the planner reads."""

    def test_detail_false_same_plan_fields(self, base6):
        full = run_fleet(base6)
        fast = run_fleet(base6, compute_bound=False, detail=False)
        for f in ("cost_usd", "energy_wh", "carbon_kg",
                  "p99_added_latency_s", "gpu_hours_usd", "energy_usd",
                  "preemptions", "requests"):
            assert getattr(full, f) == getattr(fast, f), f
        assert full.tier_billed_s == fast.tier_billed_s
        # and the fast path really did skip the detail work
        assert fast.carbon_timeline == []
        assert all(log == [] for log in fast.replica_timeline.values())
        assert full.carbon_timeline
        assert any(full.replica_timeline.values())
