"""Per-device electricity zones + follow-the-sun placement.

The tentpole contract (docs/CARBON.md, "Per-device zones"):

* ``"sku@ZONE"`` fleet-spec parts pin devices to a zone; zone-less
  parts inherit the scenario zone, so every pre-zone spec parses
  unchanged;
* a uniform per-device-zone fleet IS the scenario-zone fleet: the
  pinned 10-model x 6-GPU seed-100 day reproduces bit-exactly (energy,
  carbon, p99) under ``run_fleet`` AND both ``run_mega`` backends, and
  the all-devices-in-zone-Z total matches the scenario-zone-Z total to
  1e-9 kg -- the single-resolver guarantee
  (``carbon.resolve_zone_trace`` is the only zone->trace owner);
* zone decompositions (``zone_energy_wh`` / ``zone_carbon_kg``) fsum
  back to the global totals for ANY zone assignment (property test);
* ``CarbonTrace.shifted`` realizes each zone's local solar day on the
  shared sim clock (mean-preserving, identity at zero/whole-period
  shift);
* cross-zone migrations pay the WAN checkpoint transfer: latency
  stretches the returned load duration (threads into p99), energy
  accrues to ``Cluster.transfer_j``;
* the payoff: on the seeded 3-zone day, zone-aware carbon routing +
  consolidation lands strictly below zone-blind in kgCO2e at the
  pinned p99 bound.
"""
import dataclasses
import math

import pytest

from repro.core.scheduler import Breakeven
from repro.fleet import (CarbonAwareRouter, Cluster, Consolidator,
                         FleetModelSpec, MIXES, build_fleet, flat_trace,
                         get_mix, make_trace, mixed_fleet_scenario,
                         resolve_zone_trace, run_fleet, run_mega,
                         trace_for_zone, transfer_cost_j, transfer_latency_s,
                         zone_hops)

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, st

# pinned 3-zone fleet spec, seed, and latency bound live in conftest.py
# (shared with test_mega / test_pricing)
from conftest import P99_BOUND_S, PIN_SEED, ZONES3
from repro.kernels.segment_trapz import CARBON_REL


class TestSpecParsing:
    """``sku@ZONE`` grammar on both build_fleet input shapes."""

    def test_string_spec_zone_suffix(self):
        devs = build_fleet("2xh100@DEU+1xa100@USA+l40s")
        assert [d.zone for d in devs] == ["DEU", "DEU", "USA", None]
        assert [d.instance_id for d in devs] == \
               ["h100-0", "h100-1", "a100-0", "l40s-0"]

    def test_sequence_spec_zone_suffix(self):
        devs = build_fleet(["h100@ind", "a100"])
        assert devs[0].zone == "IND"        # canonicalized via get_mix
        assert devs[1].zone is None

    def test_zoneless_spec_parses_unchanged(self):
        old = build_fleet("2xh100+2xa100+2xl40s")
        assert all(d.zone is None for d in old)
        assert [d.instance_id for d in old] == \
               ["h100-0", "h100-1", "a100-0", "a100-1", "l40s-0", "l40s-1"]

    def test_unknown_zone_raises(self):
        with pytest.raises(KeyError, match="unknown electricity mix"):
            build_fleet("h100@ATLANTIS")

    def test_scenario_zone_fills_blanks(self):
        sc = mixed_fleet_scenario(Breakeven, "warm-first",
                                  fleet="h100@DEU+a100", zone="IND")
        zones = sc.device_zones()
        assert zones["h100-0"] == "DEU" and zones["a100-0"] == "IND"


class TestShiftedTrace:
    """Zone tz offsets realize local solar days on the sim clock."""

    def test_zero_shift_is_identity_object(self):
        tr = make_trace("solar-duck", 0.4)
        assert tr.shifted(0.0) is tr
        assert tr.shifted(tr.period_s) is tr       # whole period wraps

    def test_flat_trace_shift_is_identity(self):
        fl = flat_trace(0.3)
        assert fl.shifted(7 * 3600.0) is fl

    def test_shift_moves_the_clock(self):
        tr = make_trace("solar-duck", 0.4)
        dt = 7 * 3600.0
        sh = tr.shifted(dt)
        for t in (0.0, 3 * 3600.0, 11.25 * 3600.0, 23 * 3600.0):
            assert sh.intensity_at(t) == pytest.approx(
                tr.intensity_at(t + dt), rel=1e-9, abs=1e-12)

    def test_shift_preserves_daily_mean(self):
        tr = make_trace("solar-duck", 0.4)
        sh = tr.shifted(11.5 * 3600.0)
        assert sh.daily_mean_kg_per_kwh == pytest.approx(
            tr.daily_mean_kg_per_kwh, rel=1e-9)

    def test_usa_trace_is_unshifted(self):
        # the sim clock IS US local time: the default zone's preset
        # trace must be exactly the catalog shape (tz_offset 0)
        usa = trace_for_zone("USA")
        raw = make_trace("solar-duck", get_mix("USA").gwp_kg_per_kwh)
        assert usa.points == raw.points

    def test_zone_traces_trough_at_local_noon(self):
        # DEU (UTC+1-ish vs the US sim clock): solar trough lands
        # 7 simulated hours earlier than the USA trough
        deu = trace_for_zone("DEU")
        usa_shape = make_trace("solar-duck", get_mix("DEU").gwp_kg_per_kwh)
        assert deu.intensity_at(6 * 3600.0) == pytest.approx(
            usa_shape.intensity_at(13 * 3600.0), rel=1e-9)


class TestResolver:
    """carbon.resolve_zone_trace: the one zone->trace owner."""

    def test_none_resolves_flat_at_zone_mean(self):
        for z in sorted(MIXES):
            tr = resolve_zone_trace(z)
            assert tr.is_flat
            assert tr.daily_mean_kg_per_kwh == pytest.approx(
                get_mix(z).gwp_kg_per_kwh, rel=1e-12)

    def test_zone_keyword_resolves_preset(self):
        tr = resolve_zone_trace("DEU", "zone")
        assert tr.points == trace_for_zone("DEU").points

    def test_shape_name_resolves_at_zone_mean(self):
        tr = resolve_zone_trace("IND", "solar-duck")
        assert tr.daily_mean_kg_per_kwh == pytest.approx(
            get_mix("IND").gwp_kg_per_kwh, rel=1e-9)

    def test_explicit_trace_passes_through_for_home_zone(self):
        ct = make_trace("solar-duck", 0.123)
        assert resolve_zone_trace("USA", ct) is ct
        assert resolve_zone_trace("USA", ct, scenario_zone="USA") is ct

    def test_explicit_trace_rescales_for_foreign_zone(self):
        ct = make_trace("solar-duck", 0.123)
        got = resolve_zone_trace("SWE", ct, scenario_zone="USA")
        assert got.daily_mean_kg_per_kwh == pytest.approx(
            get_mix("SWE").gwp_kg_per_kwh, rel=1e-9)

    def test_device_traces_share_scenario_object_in_home_zone(self):
        sc = mixed_fleet_scenario(Breakeven, "warm-first",
                                  carbon_trace="zone", zone="USA")
        resolved = sc.resolved_carbon_trace()
        per_dev = sc.device_carbon_traces(resolved)
        assert all(tr is resolved for tr in per_dev.values())


class TestTransferModel:
    """Cross-zone WAN checkpoint-shipping costs."""

    def test_hops(self):
        assert zone_hops("USA", "usa") == 0
        assert zone_hops("DEU", "FRA") == 1       # same region (EU)
        assert zone_hops("DEU", "USA") == 2
        assert zone_hops("WOR", "USA") == 2       # GLOBAL never adjacent

    def test_costs_scale_with_gb_and_hops(self):
        assert transfer_cost_j(10.0, "USA", "USA") == 0.0
        assert transfer_latency_s(10.0, "USA", "USA") == 0.0
        assert transfer_cost_j(10.0, "DEU", "USA") == \
            2 * transfer_cost_j(10.0, "DEU", "FRA")
        assert transfer_latency_s(4.0, "DEU", "USA") == \
            2 * transfer_latency_s(2.0, "DEU", "USA")

    def test_cross_zone_migration_accounting(self):
        devices = build_fleet("h100@DEU+h100@USA")
        c = Cluster(devices)
        c.device_zones = {d.instance_id: d.zone for d in devices}
        gb = 8.0
        c.register_model(FleetModelSpec(
            model_id="m", policy_factory=Breakeven,
            checkpoint_bytes=int(gb * 1024 ** 3), vram_gb=gb * 1.1))
        dt = c.start_load("h100-0", "m")
        c.advance_to(dt)
        c.finish_load("h100-0", "m")
        dur = c.start_migration("m", "h100-0", "h100-1")
        base = c.loader_for("m", "h100-1").t_load_s
        assert dur == base + transfer_latency_s(gb, "DEU", "USA")
        assert c.cross_zone_migrations == 1
        assert c.transfer_j == transfer_cost_j(gb, "DEU", "USA")

    def test_same_zone_migration_costs_nothing_extra(self):
        devices = build_fleet("2xh100@DEU")
        c = Cluster(devices)
        c.device_zones = {d.instance_id: d.zone for d in devices}
        c.register_model(FleetModelSpec(
            model_id="m", policy_factory=Breakeven,
            checkpoint_bytes=8 * 1024 ** 3, vram_gb=9.0))
        dt = c.start_load("h100-0", "m")
        c.advance_to(dt)
        c.finish_load("h100-0", "m")
        dur = c.start_migration("m", "h100-0", "h100-1")
        assert dur == c.loader_for("m", "h100-1").t_load_s
        assert c.cross_zone_migrations == 0 and c.transfer_j == 0.0


def _uniform_zone_fleet(zone: str) -> str:
    return f"2xh100@{zone}+2xa100@{zone}+2xl40s@{zone}"


class TestUniformZoneEquivalence:
    """All-devices-in-zone-Z == scenario-zone-Z: the resolver can never
    disagree with itself, pinned bit-exact on the seed-100 day."""

    @pytest.mark.parametrize("runner", ["fleet", "mega-numpy", "mega-jax"])
    def test_pinned_day_bit_exact(self, runner):
        def go(fleet):
            sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED,
                                      fleet=fleet, zone="DEU",
                                      carbon_trace="zone")
            if runner == "fleet":
                return run_fleet(sc)
            return run_mega(sc, backend=runner.split("-")[1])

        ref = go("2xh100+2xa100+2xl40s")          # scenario zone only
        got = go(_uniform_zone_fleet("DEU"))      # every device pinned
        assert got.energy_wh == ref.energy_wh             # bit-for-bit
        assert got.carbon_kg == ref.carbon_kg
        assert got.carbon_kg_flat == ref.carbon_kg_flat
        assert got.energy_usd == ref.energy_usd
        assert got.carbon_timeline == ref.carbon_timeline
        assert got.p99_added_latency_s == ref.p99_added_latency_s
        assert abs(got.carbon_kg - ref.carbon_kg) <= 1e-9  # issue bound
        assert set(got.zone_carbon_kg) == {"DEU"}
        assert got.zone_carbon_kg["DEU"] == pytest.approx(
            got.carbon_kg, rel=1e-12)
        assert got.zone_energy_wh["DEU"] == pytest.approx(
            got.energy_wh, rel=1e-12)

    def test_multi_zone_day_mega_matches_event_loop(self):
        # warm-first routing is zone-blind, so the mega scope covers the
        # multi-zone day too: per-zone accounting must agree
        def go(runner):
            sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED,
                                      fleet=ZONES3, carbon_trace="zone")
            return run_fleet(sc) if runner == "fleet" \
                else run_mega(sc, backend=runner)

        ref = go("fleet")
        assert set(ref.zone_carbon_kg) == {"DEU", "IND", "USA"}
        for backend in ("numpy", "jax"):
            got = go(backend)
            # the jax backend's carbon runs through the f32 metering
            # kernel: held to its derived bound instead of 1e-9
            crel = CARBON_REL if backend == "jax" else 1e-9
            assert got.energy_wh == pytest.approx(ref.energy_wh, rel=1e-9)
            assert got.carbon_kg == pytest.approx(ref.carbon_kg, rel=crel)
            for z in ref.zone_carbon_kg:
                assert got.zone_carbon_kg[z] == pytest.approx(
                    ref.zone_carbon_kg[z], rel=crel)
                assert got.zone_energy_wh[z] == pytest.approx(
                    ref.zone_energy_wh[z], rel=1e-9)
            for (t1, c1), (t2, c2) in zip(ref.carbon_timeline,
                                          got.carbon_timeline):
                assert t2 == t1
                assert c2 == pytest.approx(c1, rel=crel, abs=1e-12)


class TestZoneDecomposition:
    """zone_energy_wh / zone_carbon_kg fsum back to the globals."""

    @settings(max_examples=5)
    @given(zones=st.lists(st.sampled_from(sorted(MIXES)),
                          min_size=6, max_size=6))
    def test_decomposition_sums_to_totals(self, zones):
        sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED,
                                  horizon_s=6 * 3600.0,
                                  carbon_trace="zone")
        sc.devices[:] = [dataclasses.replace(d, zone=z)
                         for d, z in zip(sc.devices, zones)]
        res = run_fleet(sc)
        assert set(res.zone_carbon_kg) == set(zones)
        assert math.fsum(res.zone_energy_wh.values()) == pytest.approx(
            res.energy_wh, rel=1e-12)
        assert math.fsum(res.zone_carbon_kg.values()) == pytest.approx(
            res.carbon_kg, rel=1e-12)
        for z in set(zones):
            dev_kg = math.fsum(d.carbon_kg for d in res.devices
                               if d.zone == z)
            assert res.zone_carbon_kg[z] == pytest.approx(
                dev_kg, rel=1e-12, abs=1e-15)


class TestDocsExample:
    """docs/CARBON.md "Per-device zones" snippets, executed verbatim."""

    def test_build_fleet_snippet(self):
        devs = build_fleet("2xh100@DEU+1xa100@USA+l40s")
        assert [d.zone for d in devs] == ["DEU", "DEU", "USA", None]

    def test_worked_3zone_snippet(self):
        sc = mixed_fleet_scenario(Breakeven, "warm-first", n_models=4,
                                  fleet="h100@DEU+a100@USA+l40s@IND",
                                  horizon_s=6 * 3600.0, carbon_trace="zone")
        res = run_fleet(sc)
        assert set(res.zone_carbon_kg) == {"DEU", "USA", "IND"}
        assert abs(math.fsum(res.zone_carbon_kg.values())
                   - res.carbon_kg) < 1e-9
        assert abs(math.fsum(res.zone_energy_wh.values())
                   - res.energy_wh) < 1e-6


class TestFollowTheSun:
    """The tentpole payoff: chasing troughs across zones cuts kgCO2e."""

    @staticmethod
    def _run(zone_aware: bool):
        sc = mixed_fleet_scenario(
            Breakeven, CarbonAwareRouter(math.inf, zone_aware=zone_aware),
            consolidate=Consolidator(carbon_aware=True, period_s=300.0),
            fleet=ZONES3, seed=PIN_SEED, carbon_trace="zone", zone="USA")
        return run_fleet(sc)

    def test_zone_aware_beats_zone_blind_at_p99_bound(self):
        aware = self._run(True)
        blind = self._run(False)
        assert aware.carbon_kg < blind.carbon_kg          # strictly below
        assert aware.p99_added_latency_s <= P99_BOUND_S
        assert blind.p99_added_latency_s <= P99_BOUND_S

    def test_transfer_accounting_consistent(self):
        res = self._run(True)
        if res.cross_zone_migrations:
            assert res.transfer_wh > 0.0
        else:
            assert res.transfer_wh == 0.0
        # single-zone fleets can never pay the WAN
        sc = mixed_fleet_scenario(
            Breakeven, CarbonAwareRouter(math.inf),
            consolidate=Consolidator(carbon_aware=True, period_s=300.0),
            seed=PIN_SEED, carbon_trace="solar-duck")
        one = run_fleet(sc)
        assert one.cross_zone_migrations == 0
        assert one.transfer_wh == 0.0
