"""Equivalence anchors + scope guards for the vectorized mega simulator.

The correctness spine of `fleet/mega/megasim.py` is a single claim: on
its supported scope, `run_mega` IS `run_fleet` -- same routing, same
evictions, same joules -- just re-expressed as an array program.  This
file pins that claim the way every other layer pins its anchor
(docs/ARCHITECTURE.md, "The equivalence-anchor contract"):

* the pinned 10-model x 6-GPU seed-100 day matches the event loop
  **bit-for-bit** on fleet totals (the ISSUE acceptance asks for 1e-3
  relative; we hold 0.0) and to <=1e-9 relative on every per-device
  bucket (the event loop's `Cluster.advance_to` steps its clock by
  float *deltas*, so its absolute times carry ~1-ulp accumulated drift
  that megasim, which uses exact event times, does not reproduce);
* unsupported scenarios refuse loudly (`MegaUnsupportedError`), never
  silently approximate;
* a 500-device x 100k-request day completes, conserves requests, and
  meters non-negative energy;
* the trace generators are seed-deterministic (same seed => the
  bit-identical trace) and round-trip through the record schema --
  as does the streaming JSON-Lines form (``FleetTrace.to_jsonl``);
* the compiled backend (``run_mega(backend="jax")``) matches the numpy
  anchor on energy to <=1e-9 relative, on carbon within the f32
  metering kernel's derived bound (``CARBON_REL``), and bit-for-bit on
  requests, cold starts, power timeline, and the fsum'd latency total,
  across the pinned day, generated days, and a property sweep of
  random seeds x policies x generators;
* the big-gap cache reuses derived stream arrays across runs on the
  same trace and stays within its bounds.
"""
import dataclasses
import json
import math
import pathlib
import sys

import numpy as np
import pytest

from repro.core.scheduler import (AdaptiveBreakeven, AlwaysOn, Breakeven,
                                  Clairvoyant, FixedTTL)
from repro.fleet import (CarbonBreakeven, FleetTrace, MegaUnsupportedError,
                         ReplicaAutoscaler, flash_crowd, make_trace,
                         mixed_fleet_scenario, product_launch,
                         regional_outage, run_fleet, run_mega, solar_duck,
                         trace_from_records)
from repro.fleet.mega import GENERATORS
from repro.fleet.mega.megasim import _BigGapCache, biggap_cache
from repro.kernels.segment_trapz import CARBON_REL

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_shim import given, settings, st

DATA = pathlib.Path(__file__).parent / "data"

# pinned seed and the cross-engine tolerance live in conftest.py
# (shared with test_zones / test_pricing)
from conftest import PIN_SEED, REL


def _ttl300():
    return FixedTTL(300.0)


def _pair(policy, **kw):
    """Run the same scenario through both simulators (scenarios hold
    mutable per-run state, so each gets a fresh one)."""
    ref = run_fleet(mixed_fleet_scenario(policy, "warm-first", **kw))
    got = run_mega(mixed_fleet_scenario(policy, "warm-first", **kw))
    return ref, got


class TestEquivalenceAnchor:
    """run_mega == run_fleet on the pinned 10-model x 6-GPU day."""

    def test_pinned_day_bit_exact_fleet_totals(self):
        ref, got = _pair(Breakeven, seed=PIN_SEED)
        assert got.requests == ref.requests
        assert got.cold_starts == ref.cold_starts
        assert got.energy_wh == ref.energy_wh            # bit-for-bit
        assert got.parking_tax_wh == ref.parking_tax_wh
        assert got.carbon_kg == ref.carbon_kg
        # per-state aggregates sum the per-device buckets, which carry the
        # event loop's ~1-ulp clock drift (see module docstring)
        for k in ref.state_energy_wh:
            assert got.state_energy_wh[k] == pytest.approx(
                ref.state_energy_wh[k], rel=1e-12)
        for k in ref.state_durations_s:
            assert got.state_durations_s[k] == pytest.approx(
                ref.state_durations_s[k], rel=1e-12)
        assert got.power_timeline == ref.power_timeline  # same segments
        assert got.replica_timeline == ref.replica_timeline
        assert got.lb_nongated_wh == ref.lb_nongated_wh
        assert got.cv_per_model_wh == ref.cv_per_model_wh
        assert got.infra_usd == ref.infra_usd
        assert got.energy_usd == ref.energy_usd
        assert got.carbon_timeline == ref.carbon_timeline

    @pytest.mark.parametrize("policy", [Breakeven, AlwaysOn, _ttl300,
                                        CarbonBreakeven],
                             ids=["breakeven", "always-on", "ttl-300",
                                  "carbon-breakeven"])
    def test_per_device_reports_match(self, policy):
        ref, got = _pair(policy, seed=PIN_SEED)
        assert got.requests == ref.requests
        assert got.cold_starts == ref.cold_starts
        assert got.energy_wh == pytest.approx(ref.energy_wh, rel=REL)
        for rd, gd in zip(ref.devices, got.devices):
            assert gd.instance_id == rd.instance_id
            assert gd.cold_starts == rd.cold_starts
            assert gd.requests == rd.requests
            assert gd.meter_state == rd.meter_state
            assert gd.resident == rd.resident
            assert list(gd.energy_wh) == list(rd.energy_wh)  # key order too
            for k in rd.energy_wh:
                assert gd.energy_wh[k] == pytest.approx(
                    rd.energy_wh[k], rel=REL, abs=1e-9)
            for k in rd.durations_s:
                assert gd.durations_s[k] == pytest.approx(
                    rd.durations_s[k], rel=REL, abs=1e-6)

    def test_latency_multiset_matches(self):
        ref, got = _pair(Breakeven, seed=PIN_SEED)
        assert len(got.latencies_s) == len(ref.latencies_s)
        assert np.allclose(np.asarray(got.latencies_s),
                           np.asarray(ref.latencies_s), rtol=0, atol=1e-9)
        assert got.p99_added_latency_s == pytest.approx(
            ref.p99_added_latency_s, abs=1e-9)

    @pytest.mark.parametrize("seed", [7, 42, 2024])
    def test_other_seeds_match(self, seed):
        ref, got = _pair(Breakeven, seed=seed)
        assert got.requests == ref.requests
        assert got.cold_starts == ref.cold_starts
        assert got.energy_wh == pytest.approx(ref.energy_wh, rel=REL)

    def test_generated_trace_day_matches_event_loop(self):
        tr = flash_crowd(n_routes=4, fleet="h100+a100+l40s",
                         horizon_s=4 * 3600.0, seed=PIN_SEED)
        ref = run_fleet(tr.to_scenario(Breakeven))
        got = run_mega(tr.to_scenario(Breakeven))
        assert got.requests == ref.requests == tr.requests
        assert got.cold_starts == ref.cold_starts
        assert got.energy_wh == pytest.approx(ref.energy_wh, rel=REL)


class TestScopeGuards:
    """Out-of-scope scenarios refuse loudly instead of approximating."""

    def test_non_warm_first_router_rejected(self):
        with pytest.raises(MegaUnsupportedError, match="warm-first"):
            run_mega(mixed_fleet_scenario(Breakeven, "least-loaded",
                                          seed=PIN_SEED))

    def test_stateful_policy_rejected(self):
        with pytest.raises(MegaUnsupportedError, match="adapts"):
            run_mega(mixed_fleet_scenario(AdaptiveBreakeven, "warm-first",
                                          seed=PIN_SEED))

    def test_clairvoyant_policy_rejected(self):
        with pytest.raises(MegaUnsupportedError):
            run_mega(mixed_fleet_scenario(Clairvoyant, "warm-first",
                                          seed=PIN_SEED))

    def test_nonzero_service_time_rejected(self):
        # the service path takes RooflineServiceTime and a positive
        # ConstantServiceTime (tests/test_mega_service.py); service time
        # from any other model, or a negative one, still falls back
        from repro.serving.service_model import (ConstantServiceTime,
                                                 ServiceTimeModel)

        class PerSlot(ServiceTimeModel):
            name = "per-slot"

            def request_service_s(self, spec, device, batch):
                return 2.0 * batch

        sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED)
        for svc in (PerSlot(), ConstantServiceTime(-2.0)):
            with pytest.raises(MegaUnsupportedError, match="service"):
                run_mega(dataclasses.replace(sc, service_model=svc))

    def test_autoscaler_rejected(self):
        sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED)
        with pytest.raises(MegaUnsupportedError, match="autoscal"):
            run_mega(dataclasses.replace(sc,
                                         autoscaler=ReplicaAutoscaler()))

    def test_carbon_breakeven_on_shaped_trace_rejected(self):
        # flat trace => constant T*, supported (anchored above); a shaped
        # trace makes the timeout time-varying, which the probe must catch
        sc = mixed_fleet_scenario(CarbonBreakeven, "warm-first", seed=PIN_SEED,
                                  carbon_trace=solar_duck(0.4))
        with pytest.raises(MegaUnsupportedError, match="varies"):
            run_mega(sc)


class TestScale:
    """The point of the subsystem: mega days in interactive time."""

    def test_500_devices_100k_requests(self):
        tr = flash_crowd(n_routes=500,
                         fleet="170xh100+170xa100+160xl40s",
                         seed=PIN_SEED, base_rate_hr=18.0, spike_x=30.0)
        assert tr.requests > 100_000
        res = run_mega(tr.to_scenario(Breakeven), compute_bound=False)
        assert res.requests == tr.requests          # conservation
        assert len(res.devices) == 500
        assert res.energy_wh > 0.0
        assert all(v >= 0.0 for v in res.state_energy_wh.values())
        assert all(v >= 0.0 for d in res.devices
                   for v in d.energy_wh.values())
        # every device's meter covers the same shared-clock span, which
        # is the horizon plus any load still in flight at day end (the
        # event loop's final advance_to(max(horizon, clock)) semantics)
        spans = [sum(d.durations_s.values()) for d in res.devices]
        assert min(spans) == pytest.approx(max(spans), rel=1e-9)
        assert min(spans) >= tr.horizon_s - 1e-6


class TestGenerators:
    """Seed discipline + schema round-trip for the synthetic days."""

    @pytest.mark.parametrize("gen", [flash_crowd, product_launch,
                                     regional_outage],
                             ids=["flash-crowd", "product-launch",
                                  "regional-outage"])
    def test_same_seed_bit_identical(self, gen):
        a, b = gen(seed=PIN_SEED), gen(seed=PIN_SEED)
        assert [r.route_id for r in a.routes] == \
               [r.route_id for r in b.routes]
        for ra, rb in zip(a.routes, b.routes):
            assert np.array_equal(ra.arrivals_s, rb.arrivals_s)
            assert ra.checkpoint_gb == rb.checkpoint_gb

    @pytest.mark.parametrize("gen", [flash_crowd, product_launch,
                                     regional_outage],
                             ids=["flash-crowd", "product-launch",
                                  "regional-outage"])
    def test_different_seed_differs(self, gen):
        a, b = gen(seed=PIN_SEED), gen(seed=101)
        assert any(not np.array_equal(ra.arrivals_s, rb.arrivals_s)
                   for ra, rb in zip(a.routes, b.routes))

    @pytest.mark.parametrize("gen", [flash_crowd, product_launch,
                                     regional_outage],
                             ids=["flash-crowd", "product-launch",
                                  "regional-outage"])
    def test_records_round_trip(self, gen):
        tr = gen(seed=PIN_SEED)
        back = trace_from_records(tr.to_records())
        assert back.name == tr.name and back.fleet == tr.fleet
        assert back.horizon_s == tr.horizon_s and back.seed == tr.seed
        for ra, rb in zip(tr.routes, back.routes):
            assert ra.route_id == rb.route_id
            assert ra.checkpoint_gb == rb.checkpoint_gb
            assert np.array_equal(ra.arrivals_s, rb.arrivals_s)

    def test_records_reject_unknown_route(self):
        rec = flash_crowd(seed=PIN_SEED).to_records()
        rec["events"].append({"t_s": 1.0, "route": "ghost"})
        with pytest.raises(ValueError, match="unknown route"):
            trace_from_records(rec)


class TestJsonl:
    """Streaming JSON-Lines ingestion: lossless both ways."""

    def test_fixture_round_trip(self, tmp_path):
        tr = FleetTrace.from_jsonl(DATA / "mini_day.jsonl")
        assert tr.name == "flash-crowd" and tr.seed == 17
        assert tr.requests > 0
        out = tmp_path / "again.jsonl"
        tr.to_jsonl(out)
        assert out.read_text() == (DATA / "mini_day.jsonl").read_text()

    def test_generated_round_trip_lossless(self, tmp_path):
        tr = flash_crowd(n_routes=3, fleet="1xh100+1xl40s", seed=17,
                         base_rate_hr=2.0, spike_x=8.0)
        p = tmp_path / "day.jsonl"
        tr.to_jsonl(p)
        back = FleetTrace.from_jsonl(p)
        assert back.name == tr.name and back.fleet == tr.fleet
        assert back.horizon_s == tr.horizon_s and back.seed == tr.seed
        for ra, rb in zip(tr.routes, back.routes):
            assert ra.route_id == rb.route_id
            assert ra.checkpoint_gb == rb.checkpoint_gb
            assert np.array_equal(ra.arrivals_s, rb.arrivals_s)
        assert back.to_records() == tr.to_records()

    def test_rejects_unknown_route_with_line_number(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        flash_crowd(n_routes=2, seed=17, base_rate_hr=1.0).to_jsonl(p)
        with open(p, "a", encoding="utf-8") as fh:
            fh.write('{"t_s": 1.0, "route": "ghost"}\n')
        with pytest.raises(ValueError, match="unknown route"):
            FleetTrace.from_jsonl(p)

    def test_rejects_empty_file(self, tmp_path):
        p = tmp_path / "empty.jsonl"
        p.write_text("")
        with pytest.raises(ValueError, match="empty"):
            FleetTrace.from_jsonl(p)

    def test_missing_t_s_not_misreported_as_unknown_route(self, tmp_path):
        # regression: the event-parsing try block used to span the whole
        # row, so the KeyError from a missing "t_s" was swallowed by the
        # unknown-route handler and reported as "unknown route 'r0'"
        p = tmp_path / "bad.jsonl"
        flash_crowd(n_routes=2, seed=17, base_rate_hr=1.0).to_jsonl(p)
        with open(p, "a", encoding="utf-8") as fh:
            fh.write('{"route": "r0"}\n')
        n_lines = sum(1 for _ in open(p, encoding="utf-8"))
        with pytest.raises(ValueError,
                           match=rf":{n_lines}: event missing 't_s'") as ei:
            FleetTrace.from_jsonl(p)
        assert "unknown route" not in str(ei.value)

    def test_malformed_t_s_reports_line_number(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        flash_crowd(n_routes=2, seed=17, base_rate_hr=1.0).to_jsonl(p)
        with open(p, "a", encoding="utf-8") as fh:
            fh.write('{"t_s": "noonish", "route": "r0"}\n')
        n_lines = sum(1 for _ in open(p, encoding="utf-8"))
        with pytest.raises(ValueError,
                           match=rf":{n_lines}: malformed 't_s'"):
            FleetTrace.from_jsonl(p)

    def test_rejects_duplicate_route_id_in_header(self, tmp_path):
        # regression: duplicate header route ids used to silently
        # collapse into one bucket (last checkpoint wins, events merged)
        hdr = {"name": "dup", "fleet": "h100", "horizon_s": 100.0,
               "seed": None,
               "routes": [{"route": "r0", "checkpoint_gb": 4.0},
                          {"route": "r0", "checkpoint_gb": 9.0}]}
        p = tmp_path / "dup.jsonl"
        p.write_text(json.dumps(hdr) + "\n"
                     + '{"t_s": 1.0, "route": "r0"}\n')
        with pytest.raises(ValueError, match="duplicate route id 'r0'"):
            FleetTrace.from_jsonl(p)

    def test_leading_blank_lines_tolerated(self, tmp_path):
        # regression: a leading blank line used to be misreported as
        # "empty jsonl trace" (the header read was a bare readline)
        tr = flash_crowd(n_routes=2, seed=17, base_rate_hr=1.0)
        p = tmp_path / "day.jsonl"
        tr.to_jsonl(p)
        padded = tmp_path / "padded.jsonl"
        padded.write_text("\n  \n" + p.read_text())
        back = FleetTrace.from_jsonl(padded)
        assert back.to_records() == tr.to_records()

    def test_zone_field_round_trips(self, tmp_path):
        tr = flash_crowd(n_routes=2, seed=17, base_rate_hr=1.0)
        routes = tuple(
            dataclasses.replace(r, zone="DEU" if i == 0 else None)
            for i, r in enumerate(tr.routes))
        tr = dataclasses.replace(tr, routes=routes)
        p = tmp_path / "zoned.jsonl"
        tr.to_jsonl(p)
        back = FleetTrace.from_jsonl(p)
        assert back.routes[0].zone == "DEU"
        assert back.routes[1].zone is None
        rec = trace_from_records(tr.to_records())
        assert rec.routes[0].zone == "DEU" and rec.routes[1].zone is None


class TestBigGapCache:
    """Derived stream arrays are shared across runs, within bounds."""

    def test_hit_on_same_source_array(self):
        cache = _BigGapCache(maxsize=4)
        src = np.array([3.0, 1.0, 2.0, 99.0])
        a1, g1 = cache.stream_arrays(src, 10.0)
        a2, g2 = cache.stream_arrays(src, 10.0)
        assert a1 is a2 and g1 is g2            # shared derived objects
        assert cache.hits == 1 and cache.misses == 1
        assert list(a1) == [1.0, 2.0, 3.0]      # sorted, horizon-filtered
        # a different horizon is a different derivation
        a3, _ = cache.stream_arrays(src, 2.5)
        assert cache.misses == 2 and list(a3) == [1.0, 2.0]

    def test_lru_bound_holds(self):
        cache = _BigGapCache(maxsize=2)
        srcs = [np.array([float(i)]) for i in range(5)]
        for s in srcs:
            cache.stream_arrays(s, 10.0)
        assert len(cache) == 2
        cache.stream_arrays(srcs[-1], 10.0)     # newest still resident
        assert cache.hits == 1

    def test_list_source_not_cached(self):
        cache = _BigGapCache()
        arr, _ = cache.stream_arrays([2.0, 1.0], 10.0)   # no weakref
        assert list(arr) == [1.0, 2.0] and len(cache) == 0

    def test_repeat_runs_on_same_trace_hit(self):
        tr = flash_crowd(n_routes=3, fleet="1xh100+1xl40s", seed=7,
                         base_rate_hr=4.0, horizon_s=6 * 3600.0)
        biggap_cache.clear()
        run_mega(tr.to_scenario(Breakeven), compute_bound=False)
        assert biggap_cache.misses == 3 and biggap_cache.hits == 0
        run_mega(tr.to_scenario(Breakeven), compute_bound=False)
        assert biggap_cache.hits == 3           # every stream reused

    def test_biggap_dict_bounded_per_stream(self):
        cache = _BigGapCache(max_timeouts=3)
        src = np.arange(50, dtype=np.float64)
        _, gaps = cache.stream_arrays(src, 100.0)
        from repro.fleet.mega.megasim import _Stream
        ms = _Stream("m", src, gaps)
        import repro.fleet.mega.megasim as megasim_mod
        old = megasim_mod.biggap_cache
        megasim_mod.biggap_cache = cache
        try:
            for T in (0.5, 1.5, 2.5, 3.5, 4.5):
                ms.biggaps(T)
        finally:
            megasim_mod.biggap_cache = old
        assert len(ms.biggap) == 3              # oldest evicted


def _jax_pair(make_scenario, **run_kw):
    """The same scenario through both bulk backends (fresh scenarios:
    they hold mutable per-run state)."""
    ref = run_mega(make_scenario(), backend="numpy", **run_kw)
    got = run_mega(make_scenario(), backend="jax", **run_kw)
    return ref, got


def _assert_backends_match(ref, got):
    """The backend contract: identical structural outcomes, energy to
    <=1e-9 relative (summed in a different order on the compiled path),
    carbon within the metering kernel's f32 bound CARBON_REL; latency
    totals use fsum on an identical multiset, so they are exactly
    equal."""
    assert got.requests == ref.requests
    assert got.cold_starts == ref.cold_starts
    assert got.power_timeline == ref.power_timeline
    assert got.replica_timeline == ref.replica_timeline
    assert got.added_latency_s_total == ref.added_latency_s_total
    assert got.energy_wh == pytest.approx(ref.energy_wh, rel=REL)
    assert got.carbon_kg == pytest.approx(ref.carbon_kg, rel=CARBON_REL)
    assert got.parking_tax_wh == pytest.approx(ref.parking_tax_wh, rel=REL)
    for (t1, c1), (t2, c2) in zip(ref.carbon_timeline, got.carbon_timeline):
        assert t2 == t1
        assert c2 == pytest.approx(c1, rel=CARBON_REL, abs=1e-12)
    for rd, gd in zip(ref.devices, got.devices):
        assert gd.requests == rd.requests
        assert gd.cold_starts == rd.cold_starts
        assert list(gd.energy_wh) == list(rd.energy_wh)
        for k in rd.energy_wh:
            assert gd.energy_wh[k] == pytest.approx(rd.energy_wh[k],
                                                    rel=REL, abs=1e-9)
        assert gd.carbon_kg == pytest.approx(rd.carbon_kg, rel=CARBON_REL,
                                             abs=1e-12)


class TestJaxBackend:
    """run_mega(backend="jax") == the numpy anchor, which == run_fleet."""

    def test_pinned_day_matches_numpy(self):
        ref, got = _jax_pair(
            lambda: mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED))
        _assert_backends_match(ref, got)
        assert np.array_equal(np.asarray(ref.latencies_s),
                              np.asarray(got.latencies_s))

    @pytest.mark.parametrize("n", [512, 513], ids=["pow2", "pow2+1"])
    def test_gather_exact_at_the_arrival_pad_boundary(self, n):
        # the billing gather reads the arrivals padded to their
        # power-of-two bucket: no pad at 512, 511 pad slots at 513
        tr = flash_crowd(n_routes=4, fleet="h100+a100+l40s", seed=4242,
                         horizon_s=12 * 3600.0)
        cut = np.sort(np.concatenate([r.arrivals_s
                                      for r in tr.routes]))[n - 1]
        day = dataclasses.replace(tr, routes=tuple(
            dataclasses.replace(r, arrivals_s=r.arrivals_s[
                r.arrivals_s <= cut]) for r in tr.routes))
        assert day.requests == n
        ref, got = _jax_pair(lambda: day.to_scenario(Breakeven),
                             compute_bound=False)
        _assert_backends_match(ref, got)
        assert np.count_nonzero(ref.latencies_s) > 0
        assert np.array_equal(np.asarray(ref.latencies_s),
                              np.asarray(got.latencies_s))

    @pytest.mark.parametrize("gen", [flash_crowd, product_launch,
                                     regional_outage],
                             ids=["flash-crowd", "product-launch",
                                  "regional-outage"])
    def test_generated_days_match(self, gen):
        tr = gen(n_routes=4, fleet="h100+a100+l40s", seed=7)
        ref, got = _jax_pair(lambda: tr.to_scenario(Breakeven),
                             compute_bound=False)
        _assert_backends_match(ref, got)

    def test_shaped_carbon_trace_matches(self):
        # the carbon integral is the Pallas-kernel path's whole reason
        # to exist; anchor it on a non-flat intensity curve
        tr = flash_crowd(n_routes=4, fleet="h100+a100", seed=11,
                         horizon_s=8 * 3600.0)
        ct = make_trace("solar-duck", 0.39)
        ref, got = _jax_pair(
            lambda: tr.to_scenario(Breakeven, carbon_trace=ct),
            compute_bound=False)
        _assert_backends_match(ref, got)

    def test_phase_timings_reported(self):
        sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED)
        res = run_mega(sc, backend="jax")
        keys = {"biggap_s", "billing_s", "energy_s", "carbon_s",
                "bulk_scan_s", "run_s", "scenario_s", "event_loop_s",
                "report_s", "compile_s", "bulk_call_s", "bulk_host_s"}
        assert set(res.phase_timings) == keys
        assert all(v >= 0.0 for v in res.phase_timings.values())

    def test_unknown_backend_rejected(self):
        sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED)
        with pytest.raises(ValueError, match="unknown backend"):
            run_mega(sc, backend="torch")

    def test_scope_guard_parity(self):
        # out-of-scope scenarios refuse identically on either backend
        sc = mixed_fleet_scenario(AdaptiveBreakeven, "warm-first", seed=PIN_SEED)
        with pytest.raises(MegaUnsupportedError, match="adapts"):
            run_mega(sc, backend="jax")

    def test_clear_error_when_jax_missing(self, monkeypatch):
        import repro.fleet.mega as mega_pkg
        monkeypatch.delitem(sys.modules, "repro.fleet.mega.jaxback",
                            raising=False)
        monkeypatch.delattr(mega_pkg, "jaxback", raising=False)
        monkeypatch.setitem(sys.modules, "jax", None)   # import -> error
        sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED)
        with pytest.raises(RuntimeError, match="needs jax"):
            run_mega(sc, backend="jax")

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10_000),
           gen=st.sampled_from(sorted(GENERATORS)),
           policy=st.sampled_from([Breakeven, AlwaysOn, _ttl300]))
    def test_property_backends_agree(self, seed, gen, policy):
        tr = GENERATORS[gen](n_routes=3, fleet="h100+l40s", seed=seed,
                             horizon_s=6 * 3600.0)
        ref, got = _jax_pair(lambda: tr.to_scenario(policy),
                             compute_bound=False)
        _assert_backends_match(ref, got)


class TestFusedFinalize:
    """The jax backend's metering finalize (one kernel launch for
    energy + billed seconds + carbon) against the engines that meter
    each quantity in its own pass -- the event loop (``run_fleet``) and
    the numpy backend -- on the multi-trace 3-zone day with mixed
    purchase tiers, the widest surface the metering kernel covers."""

    FLEET = "2xh100@DEU:spot+2xa100@USA+2xl40s@IND"

    def _scenario(self):
        return mixed_fleet_scenario(
            Breakeven, "warm-first", fleet=self.FLEET, seed=PIN_SEED,
            horizon_s=6 * 3600.0, carbon_trace="zone")

    def _jax(self):
        return run_mega(self._scenario(), backend="jax",
                        compute_bound=False)

    def test_fused_matches_unfused(self):
        got, ref = self._jax(), run_fleet(self._scenario())
        assert got.requests == ref.requests
        assert got.cold_starts == ref.cold_starts
        assert got.energy_wh == pytest.approx(ref.energy_wh, rel=REL)
        for rd, gd in zip(ref.devices, got.devices):
            assert list(gd.energy_wh) == list(rd.energy_wh)
            for k in rd.energy_wh:
                assert gd.energy_wh[k] == pytest.approx(
                    rd.energy_wh[k], rel=REL, abs=1e-9)
            for k in rd.durations_s:
                assert gd.durations_s[k] == pytest.approx(
                    rd.durations_s[k], rel=REL, abs=1e-6)
        # the carbon lane integrates the raw charge log in the f32
        # kernel, the event loop its coalesced segments in f64: same
        # integral, within the kernel's derived bound
        assert got.carbon_kg == pytest.approx(ref.carbon_kg,
                                              rel=CARBON_REL)
        assert len(got.carbon_timeline) == len(ref.carbon_timeline)
        for (t1, c1), (t2, c2) in zip(ref.carbon_timeline,
                                      got.carbon_timeline):
            assert t2 == t1
            assert c2 == pytest.approx(c1, rel=CARBON_REL, abs=1e-12)

    def test_tier_billed_seconds_all_engines_agree(self):
        got = self._jax()
        numpy_day = run_mega(self._scenario(), backend="numpy",
                             compute_bound=False)
        ref = run_fleet(self._scenario())
        assert set(got.tier_billed_s) == {"on_demand", "spot"}
        for engine in (numpy_day, ref):
            assert set(engine.tier_billed_s) == set(got.tier_billed_s)
            for t, s in got.tier_billed_s.items():
                assert s == pytest.approx(engine.tier_billed_s[t], rel=REL)
        # mega scope has no sleep/off states, so billed seconds per
        # tier partition the full metered time
        total = sum(s for d in got.devices
                    for s in d.durations_s.values())
        assert sum(got.tier_billed_s.values()) == pytest.approx(
            total, rel=REL)

    def test_fused_matches_numpy_anchor(self):
        ref = run_mega(self._scenario(), backend="numpy",
                       compute_bound=False)
        got = run_mega(self._scenario(), backend="jax",
                       compute_bound=False)
        _assert_backends_match(ref, got)
        for t, s in ref.tier_billed_s.items():
            assert got.tier_billed_s[t] == pytest.approx(s, rel=REL)

    def test_phase_timing_keys_unchanged(self):
        res = run_mega(self._scenario(), backend="jax")
        assert set(res.phase_timings) == {
            "biggap_s", "billing_s", "energy_s", "carbon_s", "bulk_scan_s",
            "run_s", "scenario_s", "event_loop_s", "report_s", "compile_s",
            "bulk_call_s", "bulk_host_s"}


class TestMegaSweep:
    """Vmapped sweep entry point: deterministic, compiled-once batches."""

    def test_seeds_sweep_runs_and_is_deterministic(self):
        from repro.fleet import run_mega_sweep
        kw = dict(n_routes=3, fleet="h100+l40s", base_rate_hr=8.0,
                  horizon_s=6 * 3600.0)
        r1 = run_mega_sweep(seeds=[1, 2, 3], **kw)
        r2 = run_mega_sweep(seeds=[1, 2, 3], **kw)
        assert len(r1) == 3
        assert [a.energy_wh for a in r1] == [b.energy_wh for b in r2]
        assert [a.requests for a in r1] == [b.requests for b in r2]
        assert all(a.phase_timings is not None for a in r1)
        # distinct seeds produced distinct days
        assert len({a.requests for a in r1}) > 1

    def test_sweep_traces_generator_shapes(self):
        from repro.fleet.mega import sweep_traces
        for gen in sorted(GENERATORS):
            trs = sweep_traces([5], generator=gen, n_routes=3,
                               horizon_s=6 * 3600.0)
            assert len(trs) == 1 and len(trs[0].routes) == 3
            assert trs[0].requests > 0
        with pytest.raises(KeyError, match="unknown sweep generator"):
            sweep_traces([5], generator="meteor-strike")

    def test_scenarios_sweep_matches_run_mega(self):
        from repro.fleet import run_mega_sweep
        tr = flash_crowd(n_routes=3, fleet="h100+l40s", seed=9,
                         horizon_s=6 * 3600.0)
        ref = run_mega(tr.to_scenario(Breakeven), backend="jax",
                       compute_bound=False)
        got = run_mega_sweep(scenarios=[tr.to_scenario(Breakeven)])[0]
        assert got.energy_wh == ref.energy_wh
        assert got.requests == ref.requests

    def test_argument_validation(self):
        from repro.fleet import run_mega_sweep
        with pytest.raises(ValueError, match="exactly one"):
            run_mega_sweep()
        with pytest.raises(ValueError, match="exactly one"):
            run_mega_sweep(scenarios=[], seeds=[1])
        with pytest.raises(ValueError, match="need seeds"):
            run_mega_sweep(scenarios=[], n_routes=4)

    def test_on_unsupported_skip_returns_none_slots(self):
        # the batched planner's seam: out-of-scope scenarios come back
        # as None in place instead of aborting the whole sweep
        from repro.fleet.mega.jaxback import run_mega_sweep
        tr = flash_crowd(n_routes=3, fleet="h100+l40s", seed=9,
                         horizon_s=6 * 3600.0)
        good = tr.to_scenario(Breakeven)
        bad = tr.to_scenario(Breakeven)
        bad = dataclasses.replace(bad, router="slo-aware")
        out = run_mega_sweep(scenarios=[good, bad],
                             compute_bound=False, on_unsupported="skip")
        assert out[0] is not None and out[0].requests > 0
        assert out[1] is None
        with pytest.raises(MegaUnsupportedError):
            run_mega_sweep(scenarios=[tr.to_scenario(Breakeven), bad],
                           compute_bound=False)
        with pytest.raises(ValueError, match="on_unsupported"):
            run_mega_sweep(scenarios=[good], on_unsupported="ignore")
