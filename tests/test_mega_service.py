"""``run_mega`` with service time against ``run_fleet``, the reference
of the same semantics.

Requests take a decode slot for a service time frozen at their admission
occupancy; a request that finds every slot of its replica full, or its
replica loading, waits FIFO.  On small seeded flash-crowd days where
slots fill and loads overlap serving, both backends must agree with the
event loop exactly on requests, cold starts and the number of waits,
and to float-summation precision on every wait, energy, per-state Wh,
cost and carbon (the jax backend's carbon through the f32 metering
kernel, at ``CARBON_REL``).  Zero service time keeps its old path: a
pinned day gives the same bits as before the service path existed.
"""
import numpy as np
import pytest

from repro.core.coldstart import loader_from_checkpoint
from repro.core.scheduler import Breakeven
from repro.fleet import (flash_crowd, mixed_fleet_scenario, run_fleet,
                         run_mega)
from repro.kernels.segment_trapz import CARBON_REL
from repro.serving.service_model import (ConstantServiceTime, RequestShape,
                                         RooflineServiceTime)

from conftest import REL

SERVICE = {"constant": ConstantServiceTime(0.7),
           "roofline": RooflineServiceTime(RequestShape(1024, 256),
                                           mfu=0.4, overhead_s=0.01)}


def _day(svc, max_batch):
    """Three hours of eight routes on six GPUs, route 0 spiking 10x at
    the end of the first hour: bursts fill the slots, and routes that
    time out reload beside replicas that are serving."""
    trace = flash_crowd(n_routes=8, fleet="2xh100+2xa100+2xl40s",
                        horizon_s=3 * 3600.0, seed=7, base_rate_hr=600.0,
                        spike_x=10.0, spike_start_s=3600.0)
    return trace.to_scenario(Breakeven, service_model=svc,
                             max_batch=max_batch)


def _loading_while_serving(sc, res) -> bool:
    """Whether some metered segment draws a load's watts plus busy
    slots: a device served while it loaded."""
    watts = {w for _, _, w in res.power_timeline}
    for d in {x.sku.key: x for x in sc.devices}.values():
        inc = d.profile.active_power_w(0.6) - d.profile.p_ctx_w
        for fm in sc.models:
            p = loader_from_checkpoint(fm.spec.model_id,
                                       fm.spec.checkpoint_bytes,
                                       d.profile).p_load_w
            if any(p + b * inc in watts
                   for b in range(1, sc.max_batch + 1)):
                return True
    return False


@pytest.mark.parametrize("max_batch", [1, 4])
@pytest.mark.parametrize("service", sorted(SERVICE))
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_service_day_matches_run_fleet(backend, service, max_batch):
    sc = _day(SERVICE[service], max_batch)
    ref = run_fleet(sc, compute_bound=False)
    got = run_mega(sc, backend=backend, compute_bound=False)
    # the day exercises the mechanism
    assert got.counters["serve.slot_waits"] > 0
    assert _loading_while_serving(sc, ref)
    assert got.state_energy_wh["active"] > 0.0
    # counts exactly
    assert got.requests == ref.requests
    assert got.cold_starts == ref.cold_starts
    lr = np.asarray(ref.latencies_s)
    lg = np.asarray(got.latencies_s)
    assert int((lg > 0.0).sum()) == int((lr > 0.0).sum())
    assert [r.requests for r in got.devices] == \
        [r.requests for r in ref.devices]
    assert [r.cold_starts for r in got.devices] == \
        [r.cold_starts for r in ref.devices]
    # every wait, energy, per-state Wh and dollars to summation order
    np.testing.assert_allclose(lg, lr, rtol=REL, atol=1e-9)
    assert got.added_latency_s_total == pytest.approx(
        ref.added_latency_s_total, rel=REL)
    assert got.energy_wh == pytest.approx(ref.energy_wh, rel=REL)
    for k in set(ref.state_energy_wh) | set(got.state_energy_wh):
        assert got.state_energy_wh.get(k, 0.0) == pytest.approx(
            ref.state_energy_wh.get(k, 0.0), rel=REL, abs=1e-9)
        assert got.state_durations_s.get(k, 0.0) == pytest.approx(
            ref.state_durations_s.get(k, 0.0), rel=REL, abs=1e-9)
    for a, b in zip(got.devices, ref.devices):
        assert a.instance_id == b.instance_id
        assert a.total_wh == pytest.approx(b.total_wh, rel=REL)
    assert got.cost_usd == pytest.approx(ref.cost_usd, rel=REL)
    assert got.parking_tax_wh == pytest.approx(ref.parking_tax_wh, rel=REL)
    carbon_rel = CARBON_REL if backend == "jax" else REL
    assert got.carbon_kg == pytest.approx(ref.carbon_kg, rel=carbon_rel)
    assert [c for _, c in got.carbon_timeline] == pytest.approx(
        [c for _, c in ref.carbon_timeline], rel=carbon_rel)
    # the service counters hold the run's own arithmetic
    assert got.counters["serve.admissions"] == got.requests
    assert got.counters["serve.completions"] == got.requests


def test_capacity_pressure_matches_run_fleet():
    """Twenty-four routes on six GPUs: loads must make room, and a
    replica with busy slots or waiters is never the one evicted."""
    sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=7, n_models=24,
                              horizon_s=4 * 3600.0,
                              service_model=SERVICE["roofline"],
                              max_batch=2)
    ref = run_fleet(sc, compute_bound=False)
    got = run_mega(sc, compute_bound=False)
    assert got.requests == ref.requests
    assert got.cold_starts == ref.cold_starts
    np.testing.assert_allclose(np.asarray(got.latencies_s),
                               np.asarray(ref.latencies_s), rtol=REL,
                               atol=1e-9)
    assert got.energy_wh == pytest.approx(ref.energy_wh, rel=REL)


def test_short_timeout_never_evicts_a_busy_replica():
    """A 5 s idle timeout under 60 s requests: the timeout arms only
    when a replica's last slot and queue empty, never while it serves."""
    import functools
    from repro.core.scheduler import FixedTTL
    trace = flash_crowd(n_routes=6, fleet="h100+a100+l40s", seed=3,
                        horizon_s=2 * 3600.0, base_rate_hr=120.0,
                        spike_x=10.0, spike_start_s=1800.0)
    sc = trace.to_scenario(functools.partial(FixedTTL, 5.0),
                           service_model=ConstantServiceTime(60.0),
                           max_batch=2)
    ref = run_fleet(sc, compute_bound=False)
    got = run_mega(sc, compute_bound=False)
    assert got.requests == ref.requests
    assert got.cold_starts == ref.cold_starts
    np.testing.assert_allclose(np.asarray(got.latencies_s),
                               np.asarray(ref.latencies_s), rtol=REL,
                               atol=1e-9)
    assert got.energy_wh == pytest.approx(ref.energy_wh, rel=REL)


def test_service_table_is_the_model_at_each_occupancy():
    sc = _day(SERVICE["roofline"], 4)
    svc, spec, dev = SERVICE["roofline"], sc.models[0].spec, sc.devices[0]
    tab = svc.table(spec, dev, 4)
    assert tab == tuple(svc.request_service_s(spec, dev, b)
                        for b in (1, 2, 3, 4))
    assert list(tab) == sorted(tab)          # a fuller batch decodes slower


# A 6 h day of eight routes with zero service time, as run_mega gave it
# before the service path existed: the same bits on both backends.
PINNED_ZERO_SERVICE = {
    "requests": 479, "cold_starts": 239, "segments": 703,
    "energy_wh": 2106.920935670278, "cost_usd": 156.01283051228043,
    "latency_s": 7892.078079956217,
    "state_wh": {"bare": 1517.1263792638058, "parked": 469.148487521176,
                 "loading": 120.64606888529646},
    "carbon_kg": {"numpy": 0.8216991649114086, "jax": 0.8216991425701117},
    "energy_wh_jax": 2106.9209356702786,
}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_zero_service_is_bit_identical_to_before(backend):
    sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=100, n_models=8,
                              horizon_s=6 * 3600.0)
    got = run_mega(sc, backend=backend, compute_bound=False)
    pin = PINNED_ZERO_SERVICE
    assert got.requests == pin["requests"]
    assert got.cold_starts == pin["cold_starts"]
    assert len(got.power_timeline) == pin["segments"]
    assert float(got.energy_wh) == (pin["energy_wh_jax"] if backend == "jax"
                                    else pin["energy_wh"])
    assert float(got.cost_usd) == pin["cost_usd"]
    assert float(got.added_latency_s_total) == pin["latency_s"]
    assert {k: float(v) for k, v in got.state_energy_wh.items()} == \
        pin["state_wh"]
    assert float(got.carbon_kg) == pin["carbon_kg"][backend]
    assert "active" not in got.state_energy_wh
    assert "serve_s" not in got.phase_timings
    assert not any(k.startswith("serve.") for k in got.counters)
