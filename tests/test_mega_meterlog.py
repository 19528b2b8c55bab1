"""The mega simulator's meter log (``fleet/mega/megasim.py``).

* every power transition of the event loop is one record of the meter
  log; both backends reduce it at finalize, and the coalesced power
  timeline is derived from it by the rule the event loop's
  ``EnergyMeter`` coalesces by -- checked against that rule applied
  transition by transition, on hand-made and random logs;
* a pressured day (two L40S, twelve models: several replicas a device,
  capacity evictions) gives, on both backends, the power timeline,
  per-device Wh buckets, report keys and cold starts pinned here;
* the VRAM sum takes the sorted registration-order walk only on a
  device that holds two replicas or more (``vused.sorted``).
"""
import hashlib
import math

import numpy as np
import pytest

from repro.core.scheduler import Breakeven
from repro.fleet import flash_crowd, mixed_fleet_scenario, run_mega
from repro.fleet.mega.megasim import _log_arrays, _power_segments


def _loop_segs(log, n_dev, n_state):
    """The per-device coalescing the event loop ran transition by
    transition: an interval with dt > 0 extends the device's last
    segment when that one ends at its start with the same watts, and
    opens a new segment otherwise."""
    segs = [[] for _ in range(n_dev)]
    for i in range(0, len(log), 4):
        k, p, t0, t = log[i:i + 4]
        sg = segs[int(k) // n_state]
        if t - t0 > 0.0:
            if sg and sg[-1][1] == t0 and sg[-1][2] == p:
                sg[-1] = (sg[-1][0], t, p)
            else:
                sg.append((t0, t, p))
    return segs


def _derived(log, n_dev, n_state):
    keys, pw, a, b = _log_arrays(log)
    segs, bounds = _power_segments(keys // n_state, a, b, pw, n_dev)
    return segs, [segs[bounds[d]:bounds[d + 1]] for d in range(n_dev)]


def test_log_arrays_keep_log_order():
    keys, pw, a, b = _log_arrays([4, 60.0, 0.0, 1.5, 2, 30.0, 0.25, 2.0])
    assert keys.tolist() == [4, 2] and keys.dtype.kind == "i"
    assert pw.tolist() == [60.0, 30.0]
    assert a.tolist() == [0.0, 0.25] and b.tolist() == [1.5, 2.0]


def test_segments_follow_the_loop_rule_on_a_hand_made_log():
    S = 3
    log = [
        0 * S + 0, 50.0, 0.0, 10.0,      # dev 0
        1 * S + 0, 70.0, 0.0, 5.0,       # dev 1, interleaved
        0 * S + 2, 50.0, 10.0, 10.0,     # dev 0: zero length, dropped
        0 * S + 1, 50.0, 10.0, 20.0,     # dev 0: adjacent, same watts: merged
        1 * S + 1, 80.0, 5.0, 9.0,       # dev 1: other watts: new segment
        0 * S + 1, 50.0, 25.0, 30.0,     # dev 0: same watts after a gap
        1 * S + 2, 80.0, 9.0, 12.0,      # dev 1: merged
        0 * S + 0, 90.0, 30.0, 31.0,     # dev 0: adjacent, other watts
    ]
    fleet, per_dev = _derived(log, 3, S)
    assert per_dev == [
        [(0.0, 20.0, 50.0), (25.0, 30.0, 50.0), (30.0, 31.0, 90.0)],
        [(0.0, 5.0, 70.0), (5.0, 12.0, 80.0)],
        [],                              # a device with no transition
    ]
    assert per_dev == _loop_segs(log, 3, S)
    assert fleet == [sg for d in per_dev for sg in d]
    assert all(type(x) is float for sg in fleet for x in sg)


def test_segments_of_an_empty_or_zero_length_log():
    assert _derived([0, 50.0, 3.0, 3.0, 4, 60.0, 7.0, 7.0], 2, 3) == \
        ([], [[], []])


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_segments_follow_the_loop_rule_on_random_logs(seed):
    """Random interleavings of per-device timelines, with few distinct
    watts and times (so merges, gaps and zero lengths all occur), match
    the loop rule tuple for tuple."""
    rng = np.random.default_rng(seed)
    n_dev, S = 5, 4
    since = [0.0] * n_dev
    log = []
    for _ in range(400):
        d = int(rng.integers(n_dev))
        t = since[d] + float(rng.choice([0.0, 0.5, 1.0]))
        t0 = since[d] if rng.random() < 0.9 else t - 0.25     # rare gap
        log += [d * S + int(rng.integers(S)),
                float(rng.choice([40.0, 55.5, 100.0])), t0, t]
        since[d] = t
    fleet, per_dev = _derived(log, n_dev, S)
    assert per_dev == _loop_segs(log, n_dev, S)
    assert fleet == [sg for d in per_dev for sg in d]


def _pressured_day():
    """Two 48 GB L40S for twelve models of 5.5-47.9 GB: devices hold
    several replicas and evict for room."""
    return mixed_fleet_scenario(Breakeven, "warm-first", seed=7,
                                fleet="2xl40s", n_models=12,
                                horizon_s=12 * 3600.0)


def _digest(timeline):
    return hashlib.sha256(";".join(
        ",".join(float(x).hex() for x in sg) for sg in timeline
    ).encode()).hexdigest()


# the day's outputs as the event loop wrote them before the meter log
# replaced its per-transition bookkeeping (energy buckets as float.hex)
PINNED_DAY = {
    "requests": 1899, "cold_starts": 873, "segments": 1147,
    "timeline_sha256":
        "b627f0fd1b823137e0c707ce374bbb0c2120920293aa40b465c1de7606f96ee8",
    "first": [(0.0, 40.94294603174603, 102.1),
              (40.94294603174603, 69.14683941321564, 35.6)],
    "last": (43354.52002634196, 43412.38113745307, 55.724076812289965),
    "devices": [
        ("l40s-0", 446, [("bare", "0x1.474ad05e270abp+6"),
                         ("parked", "0x1.eec418a2525f1p+7"),
                         ("loading", "0x1.98e2309e84f94p+8"),
                         ("total", "0x1.710b78839bf5cp+9")]),
        ("l40s-1", 427, [("bare", "0x1.0503aaa914e19p+7"),
                         ("parked", "0x1.76a8411667284p+7"),
                         ("loading", "0x1.6d74a2226b096p+8"),
                         ("total", "0x1.55a54c0114872p+9")]),
    ],
}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_pressured_day_matches_the_pinned_outputs(backend):
    res = run_mega(_pressured_day(), backend=backend, compute_bound=False)
    ct = res.counters
    # the pressure is real: placements reach the scan, and devices hold
    # several replicas at once
    assert ct["place.scans"] > 0 and ct["vused.sorted"] > 0
    assert (res.requests, res.cold_starts) == (
        PINNED_DAY["requests"], PINNED_DAY["cold_starts"])
    tl = res.power_timeline
    assert len(tl) == PINNED_DAY["segments"]
    assert tl[:2] == PINNED_DAY["first"] and tl[-1] == PINNED_DAY["last"]
    assert _digest(tl) == PINNED_DAY["timeline_sha256"]
    got = [(r.instance_id, r.cold_starts,
            [(k, float(v).hex()) for k, v in r.energy_wh.items()])
           for r in res.devices]
    assert got == PINNED_DAY["devices"]
    for r in res.devices:
        assert list(r.durations_s) == [k for k in r.energy_wh
                                       if k != "total"]
    # the timeline and the buckets meter the same joules
    wh = math.fsum((b - a) * p for a, b, p in tl) / 3600.0
    assert wh == pytest.approx(res.energy_wh, rel=1e-12)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_vused_sorted_reads_zero_with_one_replica_a_device(backend):
    """Eight routes on twelve GPUs: least-loaded placement always finds
    an empty device, so none holds two replicas and no VRAM sum needs
    the sorted walk."""
    sc = flash_crowd(n_routes=8, fleet="4xh100+4xa100+4xl40s", seed=3,
                     horizon_s=4 * 3600.0).to_scenario(Breakeven)
    res = run_mega(sc, backend=backend, compute_bound=False)
    assert res.counters["vused.sorted"] == 0
    assert res.counters["place.calls"] > 0
    assert all(len(r.resident) <= 1 for r in res.devices)
