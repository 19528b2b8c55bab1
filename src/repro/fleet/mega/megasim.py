"""Vectorized mega-fleet simulator: the event loop's exact dynamics in
array-program form, for 500-5000-device multi-million-request days.

``fleetsim.run_fleet`` walks one heap event per request; at mega scale
(millions of arrivals) the per-event Python overhead dominates.
``run_mega`` keeps a heap, but only for STRUCTURAL events -- load
completions and armed idle-timeout evictions -- and retires the
per-request work in bulk:

  * Device state lives in plain per-device lists (occupied slots, VRAM,
    power state, watts).  Least-loaded placement reads the top of a lazy
    heap of per-device keys (occ, -free VRAM, index), pushed whenever a
    device's occupancy or VRAM changes; only when that top cannot take
    the model does it fall back to one masked scan over numpy views of
    the lists.
  * A model whose stream is in the common steady state -- exactly one
    warm replica, no load in flight or queued -- enters a WARM RUN: the
    maximal prefix of its remaining arrivals whose inter-arrival gaps
    are all <= the replica's idle timeout T is claimed in O(log n) via a
    precomputed big-gap index (``np.flatnonzero(np.diff(arr) > T)``),
    one eviction event is armed at ``arr[last] + T``, and the requests
    are committed lazily when the run ends.  Interruptions (capacity
    evictions from another model's load) commit the served prefix by
    ``searchsorted`` -- never by iterating requests.
  * A load in flight with no other replica absorbs every arrival before
    its completion straight into the wait queue (one slice), exactly the
    event loop's route-to-loading-device behaviour.
  * Energy is integrated per device as (state-interval dt) x (watts)
    only at actual power CHANGES, which is precisely what the event
    loop's ``EnergyMeter`` coalesces its timeline down to -- so the
    metered power segments come out float-identical and per-state Wh
    agrees to float-summation order.  Each change appends one record to
    the meter log; the backends reduce the log, and derive the
    coalesced power timeline from it, at finalize.

Correctness spine (the repo's equivalence-anchor discipline,
docs/ARCHITECTURE.md): on the pinned 10-model x 6-GPU seed-100 day,
``run_mega`` reproduces ``run_fleet``'s request count and cold starts
EXACTLY and total/per-state Wh to float-summation precision (pinned in
``tests/test_mega.py`` far inside the issue's 1e-3 relative budget).

Scope: warm-first routing, no consolidator/autoscaler, no drawn
preemption, and constant-timeout eviction policies (AlwaysOn / FixedTTL
/ Breakeven / CarbonBreakeven on a flat trace...), under either

  * the paper's evaluation convention, zero service time -- the warm
    runs and load absorbs above; or
  * real service time -- ``RooflineServiceTime``, or a positive
    ``ConstantServiceTime`` -- with ``max_batch`` decode slots per
    replica, ``run_fleet``'s semantics: a request's service is frozen
    at its admission occupancy (one table per (model, SKU), built
    once), requests that find every slot full wait FIFO, a busy or
    waited-on replica cannot be evicted and re-arms its idle timeout at
    its last completion, loads overlap serving, and a busy device draws
    its idle-or-loading base plus ``busy x (P_active(0.6) - P_ctx)``
    (the ACTIVE state).  Here every arrival and every completion is a
    heap event: the warm-run claim assumes instantaneous hits, so it is
    not taken.

Anything else raises ``MegaUnsupportedError`` so callers fall back to
``run_fleet`` instead of silently diverging; the probe is behavioural
(timeout sampled at several instants, arrival hook checked for
statefulness), not a class allowlist.
"""
from __future__ import annotations

import heapq
import itertools
import math
import time
import weakref
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.coldstart import loader_from_checkpoint
from repro.core.power_states import PowerState, state_power_w
from repro.core.scheduler import Policy
from repro.fleet.carbon import carbon_timeline_kg, carbon_timeline_multi_kg
from repro.fleet.catalog import (carbon_kg, energy_cost_usd,
                                 fleet_price_usd, get_mix)
from repro.fleet.cluster import _make_policy
from repro.fleet.fleetsim import (DeviceReport, FleetResult, FleetScenario,
                                  clairvoyant_bound, zone_decomposition)
from repro.fleet.mega.spans import Recorder, recording, span
from repro.fleet.pricing import (device_tier_map, price_fleet,
                                 tier_billed_seconds)
from repro.fleet.router import WarmFirstRouter
from repro.serving.service_model import (ConstantServiceTime,
                                         RooflineServiceTime)

# compact power-state codes: the first three are all a non-gated
# zero-service run can occupy, ACTIVE (busy decode slots) joins them
# when requests take service time; indices double as wire names via
# _STATE_KEYS
_BARE, _PARKED, _LOADING, _ACTIVE = 0, 1, 2, 3
_STATE_KEYS = (PowerState.BARE.value, PowerState.CTX_IDLE.value,
               PowerState.LOADING.value, PowerState.ACTIVE.value)
# run_fleet's utilization of a busy decode slot (Cluster.sync_power)
_SERVICE_UTIL = 0.6

# heap phases at equal timestamps, matching run_fleet's ordering
# (completions < arrivals) plus evictions AFTER everything -- the event
# loop's advance_to fires a deadline strictly BEFORE the next event's
# time, so a deadline equal to an event time must lose to that event
_P_DONE, _P_ARR, _P_EVICT = 0, 3, 4

_PROBE_TIMES = (0.0, 12345.678, 67801.25)


class MegaUnsupportedError(ValueError):
    """The scenario needs dynamics outside run_mega's scope (stateful
    policies, a service-time model other than ``RooflineServiceTime`` or
    ``ConstantServiceTime``, consolidation, autoscaling, drawn spot
    preemption, or a non-warm-first router).  Fall back to
    ``fleetsim.run_fleet``."""


def _takes_service(svc, max_batch: int) -> bool:
    """Whether requests take service time (the slot path) or not (the
    zero-service path); raises for a service model out of scope."""
    if isinstance(svc, ConstantServiceTime) and svc.service_s == 0.0:
        return False
    if not (isinstance(svc, RooflineServiceTime)
            or (isinstance(svc, ConstantServiceTime)
                and svc.service_s > 0.0)):
        raise MegaUnsupportedError(
            "run_mega supports zero service time, RooflineServiceTime and "
            f"a positive ConstantServiceTime (got "
            f"{getattr(svc, 'name', svc)!r} service)")
    if max_batch < 1:
        raise ValueError("need at least one decode slot per model")
    return True


def _probe_constant_timeout(policy) -> float:
    """Behavioural check that a policy is a constant idle timeout.

    Samples ``idle_timeout_s`` at several instants, and -- when the
    policy overrides the base no-op ``observe_arrival`` (duck-typed
    policies like CarbonBreakeven define their own) -- feeds it probe
    arrivals and re-samples, so stateful estimators (AdaptiveBreakeven)
    and time-varying stopping rules (CarbonBreakeven on a shaped trace)
    are rejected rather than mis-simulated."""
    try:
        ts = [policy.idle_timeout_s(t) for t in _PROBE_TIMES]
    except Exception as exc:
        raise MegaUnsupportedError(
            f"policy {getattr(policy, 'name', policy)!r} needs per-gap "
            f"context ({exc}); run_mega supports constant timeouts only"
        ) from exc
    base_hook = getattr(type(policy), "observe_arrival", None) \
        is Policy.observe_arrival
    if not base_hook:
        policy.observe_arrival(_PROBE_TIMES[0])
        policy.observe_arrival(_PROBE_TIMES[1])
        if [policy.idle_timeout_s(t) for t in _PROBE_TIMES] != ts:
            raise MegaUnsupportedError(
                f"policy {getattr(policy, 'name', policy)!r} adapts to "
                f"arrivals; run_mega supports constant timeouts only")
    if any(t != ts[0] for t in ts):
        raise MegaUnsupportedError(
            f"policy {getattr(policy, 'name', policy)!r} varies its "
            f"timeout over the day; run_mega supports constant timeouts")
    if not (ts[0] == math.inf or ts[0] > 0.0):
        raise MegaUnsupportedError(
            f"policy {getattr(policy, 'name', policy)!r} returned "
            f"non-positive timeout {ts[0]!r}")
    return float(ts[0])


class _Rep:
    """One (device, model) replica: the ManagedModel fields the mega
    dynamics need."""
    __slots__ = ("resident", "loading", "evict_at", "gen", "vram", "pos",
                 "busy", "q")

    def __init__(self, vram: float, pos: int):
        self.resident = False
        self.loading = False
        self.evict_at = math.inf
        self.gen = 0            # bumped on every (re)arm/evict: stale
        self.vram = vram        # eviction events carry the gen they saw
        self.pos = pos          # registration index on its device
        # service path only: busy decode slots and the FIFO of waiting
        # arrival times (for a slot or for the load); both pin the
        # replica, as run_fleet's pins do
        self.busy = 0
        self.q: deque = deque()

    def __repr__(self):  # pragma: no cover - debug aid
        return (f"_Rep(res={self.resident}, load={self.loading}, "
                f"evict_at={self.evict_at:g})")


def _scan_least_loaded(need: float, occ, vused, vcap, scap) -> int:
    """run_fleet's least-loaded placement over the per-device sequences:
    the lexicographic argmin of (occ, -free VRAM, index) over the devices
    with a free slot and room for ``need`` GB, else over all devices.
    Staged boolean masks over numpy views, O(N) a call."""
    occ, scap = np.asarray(occ), np.asarray(scap)
    free_v = np.asarray(vcap) - np.asarray(vused)
    cand = np.flatnonzero((scap - occ >= 1) & (free_v >= need))
    if cand.size == 0:
        cand = np.arange(len(occ))
    o = occ[cand]
    cand = cand[o == o.min()]
    f = free_v[cand]
    return int(cand[f == f.max()][0])


class _PlaceHeap:
    """Lazy min-heap of per-device placement keys (occ, -free VRAM,
    index), so a cold placement reads its answer off the top instead of
    scanning all N devices.

    ``set`` records a device's new occupancy and VRAM sum and pushes its
    key; the old entry stays behind, stale, and is popped when it
    surfaces.  The heap is rebuilt from the current keys once it holds
    more than 4N entries.  The top is the argmin over ALL devices, so it
    is ``_scan_least_loaded``'s answer whenever it has a free slot and
    room for the model; ``top`` returns -1 otherwise, and the caller
    scans."""
    __slots__ = ("vcap", "scap", "key", "heap", "bound", "calls", "scans")

    def __init__(self, vcap: List[float], scap: List[int]):
        self.vcap = vcap
        self.scap = scap
        self.key = [(0, 0.0 - v, d) for d, v in enumerate(vcap)]
        self.heap = list(self.key)
        heapq.heapify(self.heap)
        self.bound = 4 * len(vcap)
        self.calls = 0          # cold placements asked
        self.scans = 0          # of them, the top could not answer

    def set(self, d: int, occ: int, vused: float) -> None:
        # -free from the float the scan reads: vused - vcap == -(vcap -
        # vused) exactly, so ties and boundaries fall as in the scan
        k = (occ, vused - self.vcap[d], d)
        if k == self.key[d]:
            return              # unchanged
        self.key[d] = k
        heapq.heappush(self.heap, k)
        if len(self.heap) > self.bound:
            self.heap = list(self.key)
            heapq.heapify(self.heap)

    def top(self, need: float) -> int:
        self.calls += 1
        heap, key = self.heap, self.key
        k = heap[0]
        while k != key[k[2]]:
            heapq.heappop(heap)         # stale: that device moved on
            k = heap[0]
        o, nf, d = k
        if o < self.scap[d] and -nf >= need:
            return d
        self.scans += 1
        return -1


class _BigGapCache:
    """Bounded LRU of derived per-stream arrays, shared across
    ``run_mega`` calls on the same ``FleetTrace``.

    Keyed by ``(id(arrivals), horizon)`` of the raw ``arrivals_s``
    object each ``FleetModel`` carries: a ``FleetTrace`` hands every
    ``to_scenario`` the SAME per-route arrays, so repeat runs (sweeps)
    hit.  An entry holds the sorted/horizon-filtered arrival array plus
    the stream's ``T -> big-gap index`` dict, so neither is rebuilt per
    run; a weakref to the source guards against ``id()`` reuse after
    gc.  Sources that cannot be weakly referenced (plain lists) are
    derived fresh each run -- the pre-cache behaviour.
    """

    def __init__(self, maxsize: int = 256, max_timeouts: int = 16):
        if maxsize < 1 or max_timeouts < 1:
            raise ValueError("cache bounds must be positive")
        self.maxsize = maxsize             # streams kept (LRU evicted)
        self.max_timeouts = max_timeouts   # per-stream biggap dict cap
        self.hits = 0
        self.misses = 0
        self._d: Dict[Tuple[int, float], tuple] = {}   # insertion = LRU

    def __len__(self) -> int:
        return len(self._d)

    def clear(self) -> None:
        self._d.clear()
        self.hits = self.misses = 0

    def stream_arrays(self, source, horizon: float
                      ) -> Tuple[np.ndarray, Dict[float, np.ndarray]]:
        """The (derived arrival array, shared biggap dict) for a raw
        ``arrivals_s`` object at a horizon, cached."""
        key = (id(source), float(horizon))
        ent = self._d.get(key)
        if ent is not None and ent[0]() is source:
            self.hits += 1
            self._d.pop(key)               # LRU bump
            self._d[key] = ent
            return ent[1], ent[2]
        self.misses += 1
        arr = np.sort(np.asarray(source, dtype=np.float64))
        arr = arr[(arr >= 0.0) & (arr < horizon)]
        biggap: Dict[float, np.ndarray] = {}
        try:
            ref = weakref.ref(source)
        except TypeError:
            return arr, biggap             # not weakly referenceable
        if ent is not None:
            self._d.pop(key, None)         # stale entry from id() reuse
        while len(self._d) >= self.maxsize:
            self._d.pop(next(iter(self._d)))
        self._d[key] = (ref, arr, biggap)
        return arr, biggap


biggap_cache = _BigGapCache()


class _Stream:
    """One model's arrival stream + replica-set bookkeeping."""
    __slots__ = ("mid", "arr", "n", "ptr", "ev", "res", "loading", "queued",
                 "waiters", "run_active", "run_dev", "run_last", "run_E0",
                 "suspended", "biggap")

    def __init__(self, mid: str, arr: np.ndarray,
                 biggap: Optional[Dict[float, np.ndarray]] = None):
        self.mid = mid
        self.arr = arr                   # sorted, within [0, horizon)
        self.n = int(arr.size)
        self.ptr = 0                     # next unconsumed arrival index
        self.ev = 0                      # arrival-event version (staleness)
        self.res: set = set()            # device indices with warm replica
        self.loading: set = set()        # device indices mid-load
        self.queued: set = set()         # queued-not-started loads
        self.waiters: Dict[int, list] = {}
        self.run_active = False
        self.run_dev = -1
        self.run_last = -1
        self.run_E0 = math.inf
        self.suspended = False           # arrivals pre-absorbed into a load
        # T -> big-gap indices; shared through biggap_cache so repeat
        # runs on the same FleetTrace reuse the scans
        self.biggap: Dict[float, np.ndarray] = \
            {} if biggap is None else biggap

    def biggaps(self, T: float) -> np.ndarray:
        """Indices i with arr[i+1] - arr[i] > T (a warm run starting at
        or before i ends at i).  Cached per distinct timeout (timeouts
        differ per SKU, not per device, so this stays tiny), bounded at
        ``biggap_cache.max_timeouts`` oldest-out."""
        got = self.biggap.get(T)
        if got is None:
            if math.isinf(T):
                got = np.empty(0, dtype=np.int64)
            else:
                got = np.flatnonzero(np.diff(self.arr) > T)
            if len(self.biggap) >= biggap_cache.max_timeouts:
                self.biggap.pop(next(iter(self.biggap)))
            self.biggap[T] = got
        return got


def _log_arrays(log: List[float]) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray, np.ndarray]:
    """The meter log as arrays, in log order: a flat list of records of
    four, ``(device * S + state, watts, t0, t)``, one per power
    transition, becomes (keys, watts, a, b)."""
    rec = np.ascontiguousarray(
        np.fromiter(log, dtype=np.float64, count=len(log)).reshape(-1, 4).T)
    return rec[0].astype(np.int64), rec[1], rec[2], rec[3]


def _power_segments(dev: np.ndarray, a: np.ndarray, b: np.ndarray,
                    pw: np.ndarray, n_dev: int
                    ) -> Tuple[List[Tuple[float, float, float]], np.ndarray]:
    """The coalesced power timeline, ``FleetResult.power_timeline``, from
    the meter log: device by device in device order, and within a device
    in log order, every interval with ``b - a > 0``, merged into the one
    before it when that one ends at ``a`` with the same watts -- the rule
    the event loop's ``EnergyMeter`` coalesces by.  Returns the fleet's
    ``(t0, t1, watts)`` tuples and ``bounds``: device ``d``'s segments
    are ``[bounds[d]:bounds[d + 1]]``."""
    kept = np.flatnonzero(b - a > 0.0)
    # a stable sort of 16-bit keys is a radix sort
    key = dev[kept].astype(np.int16 if n_dev <= 1 << 15 else np.int64)
    kept = kept[np.argsort(key, kind="stable")]
    dk, ak, bk, pk = dev[kept], a[kept], b[kept], pw[kept]
    new = np.ones(kept.size, dtype=bool)
    new[1:] = (dk[1:] != dk[:-1]) | (bk[:-1] != ak[1:]) | (pk[1:] != pk[:-1])
    first = np.flatnonzero(new)
    last = np.empty_like(first)
    last[:-1] = first[1:] - 1
    last[-1:] = kept.size - 1
    segs = list(zip(ak[first].tolist(), bk[last].tolist(),
                    pk[first].tolist()))
    return segs, np.searchsorted(dk[first], np.arange(n_dev + 1))


class _Fin:
    """What a bulk backend hands back at finalize time."""
    __slots__ = ("energy_j", "dur_s", "waits", "carbon_dev",
                 "carbon_timeline", "power_timeline", "tier_billed_s")

    def __init__(self, energy_j, dur_s, waits, carbon_dev, carbon_timeline,
                 power_timeline, tier_billed_s=None):
        self.energy_j = energy_j           # [N][S] joules per state
        self.dur_s = dur_s                 # [N][S] seconds per state
        self.waits = waits                 # per-request waits, any order
        self.carbon_dev = carbon_dev       # [N] kgCO2e
        self.carbon_timeline = carbon_timeline
        self.power_timeline = power_timeline   # coalesced, device order
        # tier -> billed seconds when the backend fused it into the
        # metering pass; None -> run_mega re-derives it from reports
        self.tier_billed_s = tier_billed_s


class _NumpyBulk:
    """The reference bulk backend: the exact inline numpy/Python paths
    the simulator shipped with (the bit-exact anchor vs ``run_fleet``).

    The seam: the event loop owns all STRUCTURAL state (heap, replica
    sets, pointers) and the meter log, and calls the backend for every
    bulk operation -- waiter billing, big-gap run claiming, and the
    finalize pass (energy buckets and power timeline from the meter
    log, carbon integration, waits assembly).  Both backends
    see identical calls in identical order, so every control-flow
    decision (routing tie-breaks, run extents) is backend-invariant by
    construction; only the arithmetic engine differs.

    Timing: finalize marks its phases as spans (``spans.py``).  The
    calls the event loop makes per event cannot carry a span, so their
    wall accumulates in ``in_loop`` under the ``phase_timings`` key it
    belongs to: run claims under ``biggap_s``, waiter billing under
    ``billing_s``.
    """

    name = "numpy"
    wants_tables = False

    def __init__(self, n_dev: int, n_state: int):
        self.n_dev = n_dev
        self.n_state = n_state
        self.waits: List[float] = []
        self.in_loop = {"biggap_s": 0.0, "billing_s": 0.0}

    def prepare(self, streams, stream_Ts) -> None:
        pass

    def last_of_run(self, ms: _Stream, T: float) -> int:
        t0 = time.perf_counter()
        big = ms.biggaps(T)
        j = int(np.searchsorted(big, ms.ptr))
        last = int(big[j]) if j < big.size else ms.n - 1
        self.in_loop["biggap_s"] += time.perf_counter() - t0
        return last

    def absorb(self, ms: _Stream, d: int, lo: int, hi: int,
               t_done: float) -> None:
        t0 = time.perf_counter()
        ms.waiters.setdefault(d, []).extend(ms.arr[lo:hi].tolist())
        self.in_loop["billing_s"] += time.perf_counter() - t0

    def wait_one(self, ms: _Stream, d: int, t: float) -> None:
        ms.waiters.setdefault(d, []).append(t)

    def wait(self, w: float) -> None:
        """One admitted request's wait (the service path bills each
        admission from a replica's queue as it happens)."""
        self.waits.append(w)

    def waiter_count(self, ms: _Stream, d: int) -> int:
        return len(ms.waiters.get(d, ()))

    def drain(self, ms: _Stream, d: int, t: float) -> int:
        w = ms.waiters.pop(d, None)
        if not w:
            return 0
        t0 = time.perf_counter()
        self.waits.extend(t - a for a in w)
        self.in_loop["billing_s"] += time.perf_counter() - t0
        return len(w)

    def finalize(self, log: List[float], trace, horizon: float,
                 dev_traces=None, tiers=None) -> _Fin:
        with span("mega.meter"):
            keys, pw, a, b = _log_arrays(log)
            dt = b - a
            # ufunc.at adds unbuffered, element by element in log order:
            # each bucket is the left fold of += the event loop ran
            energy_j = np.zeros(self.n_dev * self.n_state)
            dur_s = np.zeros(self.n_dev * self.n_state)
            np.add.at(energy_j, keys, dt * pw)
            np.add.at(dur_s, keys, dt)
            fleet_segments, bounds = _power_segments(
                keys // self.n_state, a, b, pw, self.n_dev)
            bounds = bounds.tolist()
            segs = [fleet_segments[lo:hi]
                    for lo, hi in zip(bounds[:-1], bounds[1:])]
        with span("mega.billing"):
            waits = np.asarray(self.waits, dtype=np.float64)
        with span("mega.carbon"):
            if dev_traces is not None and any(tr is not trace
                                              for tr in dev_traces):
                # multi-zone fleet: each device integrates against its
                # own zone's trace; the fleet timeline folds the
                # per-device segments in the exact order fleet_segments
                # concatenates
                carbon_dev = [tr.carbon_for_segments(s)
                              for tr, s in zip(dev_traces, segs)]
                timeline = carbon_timeline_multi_kg(
                    [(tr, sg) for tr, s in zip(dev_traces, segs)
                     for sg in s], end_s=horizon)
            else:
                carbon_dev = [trace.carbon_for_segments(s) for s in segs]
                timeline = carbon_timeline_kg(trace, fleet_segments,
                                              end_s=horizon)
        shape = (self.n_dev, self.n_state)
        return _Fin(energy_j.reshape(shape).tolist(),
                    dur_s.reshape(shape).tolist(), waits, carbon_dev,
                    timeline, fleet_segments)


def _phase_timings(rec: Recorder, in_loop: Dict[str, float]
                   ) -> Dict[str, float]:
    """``FleetResult.phase_timings`` from one run's spans.

    The five bulk keys keep their meaning on both backends: big-gap
    tables and run claims (``biggap_s``), waiter billing
    (``billing_s``), the compiled metering call (``energy_s``; 0 on the
    numpy backend), the carbon integral and the rest of ``mega.meter``,
    the metering pass's host side (``carbon_s``: the meter log's arrays
    and the power timeline, and on the numpy backend its energy
    buckets), and their sum ``bulk_scan_s``.  The run claims and
    numpy's waiter billing happen inside the event loop, so
    ``bulk_scan_s`` overlaps ``event_loop_s`` by them.  The rest is wall
    per phase, with each compiled call (``mega.*.call``) split into
    ``compile_s`` and ``bulk_call_s`` and the bulk phases' host side in
    ``bulk_host_s``;
    ``mega.prepare`` + ``mega.finalize`` is ``bulk_host_s`` +
    ``bulk_call_s`` + the compiles inside the calls, which are all of
    ``compile_s``.  A run whose requests take service time adds
    ``serve_s``, the host seconds of its service path inside the event
    loop (completions, admissions, the slot queues)."""
    w = rec.wall
    calls = [s for s in rec.spans if s.name.endswith(".call")]
    call_s = sum((s.wall for s in calls), 0.0)
    t = {"biggap_s": in_loop["biggap_s"] + w("mega.prepare"),
         "billing_s": in_loop["billing_s"] + w("mega.billing"),
         "energy_s": w("mega.meter.call"),
         "carbon_s": (w("mega.carbon") + w("mega.meter")
                      - w("mega.meter.call"))}
    t["bulk_scan_s"] = sum(t.values())
    t.update(run_s=w("mega.run"), scenario_s=w("mega.scenario"),
             event_loop_s=w("mega.event_loop"), report_s=w("mega.report"),
             compile_s=rec.compile_s,
             bulk_call_s=call_s - sum((s.compile_s for s in calls), 0.0),
             bulk_host_s=w("mega.prepare") + w("mega.finalize") - call_s)
    if "serve_s" in in_loop:
        t["serve_s"] = in_loop["serve_s"]
    return t


def run_mega(scenario: FleetScenario, *,
             compute_bound: bool = True,
             backend: str = "numpy") -> FleetResult:
    """Vectorized replacement for ``run_fleet`` on its supported scope
    (see module docstring); raises ``MegaUnsupportedError`` otherwise.

    ``compute_bound=False`` skips the O(requests) clairvoyant-bound pass
    (reported as 0.0) -- the bound is a per-gap Python loop and would
    dominate wall-clock on multi-million-request days.

    ``backend`` selects the bulk-scan engine: ``"numpy"`` (default) is
    the bit-exact anchor vs ``run_fleet``; ``"jax"`` retires the bulk
    phases -- big-gap scans, deferred waiter billing, per-state energy
    segment-sums, and the carbon trapezoid integral -- as jit-compiled
    array programs (``fleet/mega/jaxback.py``, docs/SCALE.md).  Both
    backends drive the identical structural event loop, so request
    counts and cold starts are equal and float totals agree to <=1e-9
    relative (pinned in tests).  ``FleetResult.phase_timings`` reports
    wall seconds per phase and ``FleetResult.counters`` the programs
    lowered and loaded, for either backend (``spans.py``,
    docs/SCALE.md); a run with service time also reports ``serve_s``
    and the counters ``serve.admissions``, ``serve.slot_waits``
    (admissions from a full pool's queue at a completion) and
    ``serve.completions``.  Every run counts its cold placements,
    ``place.calls``, and how many of them the placement heap's top could
    not answer, ``place.scans``.
    """
    with recording("mega.run") as rec:
        res, in_loop = _run_mega(scenario, compute_bound, backend, rec)
    res.phase_timings = _phase_timings(rec, in_loop)
    res.counters = dict(rec.counters)
    return res


def _run_mega(sc: FleetScenario, compute_bound: bool, backend: str,
              rec: Recorder) -> Tuple[FleetResult, Dict[str, float]]:
    """The body of ``run_mega``, marking its phases on ``rec``; returns
    the result and the backend's in-loop timings."""
    if backend == "numpy":
        _Bulk = _NumpyBulk
    elif backend == "jax":
        try:
            from repro.fleet.mega import jaxback
        except ImportError as exc:
            raise RuntimeError(
                "run_mega(backend='jax') needs jax, which is not "
                "importable in this environment; install jax or use "
                "backend='numpy'") from exc
        _Bulk = jaxback._JaxBulk
    else:
        raise ValueError(
            f"unknown backend {backend!r}: expected 'numpy' or 'jax'")
    # ---- scope guard ------------------------------------------------------
    rec.phase("mega.scenario")
    if not (sc.router == "warm-first"
            or isinstance(sc.router, WarmFirstRouter)):
        raise MegaUnsupportedError(
            f"run_mega supports warm-first routing only, got {sc.router!r}")
    if sc.consolidator is not None:
        raise MegaUnsupportedError("run_mega does not support consolidation")
    if sc.autoscaler is not None:
        raise MegaUnsupportedError("run_mega does not support autoscaling")
    svc = sc.resolved_service_model()
    svc_on = _takes_service(svc, sc.max_batch)
    if sc.preemptions is not None and sc.preemptions.draw(
            sc.devices, sc.device_tiers(), sc.horizon_s):
        # guard on the DRAW, not the model: an all-on-demand plan under
        # a preemption model has no revocable devices and replays
        # exactly -- only actual fault events exceed the mega scope
        raise MegaUnsupportedError(
            "run_mega does not support spot preemption faults; "
            "fall back to run_fleet")
    if not sc.devices:
        raise ValueError("empty fleet")

    trace = sc.resolved_carbon_trace()
    horizon = float(sc.horizon_s)
    # per-device zone bindings (tentpole): accounting-only at mega scope
    # -- policies keep the SCENARIO trace (so the per-(model, SKU)
    # loader/timeout cache stays valid) and warm-first routing is
    # zone-blind, but every device's joules integrate against its own
    # zone's intensity.  Single-zone fleets bind the same trace object
    # everywhere, keeping the bit-exact anchor vs run_fleet.
    zones = sc.device_zones()
    dev_traces_by_id = sc.device_carbon_traces(trace)
    multi_zone = len(set(zones.values())) > 1

    # ---- device vectors (index = rank in sorted(instance_id), so integer
    # comparisons reproduce every instance-id string tie-break) ------------
    by_id = {d.instance_id: d for d in sc.devices}
    if len(by_id) != len(sc.devices):
        raise ValueError("duplicate instance_id in fleet")
    dids = sorted(by_id)
    devs = [by_id[i] for i in dids]
    N = len(devs)
    vcap = [float(d.sku.vram_gb) for d in devs]
    scap = [int(d.sku.slots) for d in devs]
    occ = [0] * N
    vused = [0.0] * N
    place = _PlaceHeap(vcap, scap)
    p_bare = [state_power_w(d.profile, PowerState.BARE) for d in devs]
    p_park = [state_power_w(d.profile, PowerState.CTX_IDLE) for d in devs]
    state = [_BARE] * N
    watts = [p_bare[d] for d in range(N)]
    since = [0.0] * N
    S = 4 if svc_on else 3      # power-state codes a device can hold
    bulk = _Bulk(N, S)
    # the meter log: one record of four a power transition, flat (see
    # _log_arrays); the backends reduce it at finalize
    meter_log: List[float] = []
    log_rec = meter_log.extend
    touched = [False] * (N * S)                 # by log key d * S + s
    key_order: List[List[int]] = [[] for _ in range(N)]
    res_count = [0] * N
    d_cold = [0] * N
    d_reqs = [0] * N
    dev_models: List[List[str]] = [[] for _ in range(N)]   # registration order
    act: List[set] = [set() for _ in range(N)]   # currently resident|loading

    def _touch(d: int, s: int) -> None:
        k = d * S + s
        if not touched[k]:
            touched[k] = True
            key_order[d].append(s)

    def _trans(d: int, t: float, ns: int, w: float) -> None:
        """Log the open interval under the current state's key and enter
        (ns, w) -- the EnergyMeter transition, minus the dt=0 flushes
        the event loop performs (which change no joules and coalesce
        away in its timeline)."""
        s = state[d]
        k = d * S + s
        log_rec((k, watts[d], since[d], t))
        if not touched[k]:      # _touch inlined: one call fewer each
            touched[k] = True
            key_order[d].append(s)
        state[d] = ns
        watts[d] = w
        since[d] = t

    n_sorted = 0                # VRAM sums that took the sorted walk

    def recompute_vused(d: int) -> None:
        """Fresh registration-order sum, so capacity comparisons see the
        exact float the event loop's ``vram_used_gb`` computes (an
        incremental add/subtract could drift in the last bits and flip a
        boundary ``fits`` decision).  Walks only the currently-contributing
        replicas (``act``), sorted back into registration order -- NOT all
        models ever registered on the device, which grows toward M over a
        long day and made this O(M * events).  One replica or none needs
        no sort: its sum is exact in any order."""
        nonlocal n_sorted
        a = act[d]
        if len(a) > 1:
            n_sorted += 1
            a = sorted(a, key=lambda m: reps[(d, m)].pos)
        s = 0.0
        for m in a:
            s += reps[(d, m)].vram
        vused[d] = s
        place.set(d, occ[d], s)

    # ---- per-(model, SKU) constants: loader + probed constant timeout ----
    specs = {}
    for fm in sc.models:
        if fm.spec.model_id in specs:
            raise MegaUnsupportedError(
                f"duplicate model_id {fm.spec.model_id!r}: run_fleet would "
                f"merge their specs; run_mega refuses")
        specs[fm.spec.model_id] = fm.spec
    sku_of = [d.sku.key for d in devs]
    _per_sku: Dict[Tuple[str, str], Tuple[object, float]] = {}

    def _loader_T(mid: str, d: int):
        key = (mid, sku_of[d])
        got = _per_sku.get(key)
        if got is None:
            spec = specs[mid]
            if spec.loader is not None:
                loader = spec.loader
            else:
                loader = loader_from_checkpoint(
                    mid, spec.checkpoint_bytes, devs[d].profile)
            policy = _make_policy(spec.policy_factory, loader,
                                  devs[d].profile, trace)
            got = (loader, _probe_constant_timeout(policy))
            _per_sku[key] = got
        return got

    # ---- service path: per-(model, SKU) service tables, slot counts ------
    mb = sc.max_batch
    inc = [d.profile.active_power_w(_SERVICE_UTIL) - d.profile.p_ctx_w
           for d in devs]       # watts each busy decode slot adds
    dev_busy = [0] * N
    _svc_tab: Dict[Tuple[str, str], Tuple[float, ...]] = {}

    def svc_table(mid: str, d: int) -> Tuple[float, ...]:
        key = (mid, sku_of[d])
        got = _svc_tab.get(key)
        if got is None:
            got = svc.table(specs[mid], devs[d], mb)
            if min(got) <= 0.0:
                raise MegaUnsupportedError(
                    f"service model gives {min(got)!r} s for {mid!r} on "
                    f"{sku_of[d]!r}; run_mega serves positive times only")
            _svc_tab[key] = got
        return got

    # ---- streams, replicas, heap -----------------------------------------
    streams: Dict[str, _Stream] = {}
    for fm in sc.models:
        a, shared_biggap = biggap_cache.stream_arrays(fm.arrivals_s,
                                                      horizon)
        streams[fm.spec.model_id] = _Stream(fm.spec.model_id, a,
                                            shared_biggap)
    # the big-gap tables serve the warm-run claim, which the service
    # path does not take
    tables = bulk.wants_tables and not svc_on
    if tables:
        # candidate constant timeouts per stream: one probe per (model,
        # SKU present).  A probe failure is skipped, NOT raised -- the
        # numpy path probes lazily on first routing, so scope rejection
        # must surface at the same instant on either backend.
        rep_dev: Dict[str, int] = {}
        for i, k in enumerate(sku_of):
            rep_dev.setdefault(k, i)
        stream_Ts: Dict[str, List[float]] = {}
        for mid in streams:
            Ts: List[float] = []
            for d0 in rep_dev.values():
                try:
                    T = _loader_T(mid, d0)[1]
                except MegaUnsupportedError:
                    continue
                if T not in Ts:
                    Ts.append(T)
            stream_Ts[mid] = Ts

    reps: Dict[Tuple[int, str], _Rep] = {}

    def get_rep(d: int, mid: str) -> _Rep:
        rep = reps.get((d, mid))
        if rep is None:
            rep = _Rep(specs[mid].vram_gb, len(dev_models[d]))
            reps[(d, mid)] = rep
            dev_models[d].append(mid)
        return rep

    heap: list = []
    seq = itertools.count()
    n_live = 0                  # pending arrival + load_done heap entries
    n_zero = 0                  # warm-served requests (zero added latency)
    replica_log: Dict[str, List[Tuple[float, int]]] = {}
    inflight: List[Optional[str]] = [None] * N     # loader channel
    dq = [deque() for _ in range(N)]               # queued loads (FIFO)
    dq_set: List[set] = [set() for _ in range(N)]

    def push(t: float, phase: int, payload: tuple) -> None:
        heapq.heappush(heap, (t, phase, next(seq), payload))

    def push_arr(ms: _Stream) -> None:
        nonlocal n_live
        ms.ev += 1              # at most ONE valid arrival event per stream
        push(float(ms.arr[ms.ptr]), _P_ARR, (ms.mid, ms.ptr, ms.ev))
        n_live += 1

    def log_replicas(ms: _Stream, t: float) -> None:
        log = replica_log[ms.mid]
        n = len(ms.res)
        if not log or log[-1][1] != n:
            log.append((t, n))

    def arm(d: int, mid: str, t: float) -> None:
        rep = reps[(d, mid)]
        rep.gen += 1
        T = _loader_T(mid, d)[1]
        if math.isinf(T):
            rep.evict_at = math.inf
        else:
            rep.evict_at = t + T
            push(rep.evict_at, _P_EVICT, (d, mid, rep.gen))

    def cur_evict_at(d: int, mid: str, t: float) -> float:
        """The deadline the event loop would see at instant t -- for a
        replica mid-run, that is the last run arrival before t plus its
        timeout (each warm hit re-arms), reconstructed lazily."""
        ms = streams[mid]
        if ms.run_active and ms.run_dev == d:
            k = int(np.searchsorted(ms.arr, t, "left"))
            k = min(k, ms.run_last + 1)
            if k <= ms.ptr:
                return ms.run_E0
            return float(ms.arr[k - 1]) + _loader_T(mid, d)[1]
        return reps[(d, mid)].evict_at

    def evict_replica(d: int, mid: str, t: float) -> None:
        """Unload now (idle timeout fired, or make_room pressure).  A
        replica mid-run first commits its served prefix (arrivals
        strictly before t were warm hits)."""
        nonlocal n_zero
        rep = reps[(d, mid)]
        ms = streams[mid]
        if ms.run_active and ms.run_dev == d:
            k = int(np.searchsorted(ms.arr, t, "left"))
            k = min(max(k, ms.ptr), ms.run_last + 1)
            served = k - ms.ptr
            d_reqs[d] += served
            n_zero += served
            ms.ptr = k
            ms.run_active = False
        rep.resident = False
        rep.evict_at = math.inf
        rep.gen += 1
        act[d].discard(mid)
        ms.res.discard(d)
        occ[d] -= 1
        res_count[d] -= 1
        recompute_vused(d)
        log_replicas(ms, t)
        if res_count[d] == 0 and state[d] == _PARKED:
            _trans(d, t, _BARE, p_bare[d])
        if not svc_on and ms.ptr < ms.n and not ms.suspended:
            push_arr(ms)        # stream continues cold (or on other replicas)

    def make_room(d: int, mid_new: str, t: float) -> None:
        need = specs[mid_new].vram_gb

        def over() -> bool:
            return (vused[d] + need > vcap[d] or occ[d] + 1 > scap[d])

        if not over():
            return
        # the event loop scans its models dict (registration order) and
        # stable-sorts by deadline -- reproduce that from the small active
        # set: registration order first, then a stable deadline sort
        # (a replica with busy slots or waiters is pinned, never a victim)
        victims = sorted((m for m in act[d]
                          if m != mid_new and reps[(d, m)].resident
                          and not reps[(d, m)].busy and not reps[(d, m)].q),
                         key=lambda m: reps[(d, m)].pos)
        victims.sort(key=lambda m: cur_evict_at(d, m, t))
        for m in victims:
            if not over():
                break
            evict_replica(d, m, t)

    def least_loaded(mid: str) -> int:
        need = specs[mid].vram_gb
        d = place.top(need)
        return d if d >= 0 else _scan_least_loaded(need, occ, vused, vcap,
                                                   scap)

    def start_load(d: int, ms: _Stream, t: float) -> None:
        nonlocal n_live
        rep = get_rep(d, ms.mid)
        make_room(d, ms.mid, t)
        rep.loading = True
        act[d].add(ms.mid)
        ms.loading.add(d)
        occ[d] += 1
        recompute_vused(d)
        loader = _loader_T(ms.mid, d)[0]
        if not svc_on:          # the service path recomposes (compose)
            _trans(d, t, _LOADING, loader.p_load_w)
        t_done = t + loader.t_load_s
        push(t_done, _P_DONE, (d, ms.mid))
        n_live += 1
        # the only replica coming up: every arrival before t_done routes
        # warm-first to this loading replica and waits -- absorb them in
        # one slice instead of one heap event each
        if (not svc_on and not ms.res and ms.loading == {d}
                and not ms.queued and ms.ptr < ms.n):
            k = int(np.searchsorted(ms.arr, t_done, "left"))
            if k > ms.ptr:
                bulk.absorb(ms, d, ms.ptr, k, t_done)
                ms.ptr = k
            ms.suspended = True

    def pump(d: int, t: float) -> None:
        """Start the next queued load if the serialized channel is free
        (run_fleet's pump_loader, minus migrations/wakes)."""
        if inflight[d] is not None:
            return
        q = dq[d]
        while q:
            mid = q.popleft()
            dq_set[d].discard(mid)
            ms = streams[mid]
            ms.queued.discard(d)
            rep = reps.get((d, mid))
            if rep is not None and (rep.resident or rep.loading):
                continue        # a racing load landed it meanwhile
            inflight[d] = mid
            start_load(d, ms, t)
            return

    def continue_stream(ms: _Stream) -> None:
        """Re-plan a stream after its replica set settled: enter a bulk
        warm run when the steady single-replica state holds, otherwise
        fall back to one heap event for the next arrival."""
        ms.suspended = False
        if ms.ptr >= ms.n:
            return
        if len(ms.res) == 1 and not ms.loading and not ms.queued:
            d = next(iter(ms.res))
            rep = reps[(d, ms.mid)]
            if float(ms.arr[ms.ptr]) > rep.evict_at:
                return          # idle gap: the armed eviction restarts us
            T = _loader_T(ms.mid, d)[1]
            last = bulk.last_of_run(ms, T)
            ms.run_active = True
            ms.run_dev = d
            ms.run_last = last
            ms.run_E0 = rep.evict_at
            arm(d, ms.mid, float(ms.arr[last]))
        else:
            push_arr(ms)

    def drain_waiters(d: int, ms: _Stream, t: float) -> None:
        d_reqs[d] += bulk.drain(ms, d, t)

    def on_load_done(t: float, d: int, mid: str) -> None:
        inflight[d] = None
        ms = streams[mid]
        rep = reps[(d, mid)]
        rep.loading = False
        rep.resident = True
        ms.loading.discard(d)
        ms.res.add(d)
        res_count[d] += 1       # occ and VRAM already count it (start_load)
        d_cold[d] += 1
        if svc_on:
            land_svc(t, d, ms, rep)
            return
        _trans(d, t, _PARKED, p_park[d])
        if ms.run_active:       # defensive: a run elsewhere cannot coexist
            nonlocal n_zero     # with a load in mega scope, but commit it
            k = int(np.searchsorted(ms.arr, t, "left"))
            k = min(max(k, ms.ptr), ms.run_last + 1)
            d_reqs[ms.run_dev] += k - ms.ptr
            n_zero += k - ms.ptr
            ms.ptr = k
            ms.run_active = False
        arm(d, mid, t)
        drain_waiters(d, ms, t)
        log_replicas(ms, t)
        pump(d, t)
        continue_stream(ms)

    def on_arrival(t: float, mid: str, idx: int, ev: int) -> None:
        nonlocal n_zero
        ms = streams[mid]
        if ev != ms.ev or idx != ms.ptr:
            return              # superseded by an absorb / run / re-push
        ms.ptr += 1
        locs = ms.res | ms.loading
        if locs:
            # warm-first: least-pressure warm replica; a mid-load replica
            # counts as a full pool so residency wins ties
            d = min(locs, key=lambda x: (bulk.waiter_count(ms, x),
                                         0 if x in ms.res else 1, x))
            if d in ms.res:
                d_reqs[d] += 1
                n_zero += 1
                if state[d] == _LOADING:
                    # run_fleet's settle-then-recompose flush creates the
                    # parked bucket (0 Wh) on a device serving a warm hit
                    # mid-another-model's-load; mirror the touched keys
                    _touch(d, _LOADING)
                    _touch(d, _PARKED)
                arm(d, mid, t)
                continue_stream(ms)
            else:
                bulk.wait_one(ms, d, t)
                if ms.ptr < ms.n and not ms.suspended:
                    push_arr(ms)
            return
        # cold: least-loaded placement, queue the load on that device's
        # serialized channel (dedup while queued or in flight)
        d = least_loaded(mid)
        rep = get_rep(d, mid)
        bulk.wait_one(ms, d, t)
        if not rep.loading and mid not in dq_set[d]:
            dq_set[d].add(mid)
            dq[d].append(mid)
            ms.queued.add(d)
            pump(d, t)
        if ms.ptr < ms.n and not ms.suspended:
            push_arr(ms)

    # ---- service path: slots, FIFO queues, completions on the heap -------
    # run_fleet's DeviceRuntime semantics, replica by replica: a request
    # pins its replica from arrival to completion (busy slot or queue
    # entry), the idle timeout re-arms when the last pin goes, and the
    # device's watts are recomposed after every event that touches it.
    n_adm = n_slot_waits = n_done = 0
    serve_s = 0.0

    def compose(d: int, t: float) -> None:
        """Cluster.sync_power: the idle-or-loading base plus one active
        increment per busy slot; a transition only where it changes."""
        b = dev_busy[d]
        lmid = inflight[d]
        if b:
            base = (_loader_T(lmid, d)[0].p_load_w if lmid is not None
                    else p_park[d])
            ns, w = _ACTIVE, base + b * inc[d]
        elif lmid is not None:
            ns, w = _LOADING, _loader_T(lmid, d)[0].p_load_w
        elif res_count[d]:
            ns, w = _PARKED, p_park[d]
        else:
            ns, w = _BARE, p_bare[d]
        if ns != state[d] or w != watts[d]:
            _trans(d, t, ns, w)

    def admit(d: int, mid: str, rep: _Rep, t: float) -> None:
        """Start one request now in a free slot of a resident replica:
        its service is frozen at the occupancy it is admitted at."""
        nonlocal n_live, n_adm
        b = rep.busy
        rep.busy = b + 1
        dev_busy[d] += 1
        d_reqs[d] += 1
        n_adm += 1
        push(t + svc_table(mid, d)[b], _P_DONE, (d, mid, rep))
        n_live += 1

    def admit_waiters(d: int, mid: str, rep: _Rep, t: float) -> int:
        """Admit queued requests, oldest first, into free slots."""
        n = 0
        while rep.q and rep.busy < mb:
            bulk.wait(t - rep.q.popleft())
            admit(d, mid, rep, t)
            n += 1
        return n

    def pin(rep: _Rep) -> None:
        rep.gen += 1            # the armed idle timeout no longer fires
        rep.evict_at = math.inf

    def land_svc(t: float, d: int, ms: _Stream, rep: _Rep) -> None:
        """A load landed: waiters fill the slots, the rest stay queued."""
        nonlocal serve_s
        t0 = time.perf_counter()
        if rep.q:
            pin(rep)
        else:
            arm(d, ms.mid, t)
        admit_waiters(d, ms.mid, rep, t)
        serve_s += time.perf_counter() - t0
        log_replicas(ms, t)
        pump(d, t)
        compose(d, t)

    def on_arrival_svc(t: float, mid: str, idx: int, ev: int) -> None:
        nonlocal n_zero, serve_s
        ms = streams[mid]
        if ev != ms.ev or idx != ms.ptr:
            return
        ms.ptr += 1
        locs = ms.res | ms.loading
        if locs:
            # warm-first: fewest waiting, then busy slots (a mid-load
            # replica counts as a full pool), then lowest index
            if len(locs) == 1:
                d = next(iter(locs))
            else:
                d = min(locs, key=lambda x: (
                    len(reps[(x, mid)].q),
                    reps[(x, mid)].busy + (0 if x in ms.res else mb), x))
            rep = reps[(d, mid)]
        else:
            d = least_loaded(mid)
            rep = get_rep(d, mid)
        pin(rep)
        if rep.resident and rep.busy < mb:
            t0 = time.perf_counter()
            admit(d, mid, rep, t)
            n_zero += 1
            serve_s += time.perf_counter() - t0
        else:
            rep.q.append(t)
            if not rep.resident and not rep.loading \
                    and mid not in dq_set[d]:
                dq_set[d].add(mid)
                dq[d].append(mid)
                ms.queued.add(d)
                pump(d, t)
        compose(d, t)
        if ms.ptr < ms.n:
            push_arr(ms)

    def on_serve_done(t: float, d: int, mid: str, rep: _Rep) -> None:
        """A completion frees one slot: the oldest waiter takes it, or,
        with nothing left in flight or queued, the idle timeout arms."""
        nonlocal n_done, n_slot_waits
        rep.busy -= 1
        dev_busy[d] -= 1
        n_done += 1
        if rep.q:
            n_slot_waits += admit_waiters(d, mid, rep, t)
        elif not rep.busy:
            arm(d, mid, t)
        compose(d, t)

    # ---- prewarm (run_fleet's Table-6 warm-start convention) --------------
    idx_of = {did: i for i, did in enumerate(dids)}
    for fm in sc.models:
        mid = fm.spec.model_id
        replica_log.setdefault(mid, [])
        if fm.spec.home is None:
            continue
        d = idx_of[fm.spec.home]
        need = fm.spec.vram_gb
        if not (scap[d] - occ[d] >= 1 and vcap[d] - vused[d] >= need):
            occ_a = np.array(occ)
            free_a = np.array(vcap) - np.array(vused)
            fitting = np.flatnonzero((np.array(scap) - occ_a >= 1)
                                     & (free_a >= need))
            if fitting.size == 0:
                continue        # starts cold
            free_v = free_a[fitting]
            order = np.lexsort((fitting, -free_v, occ_a[fitting]))
            d = int(fitting[order[0]])
        rep = get_rep(d, mid)
        rep.resident = True
        act[d].add(mid)
        occ[d] += 1
        res_count[d] += 1
        recompute_vused(d)
        d_cold[d] += 1
        streams[mid].res.add(d)
        _trans(d, 0.0, _PARKED, p_park[d])
        arm(d, mid, 0.0)
    for fm in sc.models:        # timeline origin, including zero-replica
        ms = streams[fm.spec.model_id]
        replica_log[ms.mid].append((0.0, len(ms.res)))
    if tables:
        rec.phase("mega.prepare")
        bulk.prepare(streams, stream_Ts)
    rec.phase("mega.event_loop")
    for fm in sc.models:        # kick every stream
        ms = streams[fm.spec.model_id]
        if ms.n == 0:
            continue
        if ms.res and not svc_on:
            continue_stream(ms)
        else:
            push_arr(ms)

    # ---- main loop: structural events only --------------------------------
    last_done_t = 0.0
    deferred: List[Tuple[float, int, str, int]] = []
    while heap:
        t, phase, _s, payload = heapq.heappop(heap)
        if phase == _P_EVICT:
            d, mid, gen = payload
            rep = reps.get((d, mid))
            if rep is None or not rep.resident or rep.gen != gen:
                continue
            if t < horizon or n_live > 0:
                # some later event (all remaining real events are strictly
                # later) or the final advance-to-horizon will cross this
                # deadline, so the event loop fires it at exactly t
                evict_replica(d, mid, t)
            else:
                # past the horizon with nothing left in flight: fires only
                # if the final clock (a load may overshoot) passes it
                deferred.append((t, d, mid, gen))
            continue
        n_live -= 1
        if phase == _P_ARR:
            mid, idx, ev = payload
            if svc_on:
                on_arrival_svc(t, mid, idx, ev)
            else:
                on_arrival(t, mid, idx, ev)
        elif len(payload) == 3:             # a serve completion
            last_done_t = max(last_done_t, t)
            t0 = time.perf_counter()
            on_serve_done(t, *payload)
            serve_s += time.perf_counter() - t0
        else:
            d, mid = payload
            last_done_t = max(last_done_t, t)
            on_load_done(t, d, mid)

    # arrivals all land before the horizon; only a load or a completion
    # can overshoot it
    final_clock = max(horizon, last_done_t)
    for t, d, mid, gen in deferred:
        rep = reps.get((d, mid))
        if (rep is not None and rep.resident and rep.gen == gen
                and t < final_clock):
            evict_replica(d, mid, t)

    # commit runs still warm at the end (their eviction deadline lies at
    # or beyond the final clock, so every claimed arrival was served)
    for ms in streams.values():
        if ms.run_active:
            served = ms.run_last + 1 - ms.ptr
            d_reqs[ms.run_dev] += served
            n_zero += served
            ms.ptr = ms.run_last + 1
            ms.run_active = False
        if ms.ptr != ms.n or ms.waiters:
            raise RuntimeError(
                f"mega invariant violated: stream {ms.mid!r} left "
                f"{ms.n - ms.ptr} arrivals unserved")
    if svc_on:
        if n_done != n_adm or any(r.q or r.busy for r in reps.values()):
            raise RuntimeError(
                "mega invariant violated: requests left queued or in "
                "service at the end of the day")
        bulk.in_loop["serve_s"] = serve_s
        rec.counters.update({"serve.admissions": n_adm,
                             "serve.slot_waits": n_slot_waits,
                             "serve.completions": n_done})
    rec.counters.update({"place.calls": place.calls,
                         "place.scans": place.scans,
                         "vused.sorted": n_sorted})
    for d in range(N):
        _trans(d, final_clock, state[d], watts[d])   # totals() flush
    rec.phase(None)

    # ---- bulk finalize: energy buckets and power timeline from the meter
    # log, billing, carbon integration -------------------------------------
    dev_trace_list = [dev_traces_by_id[did] for did in dids]
    tiers_map = device_tier_map(sc.devices, sc.price_tier)
    rec.phase("mega.finalize")
    fin = bulk.finalize(meter_log, trace, horizon, dev_trace_list,
                        tiers=[tiers_map[did] for did in dids])
    energy_j = fin.energy_j
    dur_s = fin.dur_s

    # ---- reports (same construction as run_fleet) -------------------------
    rec.phase("mega.report")
    reports = []
    for d in range(N):
        e_wh = {_STATE_KEYS[s]: energy_j[d][s] / 3600.0
                for s in key_order[d]}
        e_wh["total"] = sum(e_wh.values())
        durations = {_STATE_KEYS[s]: dur_s[d][s] for s in key_order[d]}
        reports.append(DeviceReport(
            instance_id=dids[d], sku=devs[d].sku.key,
            energy_wh=e_wh,
            parking_tax_wh=(dur_s[d][_PARKED]
                            * devs[d].profile.dvfs_step_w / 3600.0),
            cold_starts=d_cold[d], requests=d_reqs[d],
            resident=[m for m in dev_models[d] if reps[(d, m)].resident],
            meter_state=_STATE_KEYS[state[d]],
            carbon_kg=fin.carbon_dev[d],
            zone=zones[dids[d]],
            durations_s=durations))

    if compute_bound:
        lb_nongated, cv_sum = clairvoyant_bound(sc)
    else:
        lb_nongated = cv_sum = 0.0
    energy = sum(r.total_wh for r in reports)
    mix = get_mix(sc.zone)
    state_wh: Dict[str, float] = {}
    state_s: Dict[str, float] = {}
    for r in reports:
        for k, v in r.energy_wh.items():
            if k != "total":
                state_wh[k] = state_wh.get(k, 0.0) + v
        for k, v in r.durations_s.items():
            state_s[k] = state_s.get(k, 0.0) + v
    zone_wh, zone_kg = zone_decomposition(reports)
    if multi_zone:
        # same per-zone pricing as run_fleet's multi-zone branch
        energy_usd = math.fsum(
            energy_cost_usd(wh, get_mix(z)) for z, wh in zone_wh.items())
        kg_flat = math.fsum(
            carbon_kg(wh, get_mix(z)) for z, wh in zone_wh.items())
    else:
        energy_usd = energy_cost_usd(energy, mix)
        kg_flat = carbon_kg(energy, mix)
    cost = price_fleet(sc.devices, reports, default_tier=sc.price_tier,
                       energy_usd=energy_usd)
    tier_billed = (fin.tier_billed_s if fin.tier_billed_s is not None
                   else tier_billed_seconds(sc.devices, reports,
                                            sc.price_tier))
    all_lat = np.concatenate([np.zeros(n_zero), fin.waits])
    return FleetResult(
        router="warm-first", horizon_s=horizon, devices=reports,
        energy_wh=energy,
        parking_tax_wh=sum(r.parking_tax_wh for r in reports),
        cold_starts=sum(d_cold), requests=sum(d_reqs),
        added_latency_s_total=math.fsum(fin.waits),
        migrations=0,
        lb_nongated_wh=lb_nongated, cv_per_model_wh=cv_sum,
        infra_usd=fleet_price_usd(sc.devices, horizon, sc.price_tier),
        energy_usd=energy_usd,
        carbon_kg=math.fsum(r.carbon_kg for r in reports),
        carbon_kg_flat=kg_flat,
        carbon_trace_name=trace.name,
        carbon_timeline=fin.carbon_timeline,
        power_timeline=fin.power_timeline,
        zone_energy_wh=zone_wh, zone_carbon_kg=zone_kg,
        latencies_s=np.sort(all_lat),
        replica_timeline={mid: list(log)
                          for mid, log in replica_log.items()},
        state_energy_wh=state_wh, state_durations_s=state_s,
        cost_usd=cost.cost_usd, gpu_hours_usd=cost.gpu_hours_usd,
        device_gpu_usd=cost.device_gpu_usd,
        device_cost_usd=cost.device_cost_usd,
        zone_cost_usd=cost.zone_cost_usd, device_tiers=cost.device_tiers,
        tier_billed_s=tier_billed), bulk.in_loop
