"""JAX-compiled bulk-scan backend for the mega-simulator, plus vmapped
fleet sweeps (``run_mega(..., backend="jax")`` / ``run_mega_sweep``).

``megasim.run_mega`` splits into a STRUCTURAL event loop (heap events:
load completions, armed evictions -- inherently sequential, stays
Python) and BULK phases that touch every request or metered segment.
This module re-expresses the bulk phases as jit-compiled array
programs behind the ``_NumpyBulk`` seam:

  * **big-gap scans** -- instead of per-(stream, timeout)
    ``np.flatnonzero(np.diff(arr) > T)`` + a ``searchsorted`` per run,
    ``prepare`` stacks streams into padded static-shape matrices
    (arrival lengths bucketed to powers of two so jit compiles once
    per bucket, not once per stream) and one ``lax.cummin`` reverse
    scan yields a ``nextbig`` table per (stream, T): the run ending at
    pointer ``p`` is the O(1) lookup ``nextbig[p]``.
  * **lazy-commit billing** -- waiter slices absorbed into mid-load
    replicas are recorded as (stream, lo, hi, drain-time) references,
    never materialized per element; ``finalize`` expands every record
    in one ragged gather (``searchsorted`` over the record-start
    prefix sums, indexed into the stacked stream arrays, which are
    padded to their power-of-two bucket like the records) and the wait
    of each request is one vectorized subtract.
  * **metering** -- each power-state transition appends one record,
    ``(device*S + state, watts, a, b)``, to the event loop's meter log
    (``S`` power states: 3, or 4 when requests take service time);
    ``finalize`` builds its arrays (with ``dt = b - a``) in one numpy
    pass, derives the coalesced power timeline from them, and reduces
    the whole log in ONE compiled program around
    the ``kernels/segment_trapz`` ``fused_meter`` Pallas kernel
    (compiled on a TPU, interpreted elsewhere, see ``kernels/ops.py``):
    per-(device, state) joules and seconds, per-device carbon against
    each device's zone trace, per-tier billed seconds, and the hourly
    cumulative carbon timeline, whose bins add the partial integrals
    of the entries that straddle each boundary.

Everything outside the kernel is float64 (the fleet accounting
convention) via the ``jax.enable_x64`` scope, which is thread-local and
does not disturb the f32 kernel tests elsewhere in the repo.  The
kernel itself runs in f32 (Mosaic has no 64-bit types) on the in-period
part of each carbon integral only.  All array programs pad to
power-of-two sizes with masked/zero-weight tails, so days of different
sizes whose arrivals, records and log fall in the same buckets reuse
every compiled program.

Both backends drive the identical event loop and see identical calls,
so requests/cold starts are equal, energy and dollars agree to <=1e-9
relative, and carbon agrees within the kernel's f32 bound
(``segment_trapz.CARBON_REL``) -- pinned in ``tests/test_mega.py``.
"""
from __future__ import annotations

import functools
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.fleet.carbon import CarbonTrace
from repro.fleet.fleetsim import DAY, FleetResult
from repro.fleet.mega import megasim
from repro.fleet.mega.spans import span
from repro.fleet.mega.traces import FleetTrace, RouteTrace, _route_plan
from repro.kernels import ops

_J_PER_KWH = 3.6e6


def _pow2(n: int, lo: int = 256) -> int:
    """Smallest power of two >= max(n, 1), floored at ``lo`` -- the
    padding quantum that keeps jit recompiles bounded (one compile per
    bucket, reused across streams, runs, and sweep points)."""
    return max(lo, 1 << max(int(n) - 1, 0).bit_length())


def _pad(a: np.ndarray, n: int, value=0.0) -> np.ndarray:
    if a.size >= n:
        return a
    return np.concatenate([a, np.full(n - a.size, value, dtype=a.dtype)])


# ---------------------------------------------------------------------------
# Compiled bulk programs (shapes pre-padded by the callers below).
# ---------------------------------------------------------------------------

@jax.jit
def _nextbig_rows(mat: jnp.ndarray, Ts: jnp.ndarray) -> jnp.ndarray:
    """Per-row ``nextbig`` tables: ``out[r, p]`` = the smallest i >= p
    with ``mat[r, i+1] - mat[r, i] > Ts[r]``, or a sentinel >= L when
    no such gap remains.  Rows are arrival streams padded by repeating
    their last arrival (gap 0: never "big"), so padding cannot end a
    run early."""
    gaps = mat[:, 1:] - mat[:, :-1]
    L1 = gaps.shape[1]
    idx = jnp.where(gaps > Ts[:, None],
                    jnp.arange(L1, dtype=jnp.int32)[None, :],
                    jnp.int32(L1))
    return jax.lax.cummin(idx, axis=1, reverse=True)


@functools.partial(jax.jit, static_argnames=("total_pad",))
def _bill_gather(flat: jnp.ndarray, off: jnp.ndarray, sid: jnp.ndarray,
                 lo: jnp.ndarray, hi: jnp.ndarray, t: jnp.ndarray, *,
                 total_pad: int) -> jnp.ndarray:
    """Expand ragged billing records into per-request waits.

    Record r says: arrivals ``arr_sid[lo:hi]`` of stream ``sid`` were
    served at drain time ``t`` (their wait is ``t - arrival``).  The
    expansion is the classic ragged gather: output slot k belongs to
    the record whose cumulative-count prefix contains k
    (``searchsorted`` side='right' also steps over zero-length pad
    records), and its arrival index is the offset within that record.
    Slots past the real total hit pad records; callers slice them off.
    ``flat`` is the stacked arrivals padded to their power-of-two
    bucket, so the program lowers once per bucket of arrivals, records
    and total, not once per day size; the clip keeps pad slots inside
    it.
    """
    cnt = hi - lo
    starts = jnp.cumsum(cnt) - cnt
    k = jnp.arange(total_pad, dtype=jnp.int32)
    r = jnp.searchsorted(starts, k, side="right") - 1
    r = jnp.clip(r, 0, sid.shape[0] - 1)
    pos = off[sid[r]] + lo[r] + (k - starts[r])
    pos = jnp.clip(pos, 0, flat.shape[0] - 1)
    return t[r] - flat[pos]


def _prefix_at(kt: jnp.ndarray, kv: jnp.ndarray, cum: jnp.ndarray,
               per: jnp.ndarray, g: jnp.ndarray, t: jnp.ndarray
               ) -> jnp.ndarray:
    """``F_g(t)`` per point for stacked trace tables: kt/kv/cum [G, K]
    (rows padded by repeating the last knot), per [G], g and t [P] ->
    [P].  The closed form of ``kernels/ref.segment_trapz_ref``'s
    prefix, with a compare-and-sum knot lookup per row."""
    ktg, kvg, cumg, pg = kt[g], kv[g], cum[g], per[g]
    k = jnp.floor(t / pg)
    p = t - k * pg
    j = jnp.sum((ktg <= p[:, None]).astype(jnp.int32), axis=1) - 1
    j = jnp.clip(j, 0, kt.shape[1] - 2)[:, None]
    take = jnp.take_along_axis
    kt_j = take(ktg, j, axis=1)[:, 0]
    kv_j = take(kvg, j, axis=1)[:, 0]
    span = take(ktg, j + 1, axis=1)[:, 0] - kt_j
    dt = p - kt_j
    v_p = kv_j + (take(kvg, j + 1, axis=1)[:, 0] - kv_j) * dt \
        / jnp.where(span > 0, span, 1.0)
    return (k * cumg[:, -1] + take(cumg, j, axis=1)[:, 0]
            + dt * (kv_j + v_p) * 0.5)


@functools.partial(jax.jit,
                   static_argnames=("n_dev", "nb", "n_tier", "n_state"))
def _meter_fused(keys, a, b, dt, pw, g, bucket, tdev, pseg, pk, pwp,
                 kts, kvs, cums, pers, tbr, *,
                 n_dev: int, nb: int, n_tier: int, n_state: int = 3):
    """The whole metering reduction in one compiled program fed by ONE
    metering pass (``ops.fused_meter``) over the raw meter log:

      * per-(device, state) joules/seconds -- ``segment_sum`` of the
        pass's f64 ``w * dt`` and ``dt`` lanes;
      * per-device carbon + the hourly cumulative timeline -- each
        entry's whole integral lands in the bin of its END (a
        segment-sum plus a cumsum over bins), and only entries
        STRADDLING a boundary ``t`` (``a < t < b``, precomputed host
        side as (pseg, pk) pairs) add a partial ``w * (F_g(t) -
        F_g(a))``, evaluated in f64 at those pairs only.  A device's
        entries are disjoint in time, so the pairs number at most
        devices x boundaries, not boundaries x entries; every zone's
        trace rides one stacked-table launch;
      * per-tier billed seconds -- a third segment-sum of the SAME
        seconds lane (in mega scope every metered state is
        powered-on, so raw seconds == billed seconds).
    """
    e, s, c = ops.fused_meter(a, b, dt, pw, g, kts, kvs, cums, pers)
    ej = jax.ops.segment_sum(e, keys, num_segments=n_dev * n_state)
    ds = jax.ops.segment_sum(s, keys, num_segments=n_dev * n_state)
    dev = keys // n_state
    per_dev = jax.ops.segment_sum(c, dev, num_segments=n_dev) / _J_PER_KWH
    tier_s = jax.ops.segment_sum(s, tdev[dev], num_segments=n_tier)
    full = jnp.cumsum(jax.ops.segment_sum(c, bucket, num_segments=nb))
    if nb > 1:
        pg = g[pseg]
        part = (_prefix_at(kts, kvs, cums, pers, pg, tbr[pk])
                - _prefix_at(kts, kvs, cums, pers, pg, a[pseg]))
        corr = jax.ops.segment_sum(pwp * part, pk, num_segments=nb - 1)
        full = full.at[:nb - 1].add(corr)
    return ej, ds, per_dev, tier_s, full / _J_PER_KWH


# ---------------------------------------------------------------------------
# The backend object megasim drives.
# ---------------------------------------------------------------------------

class _JaxBulk:
    """Drop-in for ``megasim._NumpyBulk`` that records the bulk work
    during the event loop and retires it compiled at finalize.  See the
    module docstring for the three phases.  Timing: spans for the
    finalize phases (``mega.billing`` and ``mega.meter``) and for each
    compiled call
    (``mega.<program>.call``, from the ``jnp.asarray`` of its inputs to
    its results on the host); the run claims the event loop makes
    accumulate in ``in_loop``, as on the numpy backend."""

    name = "jax"
    wants_tables = True

    def __init__(self, n_dev: int, n_state: int):
        self.n_dev = n_dev
        self.n_state = n_state      # power-state codes a device can hold
        self.in_loop = {"biggap_s": 0.0, "billing_s": 0.0}
        # billing records, appended by the event loop, expanded at
        # finalize
        self._bill: List[Tuple[int, int, int, float]] = []
        self._scalar_waits: List[float] = []
        self._sid: Dict[str, int] = {}
        self._flat = np.empty(0, dtype=np.float64)
        self._off = np.empty(0, dtype=np.int32)
        self._nextbig: Dict[Tuple[str, float], np.ndarray] = {}

    # -- prepare: stacked stream matrices + nextbig tables -------------------
    def prepare(self, streams: Dict[str, "megasim._Stream"],
                stream_Ts: Dict[str, Sequence[float]]) -> None:
        mids = list(streams)
        self._sid = {mid: i for i, mid in enumerate(mids)}
        arrs = [streams[mid].arr for mid in mids]
        lens = np.array([a.size for a in arrs], dtype=np.int64)
        off = np.zeros(len(arrs) + 1, dtype=np.int64)
        np.cumsum(lens, out=off[1:])
        self._off = off[:-1].astype(np.int32)
        # padded to its power-of-two bucket like every other compiled
        # input, so a day of a new size reuses the billing gather's
        # program; real slots never index the pad
        self._flat = _pad(np.concatenate(arrs) if arrs
                          else np.empty(0, dtype=np.float64),
                          _pow2(off[-1]))
        # one nextbig row per (stream, candidate timeout), bucketed by
        # padded length so each bucket is a single static-shape compile;
        # computed rows are parked in the stream's shared biggap dict
        # (under ("nb", T) keys the numpy float-keyed lookups never see)
        # so repeat runs on the same FleetTrace skip the scan entirely
        buckets: Dict[int, List[Tuple[str, float, np.ndarray]]] = {}
        for mid in mids:
            ms = streams[mid]
            if ms.n < 2:
                continue
            for T in dict.fromkeys(stream_Ts.get(mid, ())):
                if math.isinf(T) or (mid, T) in self._nextbig:
                    continue
                row = ms.biggap.get(("nb", T))
                if row is not None:
                    self._nextbig[(mid, T)] = row
                    continue
                L = _pow2(ms.n)
                buckets.setdefault(L, []).append((mid, float(T), ms.arr))
        with jax.enable_x64(True):
            for L, grp in buckets.items():
                rows = _pow2(len(grp), lo=8)
                mat = np.zeros((rows, L), dtype=np.float64)
                Ts = np.full(rows, np.inf)
                for r, (_mid, T, arr) in enumerate(grp):
                    mat[r, :arr.size] = arr
                    mat[r, arr.size:] = arr[-1]
                    Ts[r] = T
                with span("mega.nextbig.call"):
                    nb = np.asarray(_nextbig_rows(jnp.asarray(mat),
                                                  jnp.asarray(Ts)))
                for r, (mid, T, _arr) in enumerate(grp):
                    self._nextbig[(mid, T)] = nb[r]
                    ms = streams[mid]
                    if len(ms.biggap) >= megasim.biggap_cache.max_timeouts:
                        ms.biggap.pop(next(iter(ms.biggap)))
                    ms.biggap[("nb", T)] = nb[r]

    # -- event-loop hooks ----------------------------------------------------
    def last_of_run(self, ms, T: float) -> int:
        t0 = time.perf_counter()
        if ms.ptr >= ms.n - 1:
            last = ms.n - 1
        else:
            row = self._nextbig.get((ms.mid, T))
            if row is None:
                # timeout the eager probe skipped (or an infinite one):
                # the numpy scan path is the fallback, same answer
                big = ms.biggaps(T)
                j = int(np.searchsorted(big, ms.ptr))
                last = int(big[j]) if j < big.size else ms.n - 1
            else:
                v = int(row[ms.ptr])
                last = v if v <= ms.n - 2 else ms.n - 1
        self.in_loop["biggap_s"] += time.perf_counter() - t0
        return last

    def absorb(self, ms, d: int, lo: int, hi: int, t_done: float) -> None:
        ent = ms.waiters.get(d)
        if ent is None:
            ent = ms.waiters[d] = [0, []]
        ent[0] += hi - lo
        ent[1].append((lo, hi))

    def wait_one(self, ms, d: int, t: float) -> None:
        ent = ms.waiters.get(d)
        if ent is None:
            ent = ms.waiters[d] = [0, []]
        ent[0] += 1
        ent[1].append(t)

    def wait(self, w: float) -> None:
        self._scalar_waits.append(w)

    def waiter_count(self, ms, d: int) -> int:
        ent = ms.waiters.get(d)
        return ent[0] if ent is not None else 0

    def drain(self, ms, d: int, t: float) -> int:
        ent = ms.waiters.pop(d, None)
        if ent is None:
            return 0
        sid = self._sid[ms.mid]
        for item in ent[1]:
            if type(item) is tuple:
                self._bill.append((sid, item[0], item[1], t))
            else:
                self._scalar_waits.append(t - item)
        return ent[0]

    # -- finalize: the compiled bulk reductions ------------------------------
    def finalize(self, log: List[float], trace: CarbonTrace,
                 horizon: float, dev_traces=None,
                 tiers=None) -> "megasim._Fin":
        """``log`` is the event loop's meter log (``megasim._log_arrays``)."""
        with jax.enable_x64(True):
            (energy_j, dur_s, carbon_dev, timeline, tier_billed,
             power) = self._finalize_meter(log, trace, horizon, dev_traces,
                                           tiers)
            waits = self._finalize_billing()
        return megasim._Fin(energy_j, dur_s, waits, carbon_dev, timeline,
                            power, tier_billed)

    @span("mega.billing")
    def _finalize_billing(self) -> np.ndarray:
        scalar = np.asarray(self._scalar_waits, dtype=np.float64)
        if not self._bill:
            return scalar
        rec = np.asarray(self._bill, dtype=np.float64)
        m = _pow2(rec.shape[0])
        sid = _pad(rec[:, 0].astype(np.int32), m, 0)
        lo = _pad(rec[:, 1].astype(np.int32), m, 0)
        hi = _pad(rec[:, 2].astype(np.int32), m, 0)
        tt = _pad(rec[:, 3], m)
        total = int((hi - lo).sum())
        with span("mega.billing.call"):
            w = np.asarray(_bill_gather(
                jnp.asarray(self._flat), jnp.asarray(self._off),
                jnp.asarray(sid), jnp.asarray(lo), jnp.asarray(hi),
                jnp.asarray(tt), total_pad=_pow2(total)))
        return np.concatenate([w[:total], scalar])

    @span("mega.meter")
    def _finalize_meter(self, log: List[float], trace: CarbonTrace,
                        horizon: float, dev_traces=None, tiers=None):
        """Energy, durations, carbon, timeline, and per-tier billed
        seconds from ONE ``_meter_fused`` launch over the raw meter
        log, and the coalesced power timeline derived from the same
        log.  ``phase_timings`` books the compiled call
        (``mega.meter.call``) under ``energy_s`` and the rest of
        ``mega.meter``, the host-side prep (the log's arrays, the power
        timeline, table stacking, bin and straddle geometry), under
        ``carbon_s``."""
        keys64, pw_np, a_np, b_np = megasim._log_arrays(log)
        n = keys64.size
        dt_np = b_np - a_np             # the subtraction the loop made
        power = megasim._power_segments(keys64 // self.n_state, a_np, b_np,
                                        pw_np, self.n_dev)[0]
        keys_np = keys64.astype(np.int32)
        tier_names = sorted(set(tiers)) if tiers else ["on_demand"]
        # stacked knot tables: one row per distinct zone trace, K
        # padded by repeating the final knot (in-period offsets are
        # strictly below the period, so pad knots never match), G
        # padded with row-0 copies (never gathered)
        if dev_traces is None:
            dev_traces = [trace] * self.n_dev
        gid: Dict[int, int] = {}
        gidx_dev = np.zeros(self.n_dev, dtype=np.int32)
        tabs: List[CarbonTrace] = []
        for d, tr in enumerate(dev_traces):
            gi = gid.get(id(tr))
            if gi is None:
                gi = gid[id(tr)] = len(tabs)
                tabs.append(tr)
            gidx_dev[d] = gi
        kmax = _pow2(max(np.asarray(t._kt).size for t in tabs), lo=8)
        gpad = _pow2(len(tabs), lo=1)
        kts = np.zeros((gpad, kmax), dtype=np.float64)
        kvs = np.zeros((gpad, kmax), dtype=np.float64)
        cums = np.zeros((gpad, kmax), dtype=np.float64)
        pers = np.ones(gpad, dtype=np.float64)
        for gi, tr in enumerate(tabs):
            for dst, src in ((kts, tr._kt), (kvs, tr._kv),
                             (cums, tr._cum)):
                row = np.asarray(src, dtype=np.float64)
                dst[gi, :row.size] = row
                dst[gi, row.size:] = row[-1]
            pers[gi] = float(tr.period_s)
        kts[len(tabs):] = kts[0]
        kvs[len(tabs):] = kvs[0]
        cums[len(tabs):] = cums[0]
        pers[len(tabs):] = pers[0]
        g_np = gidx_dev[keys_np // self.n_state]
        # hourly-bin geometry, numpy-semantics bins: they cover
        # max(horizon, last entry end), the last bin absorbing any
        # overshoot; then the (entry, boundary) straddle pairs, expanded
        # with one repeat/cumsum -- a device's entries are disjoint in
        # time, so the pair count stays bounded by devices x boundaries
        bin_s = 3600.0
        end = max(horizon, float(b_np.max()))
        nb = max(int(math.ceil(end / bin_s - 1e-12)), 1)
        tbr = bin_s * np.arange(1, nb)
        k_lo = np.searchsorted(tbr, a_np, side="right")
        bucket = np.searchsorted(tbr, b_np, side="left").astype(np.int32)
        cnt = np.maximum(bucket - k_lo, 0)
        total = int(cnt.sum())
        pcap = _pow2(total, lo=1024)
        pseg = np.zeros(pcap, dtype=np.int32)
        pk = np.zeros(pcap, dtype=np.int32)
        pwp = np.zeros(pcap, dtype=np.float64)        # pad pairs weigh 0
        if total:
            ps = np.repeat(np.arange(n, dtype=np.int32), cnt)
            starts = np.cumsum(cnt) - cnt
            pseg[:total] = ps
            pk[:total] = (np.arange(total) - starts[ps] + k_lo[ps])
            pwp[:total] = pw_np[ps]
        tdev = np.array([tier_names.index(t) for t in tiers],
                        dtype=np.int32) if tiers else \
            np.zeros(self.n_dev, dtype=np.int32)
        m = _pow2(n)
        with span("mega.meter.call"):
            ej, ds, per_dev, tier_s, cums_nb = _meter_fused(
                jnp.asarray(_pad(keys_np, m, 0)),
                jnp.asarray(_pad(a_np, m)), jnp.asarray(_pad(b_np, m)),
                jnp.asarray(_pad(dt_np, m)), jnp.asarray(_pad(pw_np, m)),
                jnp.asarray(_pad(g_np, m, 0)),
                jnp.asarray(_pad(bucket, m, 0)), jnp.asarray(tdev),
                jnp.asarray(pseg), jnp.asarray(pk), jnp.asarray(pwp),
                jnp.asarray(kts), jnp.asarray(kvs), jnp.asarray(cums),
                jnp.asarray(pers), jnp.asarray(tbr),
                n_dev=self.n_dev, nb=nb, n_tier=len(tier_names),
                n_state=self.n_state)
            energy_j = np.asarray(ej).reshape(self.n_dev, self.n_state)
            dur_s = np.asarray(ds).reshape(self.n_dev, self.n_state)
            cums_np = np.asarray(cums_nb)
            timeline = [(min((j + 1) * bin_s, end), float(cums_np[j]))
                        for j in range(nb)]
            tier_billed = {t: float(v)
                           for t, v in zip(tier_names, np.asarray(tier_s))}
            per_dev = list(np.asarray(per_dev))
        return energy_j, dur_s, per_dev, timeline, tier_billed, power


def compiled_program_count() -> int:
    """How many distinct programs this module's jitted bulk reductions
    have compiled so far (summed jit-cache sizes).  The batched planner
    reports the delta per sweep: shared-shape grouping shows up as a
    compile count that stays flat while the point count grows."""
    return sum(fn._cache_size() for fn in (
        _nextbig_rows, _bill_gather, _meter_fused))


# ---------------------------------------------------------------------------
# Vmapped sweeps: many production-shaped days through one compiled stack.
# ---------------------------------------------------------------------------

def _diurnal_hr_j(base_hr: float, t: jnp.ndarray) -> jnp.ndarray:
    """jnp twin of ``traces._diurnal_hr`` (same day shape)."""
    h = (t / 3600.0) % 24.0
    return base_hr * (0.55 + 0.45 * jnp.sin((h - 9.0) * jnp.pi / 12.0))


def _sample_group(keys: np.ndarray, rate_fn, rate_max: float,
                  horizon_s: float, n_max: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized thinned inhomogeneous Poisson at a STATIC shape: draw
    the envelope count (clamped to ``n_max``, sized for ~10 sigma of
    headroom), keep the first ``n`` of ``n_max`` uniforms, thin by
    ``rate(t)/rate_max``, and sort rejected samples to +inf.  One
    jit-compiled vmap over every (sweep point, route) in the group --
    the whole sweep's trace generation is a single compiled call."""

    def one(key):
        k1, k2, k3 = jax.random.split(key, 3)
        lam = rate_max * horizon_s / 3600.0
        cnt = jnp.minimum(jax.random.poisson(k1, lam), n_max)
        t = jax.random.uniform(k2, (n_max,), dtype=jnp.float64,
                               maxval=horizon_s)
        u = jax.random.uniform(k3, (n_max,), dtype=jnp.float64,
                               maxval=rate_max)
        keep = (jnp.arange(n_max) < cnt) & (u < rate_fn(t))
        return jnp.sort(jnp.where(keep, t, jnp.inf)), keep.sum()

    with jax.enable_x64(True):
        ts, counts = jax.jit(jax.vmap(one))(jnp.asarray(keys))
    return np.asarray(ts), np.asarray(counts)


def _envelope_n(rate_max_hr: float, horizon_s: float) -> int:
    lam = rate_max_hr * horizon_s / 3600.0
    return int(lam + 10.0 * math.sqrt(lam + 1.0) + 20.0)


def sweep_traces(seeds: Sequence[int], *, generator: str = "flash-crowd",
                 n_routes: int = 8, fleet: str = "2xh100+2xa100+2xl40s",
                 horizon_s: float = DAY, base_rate_hr: float = 40.0,
                 spike_x: float = 40.0,
                 spike_start_s: float = 13 * 3600.0,
                 spike_width_s: float = 1800.0) -> List[FleetTrace]:
    """A batch of production-shaped days, generated on the compiled
    stack: per-route PRNG keys derive from the same ``_route_plan``
    child seeds as the numpy generators (same checkpoint plan, same
    seed discipline -- same seed, bit-identical batch), and ALL routes
    of ALL sweep points sample in one vmapped thinning call per rate
    family.  The day shapes mirror ``traces.flash_crowd`` /
    ``product_launch`` / ``regional_outage``; arrival streams come
    from jax's PRNG, so they are statistically -- not bitwise -- the
    numpy generators' days."""
    if generator not in ("flash-crowd", "product-launch",
                         "regional-outage"):
        raise KeyError(f"unknown sweep generator {generator!r}")
    plans = [_route_plan(np.random.default_rng(int(s)), n_routes)
             for s in seeds]
    keys = np.stack([
        np.asarray(jax.random.PRNGKey(int(child)))
        for child_seeds, _ in plans for child in child_seeds])
    keys = keys.reshape(len(seeds), n_routes, 2)

    tail_s = 2.0 * spike_width_s

    def flash_rate(t):
        r = _diurnal_hr_j(base_rate_hr, t)
        dt = t - spike_start_s
        hot = (dt >= 0.0) & (dt < spike_width_s)
        cool = (dt >= spike_width_s) & (dt < spike_width_s + tail_s)
        boost = jnp.where(hot, spike_x, 0.0) + jnp.where(
            cool, spike_x * jnp.exp(-(dt - spike_width_s)
                                    / (0.35 * spike_width_s)), 0.0)
        return r * (1.0 + boost)

    def launch_rate(t):
        dt = t - 9 * 3600.0
        surge = 60.0 + (600.0 - 60.0) * jnp.exp(-jnp.maximum(dt, 0.0)
                                                / (4 * 3600.0))
        return jnp.where(dt >= 0.0, surge, 0.0)

    def outage_rate(t):
        out0, out1 = 11 * 3600.0, 12 * 3600.0
        r = _diurnal_hr_j(base_rate_hr, t)
        dark = (t >= out0) & (t < out1)
        surge = (t >= out1) & (t < out1 + 1800.0)
        return jnp.where(dark, 0.0, r * jnp.where(surge, 3.0, 1.0))

    base_fn = _diurnal_hr_j
    if generator == "flash-crowd":
        groups = [(keys[:, 0, :], flash_rate,
                   base_rate_hr * (1.0 + spike_x)),
                  (keys[:, 1:, :].reshape(-1, 2),
                   lambda t: base_fn(base_rate_hr, t), base_rate_hr)]
    elif generator == "product-launch":
        groups = [(keys[:, 0, :], launch_rate, 600.0),
                  (keys[:, 1:, :].reshape(-1, 2),
                   lambda t: base_fn(base_rate_hr, t), base_rate_hr)]
    else:
        groups = [(keys.reshape(-1, 2), outage_rate, base_rate_hr * 3.0)]

    sampled: List[Tuple[np.ndarray, np.ndarray]] = []
    for gkeys, rate_fn, rmax in groups:
        sampled.append(_sample_group(
            gkeys, rate_fn, rmax, horizon_s,
            _envelope_n(rmax, horizon_s)) if gkeys.size
            else (np.empty((0, 0)), np.empty(0, dtype=np.int64)))

    traces: List[FleetTrace] = []
    for p, (seed, (_, ckpt)) in enumerate(zip(seeds, plans)):
        routes = []
        for i in range(n_routes):
            if len(groups) == 1:
                ts, cnt = sampled[0]
                row = p * n_routes + i
            elif i == 0:
                ts, cnt = sampled[0]
                row = p
            else:
                ts, cnt = sampled[1]
                row = p * (n_routes - 1) + (i - 1)
            arr = ts[row, :int(cnt[row])].copy()
            routes.append(RouteTrace(route_id=f"r{i}", arrivals_s=arr,
                                     checkpoint_gb=float(ckpt[i])))
        traces.append(FleetTrace(name=f"{generator}-sweep", fleet=fleet,
                                 horizon_s=horizon_s, routes=tuple(routes),
                                 seed=int(seed)))
    return traces


def run_mega_sweep(scenarios=None, *, seeds: Optional[Sequence[int]] = None,
                   policy_factory=None, router: str = "warm-first",
                   compute_bound: bool = False,
                   scenario_kw: Optional[dict] = None,
                   on_unsupported: str = "raise",
                   **trace_kw) -> List[Optional[FleetResult]]:
    """Run a sweep of mega days on the jax backend: either explicit
    ``scenarios`` (any ``FleetScenario`` in run_mega's scope) or
    ``seeds`` + generator kwargs (``generator=``, ``n_routes=``,
    ``fleet=``, ... -- see ``sweep_traces``), in which case trace
    generation for the whole batch is one vmapped compiled call.

    The points then replay through ``run_mega(backend="jax")``
    sequentially (the structural event loop is inherently serial), but
    every compiled bulk program -- nextbig scans, billing gather,
    metering -- is shared across points
    through the power-of-two shape buckets, so the batch pays each
    compile once per bucket (and per count of hourly bins), not per
    point: point 1 is compile-bound, points 2..P run hot.
    Returns one ``FleetResult`` per point, in input order.

    ``on_unsupported="skip"`` returns ``None`` for points outside
    run_mega's scope (``MegaUnsupportedError``) instead of raising --
    the seam the batched planner dispatches event-loop fallbacks
    behind; the default ``"raise"`` keeps the PR-7 contract.
    """
    if (scenarios is None) == (seeds is None):
        raise ValueError("pass exactly one of scenarios= or seeds=")
    if on_unsupported not in ("raise", "skip"):
        raise ValueError(f"on_unsupported={on_unsupported!r}")
    if seeds is not None:
        if policy_factory is None:
            from repro.core.scheduler import Breakeven
            policy_factory = Breakeven
        traces = sweep_traces(seeds, **trace_kw)
        scenarios = [tr.to_scenario(policy_factory, router,
                                    **(scenario_kw or {}))
                     for tr in traces]
    elif trace_kw:
        raise ValueError(f"trace kwargs {sorted(trace_kw)} need seeds=")
    out: List[Optional[FleetResult]] = []
    for sc in scenarios:
        try:
            out.append(megasim.run_mega(sc, compute_bound=compute_bound,
                                        backend="jax"))
        except megasim.MegaUnsupportedError:
            if on_unsupported != "skip":
                raise
            out.append(None)
    return out
