"""Plain reference of the fleet day the benchmark's cells simulate.

A straightforward per-request event loop over the semantics the
configuration states, written from those semantics and importing
nothing of the program:

  * devices from a fleet spec (``200xh100+...``), ids ``<sku>-<i>``,
    ordered by id for every tie-break; route ``i`` prewarms on the
    ``i``-th device of the spec, round robin, with VRAM at
    ``vram_per_checkpoint`` times its checkpoint;
  * warm-first routing: a request goes to the replica of its model with
    the fewest waiting requests, resident before loading, lowest id;
    with none, the model is queued for a load on the least-loaded device
    (fewest replicas, then most free VRAM, then lowest id), one load at
    a time per device, making room by evicting resident replicas in
    order of their idle deadline;
  * eviction: a replica unloads after ``T* = P_load t_load / P_park``
    idle seconds (the paper's Eq. 12, full loading power); the loader is
    derived from checkpoint bytes (deserialize at 1 GB/s x 1.8, ingest
    at 0.15% of memory bandwidth, at least 1 GB/s);
  * power: bare idle, parked (a live context) or loading; energy is
    watts x seconds, carbon the exact trapezoid integral of watts against
    the periodic piecewise-linear intensity, dollars the on-demand rate
    over powered-on hours plus energy at the zone's tariff.

``simulate`` returns the raw record (per-device power segments, loads,
requests, waits); ``account`` turns it into the numbers compared, in a
chosen float type: float64 is the reference, float32 the control.
"""
from __future__ import annotations

import heapq
import math
import re
from collections import deque
from typing import Dict, List, Sequence, Tuple

import numpy as np

GB = 1024 ** 3
BARE, PARKED, LOADING = 0, 1, 2


def build_devices(spec: str) -> List[Tuple[str, str]]:
    """[(instance id, sku)] in spec order."""
    out, seen = [], {}
    for part in spec.split("+"):
        m = re.fullmatch(r"\s*(?:(\d+)\s*x\s*)?([a-z0-9_]+)\s*", part)
        if not m:
            raise ValueError(f"bad fleet spec part {part!r}")
        sku = m.group(2)
        for _ in range(int(m.group(1) or 1)):
            i = seen.get(sku, 0)
            seen[sku] = i + 1
            out.append((f"{sku}-{i}", sku))
    return out


def loader(ckpt_gb: float, sku: dict) -> Tuple[float, float, float]:
    """(load watts, load seconds, idle timeout T*) of one checkpoint on
    one SKU."""
    gbs = int(ckpt_gb * GB) / GB
    ingest = max(sku["mem_bw_gbps"] * 0.0015, 1.0)
    t_deser = gbs / 1.0 * 1.8
    t_xfer = gbs / ingest
    t_load = t_deser + t_xfer
    p_base, p_ctx = sku["p_base_w"], sku["p_ctx_w"]
    park_w = p_base + (p_ctx - p_base) + 0.0
    burst_w = park_w + 0.004 * sku["tdp_w"]
    p_load = (t_deser * (p_base * 0.99) + t_xfer * burst_w) / t_load
    return p_load, t_load, p_load * t_load / (p_ctx - p_base)


def simulate(routes: Sequence[Tuple[str, np.ndarray, float]], config: dict
             ) -> dict:
    """Run one day. ``routes`` is [(route id, sorted arrivals, ckpt GB)]."""
    skus = config["skus"]
    horizon = float(config["horizon_s"])
    spec_order = build_devices(config["fleet"])
    ids = sorted(i for i, _ in spec_order)
    idx = {i: k for k, i in enumerate(ids)}
    sku_of = dict(spec_order)
    N = len(ids)
    dsku = [skus[sku_of[i]] for i in ids]
    vcap = [s["vram_gb"] for s in dsku]
    scap = [s["slots"] for s in dsku]
    p_bare = [s["p_base_w"] for s in dsku]
    p_park = [s["p_base_w"] + (s["p_ctx_w"] - s["p_base_w"]) + 0.0
              for s in dsku]
    vfac = float(config["vram_per_checkpoint"])

    M = len(routes)
    vram = [r[2] * vfac for r in routes]
    lcache: Dict[Tuple[int, str], Tuple[float, float, float]] = {}

    def ld(m: int, d: int):
        key = (m, sku_of[ids[d]])
        got = lcache.get(key)
        if got is None:
            got = lcache[key] = loader(routes[m][2], dsku[d])
        return got

    # device state
    occ = np.zeros(N, dtype=np.int64)
    vused = np.zeros(N, dtype=np.float64)
    state = [BARE] * N
    watts = list(p_bare)
    since = [0.0] * N
    segs: List[List[List[float]]] = [[] for _ in range(N)]
    n_reg = [0] * N                  # models registered on each device
    act: List[set] = [set() for _ in range(N)]
    res_count = [0] * N
    cold = [0] * N
    reqs = [0] * N
    inflight = [-1] * N
    queue = [deque() for _ in range(N)]
    qset: List[set] = [set() for _ in range(N)]
    # replica state, keyed (device, model)
    pos: Dict[Tuple[int, int], int] = {}
    resident: set = set()
    loading_r: set = set()
    deadline: Dict[Tuple[int, int], float] = {}
    # model state
    res = [set() for _ in range(M)]
    loading = [set() for _ in range(M)]
    waiters: List[Dict[int, list]] = [dict() for _ in range(M)]
    waits: List[Tuple[float, float]] = []             # (served at, arrival)
    n_zero = 0

    heap: list = []
    seq = [0]

    def push(t, phase, payload):
        seq[0] += 1
        heapq.heappush(heap, (t, phase, seq[0], payload))

    def trans(d, t, ns, w):
        t0 = since[d]
        if t > t0:
            sg = segs[d]
            p = watts[d]
            if sg and sg[-1][1] == t0 and sg[-1][2] == p and sg[-1][3] == state[d]:
                sg[-1][1] = t
            else:
                sg.append([t0, t, p, state[d]])
        state[d] = ns
        watts[d] = w
        since[d] = t

    def recompute(d):
        s = 0.0
        for m in sorted(act[d], key=lambda m: pos[(d, m)]):
            s += vram[m]
        vused[d] = s

    def register(d, m):
        if (d, m) not in pos:
            pos[(d, m)] = n_reg[d]
            n_reg[d] += 1

    def arm(d, m, t):
        T = ld(m, d)[2]
        deadline[(d, m)] = t + T
        push(t + T, 4, (d, m))

    def evict(d, m, t):
        resident.discard((d, m))
        deadline.pop((d, m), None)
        act[d].discard(m)
        res[m].discard(d)
        occ[d] -= 1
        res_count[d] -= 1
        recompute(d)
        if res_count[d] == 0 and state[d] == PARKED:
            trans(d, t, BARE, p_bare[d])

    def make_room(d, m_new, t):
        need = vram[m_new]
        if not (vused[d] + need > vcap[d] or occ[d] + 1 > scap[d]):
            return
        victims = sorted((m for m in act[d]
                          if m != m_new and (d, m) in resident),
                         key=lambda m: pos[(d, m)])
        victims.sort(key=lambda m: deadline[(d, m)])
        for m in victims:
            if not (vused[d] + need > vcap[d] or occ[d] + 1 > scap[d]):
                break
            evict(d, m, t)

    def start_load(d, m, t):
        register(d, m)
        make_room(d, m, t)
        loading_r.add((d, m))
        act[d].add(m)
        loading[m].add(d)
        occ[d] += 1
        recompute(d)
        p_load, t_load, _ = ld(m, d)
        trans(d, t, LOADING, p_load)
        push(t + t_load, 0, (d, m))

    def pump(d, t):
        if inflight[d] >= 0:
            return
        q = queue[d]
        while q:
            m = q.popleft()
            qset[d].discard(m)
            if (d, m) in resident or (d, m) in loading_r:
                continue
            inflight[d] = m
            start_load(d, m, t)
            return

    vcap_a = np.array(vcap, dtype=np.float64)
    scap_a = np.array(scap, dtype=np.int64)

    def least_loaded(m):
        """Fewest replicas, then most free VRAM, then lowest index, among
        the devices with a free slot and room (all devices if none)."""
        need = vram[m]
        free = vcap_a - vused
        cand = np.flatnonzero((scap_a - occ >= 1) & (free >= need))
        if cand.size == 0:
            cand = np.arange(N)
        oc = occ[cand]
        cand = cand[oc == oc.min()]
        f = free[cand]
        return int(cand[f == f.max()][0])

    def load_done(t, d, m):
        inflight[d] = -1
        loading_r.discard((d, m))
        resident.add((d, m))
        loading[m].discard(d)
        res[m].add(d)
        res_count[d] += 1
        recompute(d)
        cold[d] += 1
        trans(d, t, PARKED, p_park[d])
        arm(d, m, t)
        w = waiters[m].pop(d, None)
        if w:
            reqs[d] += len(w)
            waits.extend((t, a) for a in w)
        pump(d, t)

    def arrival(t, m):
        nonlocal n_zero
        locs = res[m] | loading[m]
        if locs:
            wm = waiters[m]
            d = min(locs, key=lambda x: (len(wm.get(x, ())),
                                         0 if x in res[m] else 1, x))
            if d in res[m]:
                reqs[d] += 1
                n_zero += 1
                deadline[(d, m)] = t + ld(m, d)[2]   # lazily re-armed
            else:
                wm.setdefault(d, []).append(t)
            return
        d = least_loaded(m)
        register(d, m)
        waiters[m].setdefault(d, []).append(t)
        if (d, m) not in loading_r and m not in qset[d]:
            qset[d].add(m)
            queue[d].append(m)
            pump(d, t)

    # prewarm each route on its home device
    for m in range(M):
        d = idx[spec_order[m % N][0]]
        need = vram[m]
        if not (scap[d] - occ[d] >= 1 and vcap[d] - vused[d] >= need):
            fit = [k for k in range(N)
                   if scap[k] - occ[k] >= 1 and vcap[k] - vused[k] >= need]
            if not fit:
                continue
            d = min(fit, key=lambda k: (occ[k], -(vcap[k] - vused[k]), k))
        register(d, m)
        resident.add((d, m))
        act[d].add(m)
        occ[d] += 1
        res_count[d] += 1
        recompute(d)
        cold[d] += 1
        res[m].add(d)
        trans(d, 0.0, PARKED, p_park[d])
        arm(d, m, 0.0)

    # every arrival, in time order (route order at equal times)
    lens = [len(r[1]) for r in routes]
    if sum(lens):
        t_all = np.concatenate([np.asarray(r[1], dtype=np.float64)
                                for r in routes])
        m_all = np.repeat(np.arange(M), lens)
        keep = (t_all >= 0.0) & (t_all < horizon)
        t_all, m_all = t_all[keep], m_all[keep]
        order = np.lexsort((m_all, t_all))
        t_all = t_all[order].tolist()
        m_all = m_all[order].tolist()
    else:
        t_all, m_all = [], []

    def fire(t, phase, payload, pending_loads):
        """One heap event; returns False for an eviction to defer."""
        d, m = payload
        if phase == 0:
            load_done(t, d, m)
            return True
        if (d, m) not in resident:
            return True
        dl = deadline[(d, m)]
        if dl != t:             # re-armed by a later hit: wait for it
            if dl > t:
                push(dl, 4, (d, m))
            return True
        if t < horizon or pending_loads:
            evict(d, m, t)
            return True
        return False

    def pending_loads():
        return any(e[1] == 0 for e in heap)

    # idle timeout of model m on device d: T_of[m][sku index of d]
    sku_keys = sorted(skus)
    sku_ix = [sku_keys.index(sku_of[i]) for i in ids]
    first_dev = [sku_ix.index(k) if k in sku_ix else -1
                 for k in range(len(sku_keys))]
    T_of = [[ld(m, d)[2] if d >= 0 else math.inf for d in first_dev]
            for m in range(M)]
    for t, m in zip(t_all, m_all):
        while heap and (heap[0][0] < t or (heap[0][0] == t
                                           and heap[0][1] < 3)):
            et, ph, _, pl = heapq.heappop(heap)
            fire(et, ph, pl, True)
        rs = res[m]
        if rs and not loading[m]:
            # a warm hit: resident replicas hold no waiters, so the
            # lowest device index wins
            d = min(rs) if len(rs) > 1 else next(iter(rs))
            reqs[d] += 1
            n_zero += 1
            deadline[(d, m)] = t + T_of[m][sku_ix[d]]
            continue
        arrival(t, m)
    deferred = []
    last_done = 0.0
    while heap:
        et, ph, _, pl = heapq.heappop(heap)
        if ph == 0:
            last_done = max(last_done, et)
            fire(et, ph, pl, True)
        elif not fire(et, ph, pl, pending_loads()):
            deferred.append((et, pl))
    final = max(horizon, last_done)
    for et, (d, m) in deferred:
        if (d, m) in resident and deadline[(d, m)] == et and et < final:
            evict(d, m, et)
    for d in range(N):
        trans(d, final, state[d], watts[d])
    leftover = sum(len(w) for wm in waiters for w in wm.values())
    if leftover:
        raise RuntimeError(f"reference left {leftover} requests unserved")
    return {"ids": ids, "skus": [sku_of[i] for i in ids], "segs": segs,
            "cold": cold, "reqs": reqs, "n_zero": n_zero, "waits": waits,
            "final_s": final}


# --------------------------------------------------------------------------
# Accounting: the numbers compared, in a chosen float type.
# --------------------------------------------------------------------------

def _knots(points, period: float):
    """Extended knot times/values over [0, period] and their prefix
    integrals."""
    ts = [t for t, _ in points]
    vs = [v for _, v in points]
    if len(points) > 1 and ts[0] > 0.0:
        span = ts[0] + period - ts[-1]
        v0 = vs[-1] + (vs[0] - vs[-1]) * (period - ts[-1]) / span
        ts, vs = [0.0] + ts, [v0] + vs
    ts, vs = ts + [period], vs + [vs[0]]
    kt, kv = np.array(ts), np.array(vs)
    cum = np.concatenate([[0.0], np.cumsum(np.diff(kt) * (kv[1:] + kv[:-1])
                                           / 2.0)])
    return kt, kv, cum


def _integral(a, b, points, period, ft):
    """Exact integral of the periodic intensity over each [a, b]."""
    a = np.asarray(a, dtype=ft)
    b = np.asarray(b, dtype=ft)
    if len(points) == 1:
        return (b - a) * ft(points[0][1])
    kt, kv, cum = (x.astype(ft) for x in _knots(points, period))
    per = ft(period)
    total = cum[-1]

    def g(t):
        k = np.floor(t / per)
        p = t - k * per
        j = np.clip(np.searchsorted(kt, p, side="right") - 1, 0,
                    len(kt) - 2)
        span = kt[j + 1] - kt[j]
        v = kv[j] + (kv[j + 1] - kv[j]) * (p - kt[j]) / span
        return k * total + cum[j] + (p - kt[j]) * (kv[j] + v) / ft(2.0)

    return g(b) - g(a)


def account(raw: dict, config: dict, dtype=np.float64) -> dict:
    """The compared numbers of one simulated day, in ``dtype``."""
    ft = np.dtype(dtype).type
    J = ft(3.6e6)
    pts = tuple(config["carbon_points"])
    period = float(config.get("carbon_period_s", 86400.0))
    skus = config["skus"]
    tariff = ft(config["usd_per_kwh"])
    tier = config["price_tier"]
    dev_e, dev_c, dev_s = [], [], []
    seg_all = []
    for d, sg in enumerate(raw["segs"]):
        arr = np.asarray(sg, dtype=np.float64).reshape(-1, 4)
        a, b, p = (arr[:, k].astype(ft) for k in range(3))
        dev_e.append(np.sum((b - a) * p, dtype=ft) / ft(3600.0))
        dev_s.append(np.sum(b - a, dtype=ft))
        c = p * _integral(a, b, pts, period, ft) / J
        dev_c.append(np.sum(c, dtype=ft))
        seg_all.append(arr[:, :3])
    seg_all = np.concatenate(seg_all) if seg_all else np.zeros((0, 3))
    dev_e = np.array(dev_e, dtype=ft)
    energy = np.sum(dev_e, dtype=ft)
    rate = np.array([skus[s][f"usd_per_hr_{tier}"] for s in raw["skus"]],
                    dtype=ft)
    gpu = np.sum(rate * np.array(dev_s, dtype=ft) / ft(3600.0), dtype=ft)
    energy_usd = energy / ft(1e3) * tariff
    w = np.asarray(raw["waits"], dtype=np.float64).reshape(-1, 2)
    waits = np.sort(w[:, 0].astype(ft) - w[:, 1].astype(ft))
    lat = np.concatenate([np.zeros(raw["n_zero"], dtype=ft), waits])
    # hourly cumulative timeline, segments split at bin edges
    bin_s = 3600.0
    end = max(float(config["horizon_s"]),
              float(seg_all[:, 1].max()) if len(seg_all) else 0.0)
    nb = max(int(math.ceil(end / bin_s - 1e-12)), 1)
    a, b, p = seg_all[:, 0], seg_all[:, 1], seg_all[:, 2]
    bins = np.zeros(nb, dtype=ft)
    for j in range(nb):
        lo = j * bin_s if j else -np.inf
        hi = (j + 1) * bin_s if j < nb - 1 else np.inf
        x0, x1 = np.maximum(a, lo), np.minimum(b, hi)
        m = x1 > x0
        c = p[m].astype(ft) * _integral(x0[m], x1[m], pts, period, ft) / J
        bins[j] = np.sum(c, dtype=ft)
    return {
        "requests": int(sum(raw["reqs"])),
        "cold_starts": int(sum(raw["cold"])),
        "waits": waits,
        "energy_wh": energy,
        "device_energy_wh": dict(zip(raw["ids"], dev_e)),
        "cost_usd": gpu + energy_usd,
        "latency_total_s": np.sum(waits, dtype=ft),
        "p99_s": ft(np.percentile(lat, 99.0)) if lat.size else ft(0.0),
        "carbon_kg": np.sum(np.array(dev_c, dtype=ft), dtype=ft),
        "device_carbon_kg": dict(zip(raw["ids"], dev_c)),
        "timeline_kg": np.cumsum(bins, dtype=ft),
    }
