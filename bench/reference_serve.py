"""Plain reference of a fleet day whose requests take service time.

A straightforward per-request event loop over the semantics the
configuration states, written from those semantics and importing
nothing of the program.  Devices, prewarm, least-loaded placement, the
loader, eviction and the accounting are ``bench/reference.py``'s; on
top of them:

  * each resident replica has ``max_batch`` decode slots; a request
    routed to it starts at once if a slot is free, else it waits in the
    replica's FIFO queue, as does every request routed to a replica that
    is loading or queued for a load;
  * warm-first routing picks, among the replicas resident or loading,
    the one with the fewest waiting requests, then the fewest busy slots
    (a loading replica counting as ``max_batch`` busy), then the lowest
    device id;
  * a request's service time is fixed when it starts, by the roofline
    at the replica's occupancy then (itself included): ``overhead_s`` +
    ``prompt_tokens x F / (TFLOPS x mfu)`` + ``output_tokens`` decode
    steps of ``W / BW + occupancy x (K x (prompt + output / 2) / BW +
    F / (TFLOPS x mfu))``, where a checkpoint of ``W`` bytes holds
    ``W / dtype_bytes`` parameters, ``F`` = 2 x parameters flops per
    token and ``K`` = ``kv_bytes_per_weight_byte x W`` cache bytes per
    token;
  * when a load lands, its waiters fill the free slots, oldest first;
    when a request completes, the oldest waiter takes its slot;
  * a replica with a busy slot or a waiting request is never evicted,
    neither by its idle timeout nor to make room; its idle timeout is
    armed again when the last of them is gone (at a completion);
  * power: a device with busy slots draws its base (loading watts while
    a load runs, else parked watts) plus, per busy slot,
    ``P_active - P_ctx`` with ``P_active = P_ctx + service_util x (TDP -
    P_ctx)``; otherwise loading, parked or bare watts.

``simulate`` returns ``bench/reference.py``'s raw record plus the count
of requests that took a slot at a completion (``slot_waits``);
``account`` there turns it into the compared numbers, in float64 (the
reference) or float32 (the control).
"""
from __future__ import annotations

import heapq
import math
from collections import deque
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.reference import GB, build_devices, loader

BARE, PARKED, LOADING, ACTIVE = 0, 1, 2, 3


def service_table(ckpt_gb: float, sku: dict, config: dict, shape: dict,
                  max_batch: int) -> List[float]:
    """Service seconds of one request at occupancy 1..max_batch."""
    w = float(int(ckpt_gb * GB))
    flops = 2.0 * (w / float(config["dtype_bytes"]))
    kv = float(config["kv_bytes_per_weight_byte"]) * w
    bw = sku["mem_bw_gbps"] * 1e9
    peak = sku["tflops_bf16"] * 1e12 * float(config["mfu"])
    prompt = int(shape["prompt_tokens"])
    out = int(shape["output_tokens"])
    prefill = prompt * flops / peak
    per_seq = kv * (prompt + out / 2) / bw + flops / peak
    return [float(config["overhead_s"]) + prefill
            + out * (w / bw + b * per_seq) for b in range(1, max_batch + 1)]


def simulate(routes: Sequence[Tuple[str, np.ndarray, float]], config: dict,
             shape: dict) -> dict:
    """Run one day. ``routes`` is [(route id, sorted arrivals, ckpt GB)];
    ``shape`` holds the request's ``prompt_tokens`` and
    ``output_tokens``."""
    skus = config["skus"]
    horizon = float(config["horizon_s"])
    mb = int(config["max_batch"])
    util = float(config["service_util"])
    spec_order = build_devices(config["fleet"])
    ids = sorted(i for i, _ in spec_order)
    idx = {i: k for k, i in enumerate(ids)}
    sku_of = dict(spec_order)
    N = len(ids)
    dsku = [skus[sku_of[i]] for i in ids]
    vcap = np.array([s["vram_gb"] for s in dsku], dtype=np.float64)
    scap = np.array([s["slots"] for s in dsku], dtype=np.int64)
    p_bare = [s["p_base_w"] for s in dsku]
    p_park = [s["p_base_w"] + (s["p_ctx_w"] - s["p_base_w"]) + 0.0
              for s in dsku]
    inc = [(s["p_ctx_w"] + util * (s["tdp_w"] - s["p_ctx_w"]))
           - s["p_ctx_w"] for s in dsku]
    vfac = float(config["vram_per_checkpoint"])
    M = len(routes)
    vram = [r[2] * vfac for r in routes]
    lcache: Dict[Tuple[int, str], tuple] = {}
    scache: Dict[Tuple[int, str], List[float]] = {}

    def ld(m, d):
        key = (m, sku_of[ids[d]])
        if key not in lcache:
            lcache[key] = loader(routes[m][2], dsku[d])
        return lcache[key]

    def svc(m, d, occ):
        key = (m, sku_of[ids[d]])
        if key not in scache:
            scache[key] = service_table(routes[m][2], dsku[d], config,
                                        shape, mb)
        return scache[key][occ - 1]

    occ = np.zeros(N, dtype=np.int64)
    vused = np.zeros(N, dtype=np.float64)
    state = [BARE] * N
    watts = list(p_bare)
    since = [0.0] * N
    segs: List[List[List[float]]] = [[] for _ in range(N)]
    n_reg = [0] * N
    act: List[set] = [set() for _ in range(N)]
    busy_dev = [0] * N
    cold = [0] * N
    reqs = [0] * N
    inflight = [-1] * N
    lqueue = [deque() for _ in range(N)]
    lqset: List[set] = [set() for _ in range(N)]
    # replica state, keyed (device, model)
    pos: Dict[Tuple[int, int], int] = {}
    resident: set = set()
    loading_r: set = set()
    deadline: Dict[Tuple[int, int], float] = {}
    busy: Dict[Tuple[int, int], int] = {}
    waitq: Dict[Tuple[int, int], deque] = {}
    res = [set() for _ in range(M)]
    loading = [set() for _ in range(M)]
    waits: List[Tuple[float, float]] = []             # (served at, arrival)
    count = {"zero": 0, "slot_waits": 0}

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
            if sg and sg[-1][1] == t0 and sg[-1][2] == p \
                    and sg[-1][3] == state[d]:
                sg[-1][1] = t
            else:
                sg.append([t0, t, p, state[d]])
        state[d] = ns
        watts[d] = w
        since[d] = t

    def power(d, t):
        """The device's draw from what it is doing now."""
        b = busy_dev[d]
        m = inflight[d]
        if b:
            base = ld(m, d)[0] if m >= 0 else p_park[d]
            ns, w = ACTIVE, base + b * inc[d]
        elif m >= 0:
            ns, w = LOADING, ld(m, d)[0]
        elif any((d, k) in resident for k in act[d]):
            ns, w = PARKED, p_park[d]
        else:
            ns, w = BARE, p_bare[d]
        if ns != state[d] or w != watts[d]:
            trans(d, t, ns, w)

    def recompute(d):
        s = 0.0
        for m in sorted(act[d], key=lambda m: pos[(d, m)]):
            s += vram[m]
        vused[d] = s

    def register(d, m):
        if (d, m) not in pos:
            pos[(d, m)] = n_reg[d]
            n_reg[d] += 1
            busy[(d, m)] = 0
            waitq[(d, m)] = deque()

    def pinned(d, m):
        return busy[(d, m)] > 0 or len(waitq[(d, m)]) > 0

    def arm(d, m, t):
        T = ld(m, d)[2]
        deadline[(d, m)] = t + T
        if math.isfinite(T):
            push(t + T, 4, ("evict", d, m))

    def evict(d, m, t):
        resident.discard((d, m))
        deadline.pop((d, m), None)
        act[d].discard(m)
        res[m].discard(d)
        occ[d] -= 1
        recompute(d)
        power(d, t)

    def make_room(d, m_new, t):
        need = vram[m_new]

        def over():
            return vused[d] + need > vcap[d] or occ[d] + 1 > scap[d]

        if not over():
            return
        victims = sorted((m for m in act[d] if m != m_new
                          and (d, m) in resident and not pinned(d, m)),
                         key=lambda m: pos[(d, m)])
        victims.sort(key=lambda m: deadline[(d, m)])
        for m in victims:
            if not over():
                break
            evict(d, m, t)

    def pump(d, t):
        if inflight[d] >= 0:
            return
        q = lqueue[d]
        while q:
            m = q.popleft()
            lqset[d].discard(m)
            if (d, m) in resident or (d, m) in loading_r:
                continue
            inflight[d] = m
            make_room(d, m, t)
            loading_r.add((d, m))
            act[d].add(m)
            loading[m].add(d)
            occ[d] += 1
            recompute(d)
            push(t + ld(m, d)[1], 0, ("load", d, m))
            return

    def least_loaded(m):
        need = vram[m]
        free = vcap - vused
        cand = np.flatnonzero((scap - occ >= 1) & (free >= need))
        if cand.size == 0:
            cand = np.arange(N)
        oc = occ[cand]
        cand = cand[oc == oc.min()]
        f = free[cand]
        return int(cand[f == f.max()][0])

    def start(d, m, t, arrived):
        """One request takes a slot now."""
        b = busy[(d, m)] + 1
        busy[(d, m)] = b
        busy_dev[d] += 1
        reqs[d] += 1
        if arrived == t:
            count["zero"] += 1
        else:
            waits.append((t, arrived))
        push(t + svc(m, d, b), 0, ("done", d, m))

    def fill(d, m, t):
        n = 0
        q = waitq[(d, m)]
        while q and busy[(d, m)] < mb:
            start(d, m, t, q.popleft())
            n += 1
        return n

    def arrival(t, m):
        locs = res[m] | loading[m]
        if locs:
            d = min(locs, key=lambda x: (len(waitq[(x, m)]),
                                         busy[(x, m)] + (0 if x in res[m]
                                                         else mb), x))
        else:
            d = least_loaded(m)
            register(d, m)
        deadline[(d, m)] = math.inf
        if (d, m) in resident and busy[(d, m)] < mb:
            start(d, m, t, t)
        else:
            waitq[(d, m)].append(t)
            if (d, m) not in resident and (d, m) not in loading_r \
                    and m not in lqset[d]:
                lqset[d].add(m)
                lqueue[d].append(m)
                pump(d, t)
        power(d, t)

    def load_done(t, d, m):
        inflight[d] = -1
        loading_r.discard((d, m))
        resident.add((d, m))
        loading[m].discard(d)
        res[m].add(d)
        recompute(d)
        cold[d] += 1
        if waitq[(d, m)]:
            deadline[(d, m)] = math.inf
        else:
            arm(d, m, t)
        fill(d, m, t)
        pump(d, t)
        power(d, t)

    def serve_done(t, d, m):
        busy[(d, m)] -= 1
        busy_dev[d] -= 1
        if waitq[(d, m)]:
            count["slot_waits"] += fill(d, m, t)
        elif busy[(d, m)] == 0:
            arm(d, m, t)
        power(d, t)

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
        recompute(d)
        cold[d] += 1
        res[m].add(d)
        trans(d, 0.0, PARKED, p_park[d])
        arm(d, m, 0.0)

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

    last_done = [0.0]

    def fire(t, phase, payload, others_pending):
        """One heap event; returns False for an eviction to defer."""
        kind, d, m = payload
        if kind == "load":
            last_done[0] = max(last_done[0], t)
            load_done(t, d, m)
            return True
        if kind == "done":
            last_done[0] = max(last_done[0], t)
            serve_done(t, d, m)
            return True
        if (d, m) not in resident or deadline[(d, m)] != t \
                or pinned(d, m):
            return True
        if t < horizon or others_pending:
            evict(d, m, t)
            return True
        return False

    for t, m in zip(t_all, m_all):
        while heap and (heap[0][0] < t or (heap[0][0] == t
                                           and heap[0][1] < 3)):
            et, ph, _, pl = heapq.heappop(heap)
            fire(et, ph, pl, True)
        arrival(t, m)
    deferred = []
    while heap:
        et, ph, _, pl = heapq.heappop(heap)
        if not fire(et, ph, pl, any(e[1] == 0 for e in heap)):
            deferred.append((et, pl))
    final = max(horizon, last_done[0])
    for et, (_, d, m) in deferred:
        if (d, m) in resident and deadline[(d, m)] == et and et < final:
            evict(d, m, et)
    for d in range(N):
        trans(d, final, state[d], watts[d])
    left = sum(len(q) for q in waitq.values()) + sum(busy.values())
    if left:
        raise RuntimeError(f"reference left {left} requests unserved")
    return {"ids": ids, "skus": [sku_of[i] for i in ids], "segs": segs,
            "cold": cold, "reqs": reqs, "n_zero": count["zero"],
            "waits": waits, "final_s": final,
            "slot_waits": count["slot_waits"]}
