"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are the semantics the kernels must match; tests sweep shapes/dtypes
and assert against these.  They are intentionally simple -- full softmax,
full materialization -- and correct.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True,
                        window: Optional[int] = None) -> jnp.ndarray:
    """q: [B,H,S,D]; k,v: [B,Hkv,T,D] with H a multiple of Hkv.
    Positions are 0..S-1 / 0..T-1 (prefill semantics, S == T)."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) / math.sqrt(d)
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((s, k.shape[2]), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= qi - ki < window
    scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", w, vv.astype(jnp.float32)) \
        .astype(q.dtype)


def decode_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         length: jnp.ndarray | int) -> jnp.ndarray:
    """Single-token GQA decode.  q: [B,H,D]; k,v: [B,Hkv,T,D]; `length` =
    number of valid cache entries (attend to positions < length)."""
    b, h, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) / math.sqrt(d)
    valid = jnp.arange(t)[None, None, :] < jnp.asarray(length).reshape(-1, 1, 1)
    scores = jnp.where(valid, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bht,bhtd->bhd", w, vv.astype(jnp.float32)) \
        .astype(q.dtype)


def segment_trapz_ref(a: jnp.ndarray, b: jnp.ndarray, w: jnp.ndarray,
                      kt: jnp.ndarray, kv: jnp.ndarray, cum: jnp.ndarray, *,
                      period: float) -> jnp.ndarray:
    """Per-segment trapezoid integrals of a periodic piecewise-linear
    curve: ``out_i = w_i * (F(b_i) - F(a_i))`` with F the prefix
    integral of the curve described by extended knots (kt, kv) and
    prefix integrals cum over [0, period] (``CarbonTrace`` internals).
    a, b, w: [N]; kt, kv, cum: [K]."""
    total = cum[-1]

    def prefix(t):
        k = jnp.floor(t / period)
        p = t - k * period
        j = jnp.clip(jnp.searchsorted(kt, p, side="right") - 1,
                     0, kt.shape[0] - 2)
        span = kt[j + 1] - kt[j]
        dt = p - kt[j]
        v_p = kv[j] + (kv[j + 1] - kv[j]) * dt / jnp.where(span > 0, span,
                                                           1.0)
        return k * total + cum[j] + dt * (kv[j] + v_p) * 0.5

    return w * (prefix(b) - prefix(a))


def fused_meter_ref(a: jnp.ndarray, b: jnp.ndarray, dt: jnp.ndarray,
                    w: jnp.ndarray, g: jnp.ndarray,
                    kt: jnp.ndarray, kv: jnp.ndarray, cum: jnp.ndarray,
                    periods: jnp.ndarray):
    """Metering pass (see ``segment_trapz.fused_meter``): per meter-log
    entry emit energy ``w * dt``, seconds ``dt`` and carbon increment
    ``w * (F_g(b) - F_g(a))``.  kt, kv, cum are stacked ``[G, K]``
    extended knot tables (rows padded by repeating the last knot); g:
    [N] int32 selects each entry's row; periods: [G].  Same closed
    form as ``segment_trapz_ref``, row by row, in the inputs' dtype."""
    ktg = jnp.take(kt, g, axis=0)               # [N, K]
    kvg = jnp.take(kv, g, axis=0)
    cumg = jnp.take(cum, g, axis=0)
    per = jnp.take(periods, g)
    total = cumg[:, -1]

    def prefix(t):
        k = jnp.floor(t / per)
        p = t - k * per
        j = jnp.sum((ktg <= p[:, None]).astype(jnp.int32), axis=1) - 1
        j = jnp.clip(j, 0, ktg.shape[1] - 2)[:, None]
        take = jnp.take_along_axis
        kt_j = take(ktg, j, axis=1)[:, 0]
        kv_j = take(kvg, j, axis=1)[:, 0]
        span = take(ktg, j + 1, axis=1)[:, 0] - kt_j
        d = p - kt_j
        v_p = kv_j + (take(kvg, j + 1, axis=1)[:, 0] - kv_j) * d \
            / jnp.where(span > 0, span, 1.0)
        return (k * total + take(cumg, j, axis=1)[:, 0]
                + d * (kv_j + v_p) * 0.5)

    return w * dt, dt, w * (prefix(b) - prefix(a))


def rglru_scan_ref(a: jnp.ndarray, bx: jnp.ndarray,
                   h0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Diagonal linear recurrence h_t = a_t * h_{t-1} + b_t.
    a, bx: [B,S,W] fp32; h0: [B,W] or None.  Returns h: [B,S,W]."""
    a = a.astype(jnp.float32)
    bx = bx.astype(jnp.float32)
    if h0 is not None:
        bx = bx.at[:, 0, :].add(a[:, 0, :] * h0.astype(jnp.float32))

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    _, h = jax.lax.associative_scan(combine, (a, bx), axis=1)
    return h
