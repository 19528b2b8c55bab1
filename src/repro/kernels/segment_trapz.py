"""The metering kernel of the mega-simulator's jax backend
(``fleet/mega/jaxback.py``): ``fused_meter``, one pass over the meter
log that emits each entry's energy, seconds and carbon increment.

Each meter-log entry is a segment ``(a_i, b_i)`` at constant power
``w_i`` metered against a periodic piecewise-linear intensity curve
``i_g(t)`` (``CarbonTrace`` internals: knot times ``kt`` covering
``[0, period]``, knot values ``kv``, prefix trapezoid integrals
``cum``), one curve per zone, stacked ``[G, K]``.  The carbon
increment is

    c_i = w_i * \\int_{a_i}^{b_i} i_g(u) du

-- the closed form ``CarbonTrace.integral`` evaluates one segment at a
time in Python (and ``kernels/ref.segment_trapz_ref`` over a single
table), across a million entries in one pass.

Whole periods are counted in f64 XLA; the in-period rest runs on the
Pallas kernel, written for Mosaic: (rows, 128) f32 blocks, no gathers
(a one-hot over the few trace rows, a sum over knot intervals), and f32
arithmetic arranged so the carbon error has a derived bound
(``CARBON_REL``); everything it cannot do in f32 stays in f64 XLA
around it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Fused metering geometry: entries are laid out as (rows, 128) f32 lanes;
# a grid step holds up to _BLOCK_ROWS rows and walks them one (8, 128)
# vreg tile at a time, so the knot loop's carries stay in registers.
_LANES = 128
_SUB = 8
_BLOCK_ROWS = 512

# The carbon bound the f32 kernel is held to (derived in ``fused_meter``):
# every entry's carbon is within CARBON_REL relative of the exact
# integral for every shipped trace shape and zone preset, so every fleet
# total, per-device value and timeline point (sums of non-negative
# entries) is too.
CARBON_REL = 1e-6


def _period_integral_kernel(t_ref, v_ref, s_ref, phi_ref, plo_ref, r_ref,
                            g_ref, o_ref, *, n_groups: int, n_knots: int):
    """In-period carbon integral of each entry, gather-free, in f32.

    Entry ``(p, r, g)`` asks for ``I = int_p^{p+r} i_g(u) du`` with
    ``0 <= p < period`` (passed as ``p_hi + p_lo``) and ``0 <= r <
    period``.  ``t/v`` (SMEM, ``[G * n_knots]``) are each curve's knots
    over TWO periods, so a segment that wraps past the period end needs
    no branch; ``s`` holds the slope of every interval (same stride).
    For each knot interval the kernel takes the overlap ``[lo, hi]``
    with the segment in offsets from ``p`` and adds its trapezoid.  Endpoint values are
    the knot's own value where the knot lies inside the segment and the
    curve evaluated at the segment's start or end otherwise, so no
    absolute prefix is ever subtracted; the sum is compensated."""
    nk = n_knots

    def pick(ref, stride, j, g):
        # one-hot select of trace row g: the scalar itself for one trace
        x = ref[j]
        for gi in range(1, n_groups):
            x = jnp.where(g == gi, ref[gi * stride + j], x)
        return x

    def tile(i, _):
        rows = pl.ds(pl.multiple_of(i * _SUB, _SUB), _SUB)
        p_hi, p_lo = phi_ref[rows, :], plo_ref[rows, :]
        r, g = r_ref[rows, :], g_ref[rows, :]

        def offset(j):              # knot j's offset from the segment start
            return (pick(t_ref, nk, j, g) - p_hi) - p_lo

        def interval(j, c):
            d_l, v_l, acc, comp = c
            d_r = offset(j + 1)
            v_r = pick(v_ref, nk, j + 1, g)
            s = pick(s_ref, nk, j, g)
            lo = jnp.clip(d_l, 0.0, r)
            hi = jnp.clip(d_r, 0.0, r)
            f_lo = jnp.where(d_l < 0.0, v_l - s * d_l, v_l)
            f_hi = jnp.where(d_r > r, v_l + s * (r - d_l), v_r)
            y = (hi - lo) * (f_lo + f_hi) * 0.5 - comp
            t = acc + y
            return d_r, v_r, t, (t - acc) - y

        zero = jnp.zeros_like(r)
        init = (offset(0), pick(v_ref, nk, 0, g), zero, zero)
        _, _, acc, _ = jax.lax.fori_loop(jnp.int32(0), jnp.int32(nk - 1),
                                         interval, init)
        o_ref[rows, :] = acc

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(o_ref.shape[0] // _SUB),
                      tile, None)


def _period_integral(tab_t, tab_v, tab_s, p_hi, p_lo, r, g, *,
                     interpret: bool):
    """Run the kernel over [N] f32 entries (N padded here to whole
    blocks; pad entries have r = 0 and integrate to exactly 0)."""
    n = p_hi.shape[0]
    gk, nk = tab_t.shape
    rows = max(-(-n // _LANES), 1)
    rows = -(-rows // _SUB) * _SUB
    bm = min(_BLOCK_ROWS, rows)
    rows = -(-rows // bm) * bm
    pad = rows * _LANES - n

    def lanes(x):
        return jnp.pad(x, (0, pad)).reshape(rows, _LANES)

    # index maps return int32 explicitly: under x64 a bare 0 is an i64,
    # which Mosaic cannot lower
    smem = pl.BlockSpec((gk * nk,), lambda i: (jnp.int32(0),),
                        memory_space=pltpu.SMEM)
    blk = pl.BlockSpec((bm, _LANES), lambda i: (i, jnp.int32(0)))
    kernel = functools.partial(_period_integral_kernel, n_groups=gk,
                               n_knots=nk)
    out = pl.pallas_call(
        kernel,
        grid=(rows // bm,),
        in_specs=[smem, smem, smem, blk, blk, blk, blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=interpret,
    )(tab_t.reshape(-1), tab_v.reshape(-1), tab_s.reshape(-1),
      lanes(p_hi), lanes(p_lo), lanes(r), lanes(g))
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_meter(a: jnp.ndarray, b: jnp.ndarray, dt: jnp.ndarray,
                w: jnp.ndarray, g: jnp.ndarray,
                kt: jnp.ndarray, kv: jnp.ndarray, cum: jnp.ndarray,
                periods: jnp.ndarray, *, interpret: bool):
    """Metering pass over ``N`` meter-log entries.

    a, b: [N] absolute segment bounds; dt: [N] the metered interval;
    w: [N] watts; g: [N] int32 trace-group index; kt, kv, cum: [G, K]
    stacked extended knot tables (rows padded by repeating the last
    knot); periods: [G].

    Returns ``(e, s, c)``, all [N] in the inputs' dtype (f64 in the
    fleet backend): joules ``w * dt`` and seconds ``dt``, computed here
    in XLA, never through the f32 kernel, and the carbon increment ``c = w * int_a^b i_g(u) du``.

    The carbon lane splits the integral: whole periods in f64 XLA
    (``n * total_g``), the in-period rest on the Pallas kernel in f32
    (Mosaic has no 64-bit types), integrated locally from the start
    offset over the remaining span -- never as a difference of two
    absolute prefixes, which cancels at ~1e4 s in f32.

    Error bound of the kernel's part ``I`` (u = 2^-24): the start
    offset enters exact to ~2^-48 (split ``p_hi + p_lo``), the span
    rounds once (``|dI| <= u * r * max i``), each knot offset rounds at
    most twice (moving a knot by ``<= u * r`` moves the interpolant's
    area by ``<= u * r * TV``), the segment-end value carries ``u * r``
    of slope error over one partial interval (``<= 1.5 u r max|dkv|``),
    and the per-interval roundings plus the compensated sum stay below
    ``8 u I``.  With ``I >= r * min i`` this is ``|dI| <= C u I``,
    ``C = kappa + TV / min + 1.5 max|dkv| / min + 8`` (kappa the
    curve's max/min, TV its total variation over one period); the
    whole-period part is exact to f64, so ``c`` is within ``C u``
    relative of the exact integral.  Every shipped trace has ``C u``
    under ``CARBON_REL`` (``C`` ~ 13 for solar-duck; pinned in
    ``tests/test_kernels.py``).
    """
    f32 = jnp.float32
    per = periods[g]
    total = cum[:, -1][g]
    span = b - a
    p = a - jnp.floor(a / per) * per
    n_per = jnp.floor(span / per)
    rem = span - n_per * per
    p_hi = p.astype(f32)
    p_lo = (p - p_hi.astype(p.dtype)).astype(f32)
    # knots over two periods: [0, period] then (period, 2 * period]
    tab_t = jnp.concatenate([kt, kt[:, 1:] + periods[:, None]], axis=1)
    tab_v = jnp.concatenate([kv, kv[:, 1:]], axis=1)
    dk = jnp.diff(tab_t, axis=1)
    tab_s = jnp.where(dk > 0, jnp.diff(tab_v, axis=1)
                      / jnp.where(dk > 0, dk, 1.0), 0.0)
    tab_s = jnp.pad(tab_s, ((0, 0), (0, 1)))
    i_rest = _period_integral(tab_t.astype(f32), tab_v.astype(f32),
                              tab_s.astype(f32), p_hi, p_lo,
                              rem.astype(f32), g.astype(jnp.int32),
                              interpret=interpret)
    c = w * (n_per * total + i_rest.astype(w.dtype))
    return w * dt, dt, c
