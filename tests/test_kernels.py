"""Pallas kernel allclose sweeps vs. the pure-jnp oracles (interpret mode).

Per assignment: for each kernel, sweep shapes/dtypes and
assert_allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

FLASH_SHAPES = [
    # (B, H, Hkv, S, D)
    (1, 4, 4, 128, 64),      # MHA
    (2, 8, 2, 256, 64),      # GQA 4:1
    (1, 4, 1, 256, 128),     # MQA
    (2, 2, 2, 512, 32),      # long-ish
]


@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention_sweep(shape, dtype, window):
    b, h, hkv, s, d = shape
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d), dtype)
    got = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


DECODE_SHAPES = [
    (1, 4, 4, 256, 64),
    (2, 8, 2, 512, 64),
    (4, 8, 1, 1024, 128),
]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(shape, dtype):
    b, h, hkv, t, d = shape
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, t, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, t, d), dtype)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, t, size=b), jnp.int32)
    got = ops.decode_attention(q, k, v, lengths)
    want = ref.decode_attention_ref(q, k, v, lengths)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_decode_ignores_entries_past_length():
    """Garbage beyond the frontier must not affect the output."""
    b, h, hkv, t, d = 1, 4, 2, 256, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, t, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, t, d))
    out1 = ops.decode_attention(q, k, v, jnp.array([100]))
    k2 = k.at[:, :, 100:].set(1e4)
    v2 = v.at[:, :, 100:].set(-1e4)
    out2 = ops.decode_attention(q, k2, v2, jnp.array([100]))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)


@pytest.mark.parametrize("shape", [(1, 128, 128), (2, 256, 256),
                                   (3, 384, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(shape, dtype):
    b, s, w = shape
    a = jax.random.uniform(jax.random.PRNGKey(0), (b, s, w), dtype,
                           0.5, 0.999)
    bx = jax.random.normal(jax.random.PRNGKey(1), (b, s, w), dtype)
    h0 = jax.random.normal(jax.random.PRNGKey(2), (b, w), dtype)
    got = ops.rglru_scan(a, bx, h0)
    want = ref.rglru_scan_ref(a, bx, h0)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_rglru_carries_initial_state():
    b, s, w = 1, 128, 128
    a = jnp.full((b, s, w), 0.9)
    bx = jnp.zeros((b, s, w))
    h0 = jnp.ones((b, w))
    h = ops.rglru_scan(a, bx, h0)
    np.testing.assert_allclose(np.asarray(h[:, 0]), 0.9, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h[:, -1]),
                               0.9 ** s, rtol=1e-3)


# ---------------------------------------------------------------------------
# segment_trapz: single-trace carbon metering.  Oracle chain: the
# ``fused_meter`` kernel fed one stacked trace row == the scalar-table
# f64 reference ``segment_trapz_ref`` == CarbonTrace.integral evaluated
# one segment at a time.
# ---------------------------------------------------------------------------

def _trace_tables(trace):
    kt = np.asarray(trace._kt)
    kv = np.asarray(trace._kv)
    cum = np.asarray(trace._cum)
    return kt, kv, cum


def _stacked_tables(traces):
    """CarbonTrace knot tables stacked [G, K]: rows padded by repeating
    the last knot (in-period offsets are strictly below the period, so
    the pad never matches a compare)."""
    kmax = max(len(t._kt) for t in traces)
    kt = np.stack([np.concatenate(
        [t._kt, np.full(kmax - len(t._kt), t._kt[-1])]) for t in traces])
    kv = np.stack([np.concatenate(
        [t._kv, np.full(kmax - len(t._kv), t._kv[-1])]) for t in traces])
    cum = np.stack([np.concatenate(
        [t._cum, np.full(kmax - len(t._cum), t._cum[-1])]) for t in traces])
    per = np.array([t.period_s for t in traces])
    return kt, kv, cum, per


def _single_trace_carbon(a, b, w, trace):
    """``fused_meter``'s carbon lane for segments metered against one
    trace (every entry in group 0 of a one-row stacked table)."""
    tabs = _stacked_tables([trace])
    _, _, c = ops.fused_meter(
        *[jnp.asarray(x) for x in (a, b, b - a, w)],
        jnp.zeros(len(a), jnp.int32), *[jnp.asarray(x) for x in tabs])
    return np.asarray(c)


@pytest.mark.parametrize("n", [1, 17, 512, 2001])
@pytest.mark.parametrize("shape_name", ["solar-duck", "wind-night", "flat"])
def test_segment_trapz_sweep(n, shape_name):
    from repro.fleet.carbon import make_trace
    from repro.kernels.segment_trapz import CARBON_REL

    trace = make_trace(shape_name, 0.39)
    kt, kv, cum = _trace_tables(trace)
    rng = np.random.default_rng(n)
    # spans crossing knots, bins, midnight wrap, and multiple periods
    a = np.sort(rng.uniform(0.0, 2.5 * trace.period_s, n))
    b = a + rng.uniform(0.0, 4 * 3600.0, n)
    w = rng.uniform(10.0, 700.0, n)
    want = np.array([trace.integral(x, y) * z for x, y, z in zip(a, b, w)])
    with jax.enable_x64(True):
        got_ref = np.asarray(ref.segment_trapz_ref(
            *[jnp.asarray(x) for x in (a, b, w, kt, kv, cum)],
            period=trace.period_s))
        got_pl = _single_trace_carbon(a, b, w, trace)
    np.testing.assert_allclose(got_ref, want, rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(got_pl, want, rtol=CARBON_REL, atol=1e-12)


def test_segment_trapz_f32_kernel_matches_ref():
    """TPU-realistic dtype: fed f32 inputs outside any x64 scope, the
    metering kernel returns f32 lanes -- energy and seconds the f32
    products themselves -- and its carbon stays within CARBON_REL of
    the f64 reference evaluated on the same f32 inputs and knots."""
    from repro.fleet.carbon import solar_duck
    from repro.kernels.segment_trapz import CARBON_REL

    trace = solar_duck(0.39)
    kt, kv, cum, per = (x.astype(np.float32)
                        for x in _stacked_tables([trace]))
    rng = np.random.default_rng(0)
    a = np.sort(rng.uniform(0, 86400.0, 700)).astype(np.float32)
    b = a + np.float32(50.0)
    w = np.full(700, 300.0, np.float32)
    e, s, c = (np.asarray(o) for o in ops.fused_meter(
        *[jnp.asarray(x) for x in (a, b, b - a, w)],
        jnp.zeros(700, jnp.int32),
        *[jnp.asarray(x) for x in (kt, kv, cum, per)]))
    assert e.dtype == s.dtype == c.dtype == np.float32
    assert np.array_equal(e, w * (b - a)) and np.array_equal(s, b - a)
    # the curve the kernel sees: the f32 knots, with prefix integrals
    # rebuilt from them in f64 (the f32-rounded cum does not match them)
    kt64, kv64 = kt[0].astype(np.float64), kv[0].astype(np.float64)
    cum64 = np.concatenate([[0.0], np.cumsum(
        np.diff(kt64) * (kv64[1:] + kv64[:-1]) * 0.5)])
    with jax.enable_x64(True):
        want = np.asarray(ref.segment_trapz_ref(
            *[jnp.asarray(x.astype(np.float64))
              for x in (a, b, w, kt64, kv64, cum64)],
            period=float(per[0])))
    np.testing.assert_allclose(c, want, rtol=CARBON_REL, atol=1e-6)


def test_segment_trapz_zero_and_empty_segments():
    from repro.fleet.carbon import solar_duck

    trace = solar_duck(0.39)
    kt, kv, cum = _trace_tables(trace)
    with jax.enable_x64(True):
        empty = ref.segment_trapz_ref(
            jnp.zeros(0), jnp.zeros(0), jnp.zeros(0),
            jnp.asarray(kt), jnp.asarray(kv), jnp.asarray(cum),
            period=trace.period_s)
        point = ref.segment_trapz_ref(
            jnp.asarray([100.0, 7e4]), jnp.asarray([100.0, 7e4]),
            jnp.asarray([500.0, 500.0]),
            jnp.asarray(kt), jnp.asarray(kv), jnp.asarray(cum),
            period=trace.period_s)
    assert np.asarray(empty).shape == (0,)
    np.testing.assert_allclose(np.asarray(point), 0.0, atol=1e-12)


# ---------------------------------------------------------------------------
# fused_meter: the metering pass behind the mega jax backend's fused
# finalize (energy segment-sum + per-tier billed seconds + per-trace
# carbon trapezoid).  The kernel integrates each entry's in-period span
# in f32; oracle chain: kernel == f64 jnp reference == CarbonTrace.integral
# per entry within the derived bound CARBON_REL, and the energy/seconds
# outputs are exactly the f64 ``w * dt`` and ``dt``.
# ---------------------------------------------------------------------------

F32_U = 2.0 ** -24          # unit roundoff of the kernel's arithmetic


def _carbon_error_factor(kv) -> float:
    """``C`` of ``segment_trapz.fused_meter``'s derived bound
    ``|dI| <= C * F32_U * I`` for a curve with knot values ``kv``:
    ``kappa + TV/min + 1.5 * max|dkv|/min + 8``."""
    v = np.asarray(kv, dtype=np.float64)
    lo = float(v.min())
    d = np.abs(np.diff(v))
    return float(v.max() / lo + d.sum() / lo
                 + 1.5 * d.max(initial=0.0) / lo + 8.0)


def _exact_integral(trace, t0, t1):
    """int_t0^t1 i(u) du in exact rational arithmetic: the oracle for
    spans where the f64 prefix difference of ``CarbonTrace.integral``
    itself cancels (sub-second segments at ~1e5 s)."""
    import bisect
    from fractions import Fraction as Fr

    kt = [Fr(x) for x in trace._kt]
    kv = [Fr(x) for x in trace._kv]
    per = Fr(trace.period_s)
    areas = [(kt[i + 1] - kt[i]) * (kv[i + 1] + kv[i]) / 2
             for i in range(len(kt) - 1)]

    def F(t):
        k = t // per
        p = t - k * per
        j = min(max(bisect.bisect_right(kt, p) - 1, 0), len(kt) - 2)
        span = kt[j + 1] - kt[j]
        v_p = kv[j] + (kv[j + 1] - kv[j]) * (p - kt[j]) / span
        return k * sum(areas) + sum(areas[:j]) + (p - kt[j]) * (kv[j] + v_p) / 2

    return float(F(Fr(t1)) - F(Fr(t0)))


@pytest.mark.parametrize("n", [1, 33, 1024, 3001])
@pytest.mark.parametrize("seed", [0, 7])
def test_fused_meter_sweep(n, seed):
    """Multi-trace entries crossing knots, midnight, and whole periods:
    carbon matches the f64 reference and the Python integral within the
    kernel's bound, energy/seconds are exact pass-throughs, and the f64
    straddle prefix is the integral from 0 to each start."""
    from repro.fleet.carbon import make_trace
    from repro.fleet.mega.jaxback import _prefix_at
    from repro.kernels.segment_trapz import CARBON_REL

    traces = [make_trace(s, 0.39) for s in
              ("solar-duck", "wind-night", "flat")]
    kt, kv, cum, per = _stacked_tables(traces)
    rng = np.random.default_rng(seed)
    a = np.sort(rng.uniform(0.0, 2.5 * 86400.0, n))
    b = a + rng.uniform(0.0, 4 * 3600.0, n)
    dt = b - a
    w = rng.uniform(10.0, 700.0, n)
    g = rng.integers(0, len(traces), n).astype(np.int32)
    want_c = np.array([traces[gi].integral(x, y) * z
                       for gi, x, y, z in zip(g, a, b, w)])
    want_fa = np.array([traces[gi].integral(0.0, x)
                        for gi, x in zip(g, a)])
    with jax.enable_x64(True):
        args = [jnp.asarray(x) for x in (a, b, dt, w, g, kt, kv, cum, per)]
        e, s, c = (np.asarray(o) for o in ops.fused_meter(*args))
        ref_e, ref_s, ref_c = (np.asarray(o)
                               for o in ref.fused_meter_ref(*args))
        fa = np.asarray(_prefix_at(*args[5:], args[4], args[0]))
    # pass-through outputs: exact, not allclose -- the metering
    # finalize's energy segment-sum never sees the f32 kernel
    assert np.array_equal(e, w * dt) and np.array_equal(e, ref_e)
    assert np.array_equal(s, dt) and np.array_equal(s, ref_s)
    np.testing.assert_allclose(c, ref_c, rtol=CARBON_REL, atol=1e-12)
    np.testing.assert_allclose(c, want_c, rtol=CARBON_REL, atol=1e-12)
    np.testing.assert_allclose(fa, want_fa, rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("max_span_s", [1e-2, 5.0, 4 * 3600.0, 86400.0,
                                        3 * 86400.0])
def test_fused_meter_within_derived_bound(max_span_s):
    """Per entry, against exact rational integrals: the kernel holds the
    derived bound ``_carbon_error_factor(kv) * F32_U`` for every span
    regime, including starts a hair off a knot."""
    from repro.fleet.carbon import make_trace

    traces = [make_trace(s, 0.39) for s in
              ("solar-duck", "wind-night", "flat")]
    kt, kv, cum, per = _stacked_tables(traces)
    rng = np.random.default_rng(int(max_span_s))
    n = 240
    a = rng.uniform(0.0, 2.5 * 86400.0, n)
    a[:60] = (np.round(a[:60] / 1800.0) * 1800.0
              + rng.uniform(-1e-3, 1e-3, 60))
    b = a + rng.uniform(0.0, max_span_s, n)
    g = rng.integers(0, len(traces), n).astype(np.int32)
    with jax.enable_x64(True):
        _, _, c = ops.fused_meter(*[jnp.asarray(x) for x in (
            a, b, b - a, np.ones(n), g, kt, kv, cum, per)])
    c = np.asarray(c)
    exact = np.array([_exact_integral(traces[gi], x, y)
                      for gi, x, y in zip(g, a, b)])
    bound = np.array([_carbon_error_factor(traces[gi]._kv)
                      for gi in g]) * F32_U
    assert np.all(np.abs(c - exact) <= bound * exact)


def test_carbon_bound_covers_every_shipped_trace():
    """CARBON_REL is the anchor the backends are held to: it must cover
    the derived bound of every curve the fleet can price against."""
    from repro.fleet.carbon import TRACE_SHAPES, make_trace, trace_for_zone
    from repro.fleet.catalog import MIXES
    from repro.kernels.segment_trapz import CARBON_REL

    curves = [make_trace(s, 0.39) for s in TRACE_SHAPES]
    curves += [trace_for_zone(z) for z in MIXES]
    for tr in curves:
        assert _carbon_error_factor(tr._kv) * F32_U <= CARBON_REL, tr.name


def test_fused_meter_empty_and_zero_width():
    from repro.fleet.carbon import solar_duck
    from repro.fleet.mega.jaxback import _prefix_at

    kt, kv, cum, per = _stacked_tables([solar_duck(0.39)])
    with jax.enable_x64(True):
        tabs = [jnp.asarray(x) for x in (kt, kv, cum, per)]
        empty = ops.fused_meter(jnp.zeros(0), jnp.zeros(0), jnp.zeros(0),
                                jnp.zeros(0), jnp.zeros(0, jnp.int32),
                                *tabs)
        point = ops.fused_meter(jnp.asarray([7e4]), jnp.asarray([7e4]),
                                jnp.asarray([0.0]), jnp.asarray([500.0]),
                                jnp.zeros(1, jnp.int32), *tabs)
        fa = _prefix_at(*tabs, jnp.zeros(1, jnp.int32), jnp.asarray([7e4]))
    assert all(np.asarray(o).shape == (0,) for o in empty)
    e, s, c = (np.asarray(o) for o in point)
    assert e[0] == 0.0 and s[0] == 0.0 and c[0] == 0.0
    assert np.asarray(fa)[0] > 0.0          # prefix at 7e4 s into the day


def test_fused_meter_matches_segment_trapz():
    """The fused kernel's carbon lane reproduces the scalar-table f64
    reference ``segment_trapz_ref`` on a single-trace workload (same
    integral, stacked-table f32 kernel)."""
    from repro.fleet.carbon import make_trace
    from repro.kernels.segment_trapz import CARBON_REL

    trace = make_trace("wind-night", 0.39)
    rng = np.random.default_rng(3)
    n = 777
    a = np.sort(rng.uniform(0.0, 2.0 * trace.period_s, n))
    b = a + rng.uniform(0.0, 7200.0, n)
    w = rng.uniform(50.0, 400.0, n)
    with jax.enable_x64(True):
        c = _single_trace_carbon(a, b, w, trace)
        flat = ref.segment_trapz_ref(
            jnp.asarray(a), jnp.asarray(b), jnp.asarray(w),
            *[jnp.asarray(x) for x in _trace_tables(trace)],
            period=trace.period_s)
    np.testing.assert_allclose(c, np.asarray(flat), rtol=CARBON_REL, atol=0)
