"""The benchmark's own traffic and carbon-trace generators.

Copies of the fleet simulator's generators, kept here so that what a
cell offers the program is fixed by the benchmark and not by the code
under test:

  * the per-route plan and thinned inhomogeneous Poisson sampling of a
    production-shaped day (``day_routes``), as
    ``repro.fleet.mega.traces`` draws it: every seed gives a day of its
    own size;
  * the solar-duck / wind-night / flat grid-intensity knots
    (``carbon_points``), scaled to a daily mean.

A traffic mix is data: ``generator`` names a family, a module
``bench/families/<generator>.py`` whose ``rates(params)`` gives the
rate of route 0 and of every other route, and the other keys are that
family's parameters (``base_rate_hr``, ``spike_x`` ...), the program's
own keyword names.
"""
from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import numpy as np

DAY_S = 86400.0


def diurnal(base_hr: float, t):
    """Quiet overnight, peaking mid-afternoon: the shape every family
    starts from."""
    h = (t / 3600.0) % 24.0
    return base_hr * (0.55 + 0.45 * np.sin((h - 9.0) * np.pi / 12.0))


# --------------------------------------------------------------------------
# Sampling: one seeded day, route by route.
# --------------------------------------------------------------------------

def route_plan(seed: int, n_routes: int, ckpt_gb: Sequence[float]
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-route (child seed, checkpoint GB), drawn once from the seed's
    master stream so each route regenerates alike in any order."""
    rng = np.random.default_rng(int(seed))
    seeds = rng.integers(0, 2 ** 31 - 1, size=n_routes)
    lo, hi = ckpt_gb
    ckpt = np.round(rng.uniform(float(lo), float(hi), size=n_routes), 1)
    return seeds, ckpt


def _thinned(rng, rate_hr, rate_max_hr: float, horizon_s: float):
    if rate_max_hr <= 0.0:
        return np.empty(0, dtype=np.float64)
    n = rng.poisson(rate_max_hr * horizon_s / 3600.0)
    t = np.sort(rng.uniform(0.0, horizon_s, size=n))
    keep = rng.uniform(0.0, rate_max_hr, size=n) < rate_hr(t)
    return t[keep]


def day_routes(seed: int, config: dict, traffic: dict, family
               ) -> List[Tuple[str, np.ndarray, float]]:
    """One seeded day: [(route id, sorted arrivals, checkpoint GB)];
    ``family`` is the module of the traffic's generator."""
    n = int(config["n_routes"])
    horizon = float(config["horizon_s"])
    seeds, ckpt = route_plan(seed, n, config["checkpoint_gb"])
    (f0, m0), (f1, m1) = family.rates(traffic)
    out = []
    for i in range(n):
        fn, rmax = (f0, m0) if i == 0 else (f1, m1)
        rng = np.random.default_rng(int(seeds[i]))
        out.append((f"r{i}", _thinned(rng, fn, rmax, horizon),
                    float(ckpt[i])))
    return out


# --------------------------------------------------------------------------
# Grid carbon intensity: periodic piecewise-linear knots.
# --------------------------------------------------------------------------

def _solar_duck(swing: float = 0.45):
    def shape(h: float) -> float:
        belly = math.exp(-((h - 13.0) / 3.0) ** 2)
        ramp = math.exp(-((h - 20.0) / 2.0) ** 2)
        return 1.0 - swing * belly + 0.6 * swing * ramp
    return shape


def _wind_night(swing: float = 0.35):
    def shape(h: float) -> float:
        return 1.0 + swing * math.cos(2.0 * math.pi * (h - 14.0) / 24.0)
    return shape


SHAPES = {"solar-duck": _solar_duck, "wind-night": _wind_night}


def carbon_points(shape: str, mean_kg_per_kwh: float, knots: int = 48,
                  period_s: float = DAY_S) -> Tuple[Tuple[float, float], ...]:
    """Knots ((t_s, kg/kWh), ...) of a named diurnal shape over one
    period, scaled so the daily mean of the periodic piecewise-linear
    curve is ``mean_kg_per_kwh``; ``flat`` is one knot."""
    if shape == "flat":
        return ((0.0, float(mean_kg_per_kwh)),)
    fn = SHAPES[shape]()
    pts = [(24.0 * k / knots * 3600.0, max(fn(24.0 * k / knots), 1e-6))
           for k in range(knots)]
    ts = [t for t, _ in pts] + [period_s]
    vs = [v for _, v in pts] + [pts[0][1]]
    area = sum((ts[i] - ts[i - 1]) * (vs[i] + vs[i - 1]) / 2.0
               for i in range(1, len(ts)))
    k = mean_kg_per_kwh / (area / period_s)
    return tuple((t, v * k) for t, v in pts)
