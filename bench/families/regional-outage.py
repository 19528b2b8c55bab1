"""Regional outage: every route on the diurnal shape, dark for
``outage_s`` from ``outage_start_s``, then ``recovery_x`` times its rate
for ``recovery_s`` (``repro.fleet.mega.traces.regional_outage``)."""
import numpy as np

from bench.gen import diurnal


def rates(p):
    """[(rate_fn, rate_max)] for route 0 and for every other route."""
    base = float(p.get("base_rate_hr", 60.0))
    out0 = float(p.get("outage_start_s", 11 * 3600.0))
    out1 = out0 + float(p.get("outage_s", 3600.0))
    rx = float(p.get("recovery_x", 3.0))
    rs = float(p.get("recovery_s", 1800.0))

    def rate(t):
        r = diurnal(base, t)
        dark = (t >= out0) & (t < out1)
        surge = (t >= out1) & (t < out1 + rs)
        return np.where(dark, 0.0, r * np.where(surge, rx, 1.0))

    return [(rate, base * rx), (rate, base * rx)]
