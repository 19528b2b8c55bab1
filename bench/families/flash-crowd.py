"""Flash crowd: every route on the diurnal shape, route 0 spiking
``spike_x`` times for ``spike_width_s`` from ``spike_start_s`` and then
cooling down exponentially (``repro.fleet.mega.traces.flash_crowd``)."""
import numpy as np

from bench.gen import diurnal


def rates(p):
    """[(rate_fn, rate_max)] for route 0 and for every other route."""
    base = float(p["base_rate_hr"])
    x = float(p.get("spike_x", 40.0))
    start = float(p.get("spike_start_s", 13 * 3600.0))
    width = float(p.get("spike_width_s", 1800.0))
    tail = 2.0 * width

    def spiked(t):
        r = diurnal(base, t)
        dt = t - start
        hot = (dt >= 0.0) & (dt < width)
        cool = (dt >= width) & (dt < width + tail)
        boost = np.where(hot, x, 0.0) + np.where(
            cool, x * np.exp(-(dt - width) / (0.35 * width)), 0.0)
        return r * (1.0 + boost)

    return [(spiked, base * (1.0 + x)),
            (lambda t: diurnal(base, t), base)]
