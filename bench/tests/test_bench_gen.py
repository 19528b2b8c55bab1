"""The benchmark's copies of the traffic and carbon generators: pinned
counts, and the same days as the program's own generators."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import gen, manifest, run  # noqa: E402

MAN = manifest.load()
FLASH = manifest.family("flash-crowd")
OUTAGE = manifest.family("regional-outage")


def _cfg(n_routes):
    """The 600-GPU configuration, with ``n_routes`` routes."""
    return dict(manifest.config(MAN, "fleet600-3sku"), n_routes=n_routes)


@pytest.mark.parametrize("n_routes,rate,seed,total,route0", [
    (600, 130.0, 5, 1033369, 5154),
    (600, 130.0, 3000000017, 1032653, 5126),
    (6, 40.0, 5, 4187, 1566),
    (6, 40.0, 3000000017, 4226, 1570),
])
def test_day_request_counts_are_pinned(n_routes, rate, seed, total, route0):
    routes = gen.day_routes(seed, _cfg(n_routes),
                            {"generator": "flash-crowd",
                             "base_rate_hr": rate}, FLASH)
    assert sum(len(a) for _, a, _ in routes) == total
    assert len(routes[0][1]) == route0


@pytest.mark.parametrize("seed", [5, 3000000017])
def test_day_sizes_vary_with_the_seed(seed):
    """Poisson days: no two seeds are held to one size."""
    traffic = {"generator": "flash-crowd", "base_rate_hr": 40.0}
    sizes = {sum(len(a) for _, a, _ in
                 gen.day_routes(seed + k, _cfg(6), traffic, FLASH))
             for k in range(4)}
    assert len(sizes) > 1
    routes = gen.day_routes(seed, _cfg(6), traffic, FLASH)
    for _, a, _ in routes:
        assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 86400.0


def test_day_matches_the_program_generator():
    from repro.fleet.mega.traces import flash_crowd
    cfg = dict(_cfg(6), fleet="2xh100+2xa100+2xl40s")
    mine = gen.day_routes(41, cfg, {"generator": "flash-crowd",
                                    "base_rate_hr": 40.0}, FLASH)
    theirs = flash_crowd(n_routes=cfg["n_routes"], fleet=cfg["fleet"],
                         seed=41, base_rate_hr=40.0)
    for (rid, a, c), r in zip(mine, theirs.routes):
        assert rid == r.route_id and c == r.checkpoint_gb
        np.testing.assert_array_equal(a, r.arrivals_s)


def test_outage_family_matches_the_program_generator():
    from repro.fleet.mega.traces import regional_outage
    mine = gen.day_routes(9, _cfg(4), {"generator": "regional-outage",
                                       "base_rate_hr": 60.0}, OUTAGE)
    theirs = regional_outage(n_routes=4, seed=9, base_rate_hr=60.0)
    for (_, a, _), r in zip(mine, theirs.routes):
        np.testing.assert_array_equal(a, r.arrivals_s)


@pytest.mark.parametrize("shape", ["solar-duck", "wind-night", "flat"])
def test_carbon_knots_match_the_program_trace(shape):
    from repro.fleet.carbon import make_trace
    mine = gen.carbon_points(shape, 0.39)
    theirs = make_trace(shape, 0.39).points
    assert len(mine) == len(theirs)
    np.testing.assert_allclose(np.array(mine), np.array(theirs),
                               rtol=1e-14, atol=0)


def test_job_seeds_differ_between_warmup_and_window():
    warm = set(run.job_seeds(2 ** 31 + 5, "warmup", 0))
    win = {s for i in range(200)
           for s in run.job_seeds(2 ** 31 + 5, "window", i)}
    assert not warm & win
    assert run.job_seeds(3, "window", 1) == run.job_seeds(3, "window", 1)


def test_warmup_seeds_are_the_same_for_every_run_seed():
    """One warm-up day for every run, so its programs are cached after
    a cell's first run; window seeds follow ``--seed``."""
    assert run.job_seeds(7, "warmup", 0) == \
        run.job_seeds(2 ** 33 + 1, "warmup", 0)
    assert run.job_seeds(7, "window", 0) != run.job_seeds(8, "window", 0)
