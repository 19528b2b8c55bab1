"""``correct`` comes out false when the timed path is broken underneath
a run, and the float32 control fails each cell's limits; at a size the
CPU holds (six devices, 12 routes, 6 h days)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, manifest, run, testing  # noqa: E402

CELLS = [w["name"] for w in manifest.load()["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testing.tiny_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, cell, seed=3000000019):
    return run.run(["--workload", cell, "--seed", str(seed), "--seconds",
                    "0.01", "--trace", "0"], require_tpu=False, root=root)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell):
    out = _run(root, cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s", "sim_req_per_s"}


def _stale(monkeypatch, kind):
    """A job that returns its state unchanged: every call hands back the
    first call's results."""
    orig, first = kind.call, []

    def call(inputs):
        if not first:
            first.append(orig(inputs))
        return first[0]
    monkeypatch.setattr(kind, "call", call)


def _half(monkeypatch, kind):
    """Half of the batch left out: a day loses every other route."""
    orig = kind.call

    def call(scenarios):
        scenarios[0].models = scenarios[0].models[::2]
        return orig(scenarios)
    monkeypatch.setattr(kind, "call", call)


def _altered(monkeypatch, kind):
    """An answer altered where it is produced: the metering program's
    per-state joules come back one part in 10^7 high."""
    from repro.fleet.mega import jaxback
    orig = jaxback._meter_fused

    def meter(*a, **kw):
        ej, *rest = orig(*a, **kw)
        return (ej * (1.0 + 1e-7), *rest)
    meter._cache_size = orig._cache_size
    monkeypatch.setattr(jaxback, "_meter_fused", meter)


@pytest.mark.parametrize("fault", [_stale, _half, _altered])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch):
    fault(monkeypatch, manifest.cell(cell, root)["kind"])
    out = _run(root, cell)
    assert out["correct"] is False
    failed = [k for k, (v, lim) in out["checks"].items() if not v <= lim]
    assert failed


@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_fails_the_limits(root, cell):
    c = manifest.cell(cell, root)
    kind = c["kind"]
    seeds = run.job_seeds(5, "window", 0)
    ref = kind.reference(seeds, c)
    ctl = kind.reference(seeds, c, dtype=np.float32)
    table = check.verdict(check.worst([kind.gaps(p, r)
                                       for p, r in zip(ctl, ref)],
                                      kind.NUMBERS),
                          c["limits"], kind.NUMBERS)
    assert not check.passed(table)
    assert table["energy_rel"][0] > table["energy_rel"][1]
