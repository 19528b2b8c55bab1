"""The serving cell's plain reference (``bench/reference_serve.py``)
agrees with the program's own event loop, and ``correct`` comes out
false when the program drops service time or runs one decode slot
short; at a size the CPU holds (six devices, 12 routes, 6 h days)."""
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import manifest, run, testing  # noqa: E402

CELL = "serve60.flash-day"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testing.tiny_root(str(tmp_path_factory.mktemp("bench")))


def test_reference_agrees_with_run_fleet(root):
    from repro.fleet import run_fleet
    c = manifest.cell(CELL, root)
    kind = c["kind"]
    seeds = run.job_seeds(11, "window", 0)
    sc = kind.inputs(seeds, c)[0]
    want = run_fleet(sc, compute_bound=False)
    got = kind.reference(seeds, c)[0]
    lat = np.asarray(want.latencies_s)
    assert got["requests"] == want.requests
    assert got["cold_starts"] == want.cold_starts
    assert len(got["waits"]) == int((lat > 0.0).sum()) > 0
    np.testing.assert_allclose(got["waits"], np.sort(lat[lat > 0.0]),
                               rtol=1e-12)
    assert got["energy_wh"] == pytest.approx(want.energy_wh, rel=1e-12)
    assert got["cost_usd"] == pytest.approx(want.cost_usd, rel=1e-12)
    assert got["carbon_kg"] == pytest.approx(want.carbon_kg, rel=1e-12)
    for r in want.devices:
        assert got["device_energy_wh"][r.instance_id] == pytest.approx(
            r.total_wh, rel=1e-12)
    assert got["slot_waits"] > 0


def _no_service(monkeypatch, kind):
    """Service time dropped where the program prices it: every request
    completes a microsecond after it starts."""
    from repro.serving import service_model

    def table(self, spec, device, max_batch):
        return (1e-6,) * max_batch
    monkeypatch.setattr(service_model.RooflineServiceTime, "table", table)


def _slot_short(monkeypatch, kind):
    """The program runs one decode slot short of the configuration."""
    orig = kind.call

    def call(scenarios):
        return orig([dataclasses.replace(sc, max_batch=sc.max_batch - 1)
                     for sc in scenarios])
    monkeypatch.setattr(kind, "call", call)


@pytest.mark.parametrize("fault", [_no_service, _slot_short])
def test_broken_service_path_is_not_correct(root, fault, monkeypatch):
    fault(monkeypatch, manifest.cell(CELL, root)["kind"])
    out = run.run(["--workload", CELL, "--seed", "3000000023",
                   "--seconds", "0.01", "--trace", "0"],
                  require_tpu=False, root=root)
    assert out["correct"] is False
    failed = {k for k, (v, lim) in out["checks"].items() if not v <= lim}
    assert {"waits", "energy_rel"} & failed
