"""Phase spans and the compile counter of the mega simulator
(``fleet/mega/spans.py``).

* the recorder nests spans, links parents, and closes what is left
  open, phase by phase and on an error;
* recorders of concurrent threads never mix;
* ``phase_timings`` splits the bulk phases by construction:
  ``mega.prepare`` + ``mega.finalize`` = ``bulk_host_s`` +
  ``bulk_call_s`` + ``compile_s``, and ``bulk_scan_s`` exceeds that by
  the run claims made inside the event loop;
* each lowering is counted under the span it happened in, as many as
  the compiled backend's jit caches grew by; a persistent-cache load is
  counted as ``cache_loads``; a day of a new size whose inputs fall in
  the buckets of one already run lowers nothing;
* every span reaches the profiler's trace, and the numpy backend
  records its spans with ``jax`` not importable;
* every load starts from one counted cold placement (``place.calls``),
  and a fleet with no room for the next model reaches the masked scan
  (``place.scans``).
"""
import contextlib
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.core.scheduler import Breakeven
from repro.fleet import flash_crowd, mixed_fleet_scenario, run_mega
from repro.fleet.mega import megasim, spans

from conftest import PIN_SEED, REL

PHASES = ("mega.scenario", "mega.event_loop", "mega.finalize",
          "mega.report")


def _recorded(monkeypatch, sc, backend):
    """``run_mega``'s result and the recorder it filled."""
    got = {}
    real = megasim.recording

    @contextlib.contextmanager
    def keep(root):
        with real(root) as rec:
            got["rec"] = rec
            yield rec

    monkeypatch.setattr(megasim, "recording", keep)
    res = run_mega(sc, backend=backend, compute_bound=False)
    return res, got["rec"]


def _self_s(rec, i):
    return rec.spans[i].wall - sum(s.wall for s in rec.spans
                                   if s.parent == i)


def test_nesting_parents_and_self_time():
    with spans.recording("root") as rec:
        rec.phase("a")
        with spans.span("a.call"):
            pass
        rec.phase("b")
        with spans.span("b.inner"):
            rec.open("left-open")          # closed with its phase
        rec.phase(None)
        with spans.span("tail"):
            pass
    names = [s.name for s in rec.spans]
    assert names == ["root", "a", "a.call", "b", "b.inner", "left-open",
                     "tail"]
    parent = {s.name: (names[s.parent] if s.parent >= 0 else None)
              for s in rec.spans}
    assert parent == {"root": None, "a": "root", "a.call": "a",
                      "b": "root", "b.inner": "b", "left-open": "b.inner",
                      "tail": "root"}
    for i, s in enumerate(rec.spans):
        assert s.end >= s.start
        assert _self_s(rec, i) >= 0.0
        if s.parent >= 0:
            p = rec.spans[s.parent]
            assert p.start <= s.start and s.end <= p.end
    assert rec.wall("root") == pytest.approx(
        _self_s(rec, 0) + rec.wall("a") + rec.wall("b") + rec.wall("tail"))
    assert not rec._open


def test_spans_close_on_error_and_do_nothing_without_a_recorder():
    with spans.span("orphan"):              # no recorder: a no-op
        pass
    with pytest.raises(KeyError):
        with spans.recording("root") as rec:
            rec.phase("p")
            with spans.span("p.call"):
                raise KeyError("boom")
    assert [s.name for s in rec.spans] == ["root", "p", "p.call"]
    assert not rec._open
    assert spans._current.get() is None


def test_recorders_of_threads_do_not_mix():
    recs, errors = {}, []
    barrier = threading.Barrier(8)

    def work(k):
        try:
            with spans.recording(f"root{k}") as rec:
                barrier.wait(timeout=10)
                for j in range(50):
                    with spans.span(f"t{k}.{j}"):
                        pass
            recs[k] = rec
        except Exception as exc:           # pragma: no cover - reported
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors and len(recs) == 8
    for k, rec in recs.items():
        assert [s.name for s in rec.spans] == \
            [f"root{k}"] + [f"t{k}.{j}" for j in range(50)]
        assert all(s.parent == 0 for s in rec.spans[1:])


def _service_day():
    """A flash-crowd day whose requests take roofline service time in
    two decode slots a replica."""
    from repro.serving.service_model import (RequestShape,
                                             RooflineServiceTime)
    return flash_crowd(n_routes=6, fleet="h100+a100+l40s", seed=7,
                       horizon_s=2 * 3600.0, base_rate_hr=300.0,
                       spike_x=10.0, spike_start_s=1800.0).to_scenario(
        Breakeven, service_model=RooflineServiceTime(
            RequestShape(1024, 256)), max_batch=2)


@pytest.mark.parametrize("day", ["zero-service", "service"])
def test_bulk_split_is_exact_by_construction(monkeypatch, day):
    sc = (mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED)
          if day == "zero-service" else _service_day())
    res, rec = _recorded(monkeypatch, sc, "jax")
    pt = res.phase_timings
    calls = [s for s in rec.spans if s.name.endswith(".call")]
    # one metering call a day; the service day takes no big-gap tables
    # and makes no billing records
    assert {s.name for s in calls} == (
        {"mega.nextbig.call", "mega.billing.call", "mega.meter.call"}
        if day == "zero-service" else {"mega.meter.call"})
    # every compile of the run happened inside a compiled call
    assert sum(s.compile_s for s in calls) == pytest.approx(
        pt["compile_s"], rel=1e-12, abs=1e-12)
    bulk = pt["bulk_host_s"] + pt["bulk_call_s"] + pt["compile_s"]
    assert rec.wall("mega.prepare") + rec.wall("mega.finalize") == \
        pytest.approx(bulk, rel=1e-9, abs=1e-9)
    # the metering call is energy_s, the rest of mega.meter carbon_s
    assert pt["energy_s"] == rec.wall("mega.meter.call")
    assert pt["energy_s"] + pt["carbon_s"] == pytest.approx(
        rec.wall("mega.meter"), rel=1e-12, abs=1e-12)
    # bulk_scan_s also holds the run claims made inside the event loop
    assert pt["bulk_scan_s"] - (rec.wall("mega.prepare")
                                + rec.wall("mega.billing")
                                + rec.wall("mega.meter")) >= -1e-9
    if day == "zero-service":
        # ... which outweigh what mega.finalize does outside its spans
        assert pt["bulk_scan_s"] - bulk >= -1e-9
    assert pt["bulk_scan_s"] == pytest.approx(
        pt["biggap_s"] + pt["billing_s"] + pt["energy_s"]
        + pt["carbon_s"], rel=1e-12)
    assert pt["event_loop_s"] < pt["run_s"]
    assert pt["scenario_s"] + pt["event_loop_s"] + pt["report_s"] \
        + rec.wall("mega.prepare") + rec.wall("mega.finalize") \
        <= pt["run_s"] + 1e-9
    assert all(v >= 0.0 for v in pt.values())
    root = [s.name for s in rec.spans if s.parent == 0]
    assert root == ["mega.scenario"] + (
        ["mega.prepare"] if day == "zero-service" else []) + [
        "mega.event_loop", "mega.finalize", "mega.report"]


def test_compiles_counted_where_they_happen():
    from repro.fleet.mega import jaxback
    # other tests may have run this day's buckets: drop the billing
    # gather's programs so it lowers afresh
    tr = flash_crowd(n_routes=5, fleet="h100+a100+l40s", seed=4242,
                     horizon_s=5 * 3600.0)
    jaxback._bill_gather.clear_cache()
    before = jaxback.compiled_program_count()
    res = run_mega(tr.to_scenario(Breakeven), backend="jax",
                   compute_bound=False)
    grown = jaxback.compiled_program_count() - before
    lowered = {k: v for k, v in res.counters.items()
               if k.startswith("compiles.")}
    assert lowered.get("compiles.mega.billing.call", 0) >= 1
    assert sum(lowered.values()) == grown
    assert all(k.endswith(".call") for k in lowered)
    assert res.phase_timings["compile_s"] > 0.0
    # the same day again lowers nothing
    again = run_mega(tr.to_scenario(Breakeven), backend="jax",
                     compute_bound=False)
    assert not any(k.startswith("compiles.") for k in again.counters)
    assert again.phase_timings["compile_s"] == 0.0


def test_a_new_day_size_in_the_same_buckets_compiles_nothing():
    from test_mega import _assert_backends_match
    # 735 and 658 arrivals: one 1024-entry bucket, and their billing
    # records, waits, charge logs, streams and hourly bins share theirs
    days = [flash_crowd(n_routes=4, fleet="h100+a100+l40s", seed=seed,
                        horizon_s=12 * 3600.0) for seed in (4242, 4243)]
    assert days[0].requests != days[1].requests
    run_mega(days[0].to_scenario(Breakeven), backend="jax",
             compute_bound=False)
    got = run_mega(days[1].to_scenario(Breakeven), backend="jax",
                   compute_bound=False)
    assert "compiles.mega.billing.call" not in got.counters
    assert not any(k.startswith("compiles.") for k in got.counters)
    assert got.phase_timings["compile_s"] == 0.0
    ref = run_mega(days[1].to_scenario(Breakeven), backend="numpy",
                   compute_bound=False)
    _assert_backends_match(ref, got)
    assert np.array_equal(np.asarray(ref.latencies_s),
                          np.asarray(got.latencies_s))
    assert got.cost_usd == pytest.approx(ref.cost_usd, rel=REL)


def test_compiles_outside_a_recorder_are_not_counted():
    import jax
    with spans.recording("root") as rec:
        with spans.span("inside"):
            jax.jit(lambda x: x * 3.0 + 1.0)(np.ones(7, np.float32))
    jax.jit(lambda x: x * 5.0 - 2.0)(np.ones(9, np.float32))
    assert rec.counters.get("compiles.inside") == 1
    assert sum(v for k, v in rec.counters.items()
               if k.startswith("compiles.")) == 1
    assert rec.compile_s > 0.0
    assert rec.spans[1].compile_s == rec.compile_s


def test_cache_load_counted(tmp_path):
    script = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        from repro.fleet.mega import spans
        f = jax.jit(lambda x: jnp.cumsum(x) * 2.0)
        x = jnp.ones(11)
        counts = []
        for _ in range(2):
            jax.clear_caches()
            with spans.recording("root") as rec:
                with spans.span("step"):
                    f(x).block_until_ready()
            counts.append((rec.counters.get("compiles.step", 0),
                           rec.counters.get("cache_loads", 0)))
        print(counts)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")]
        + [p for p in [env.get("PYTHONPATH")] if p])
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    # a fresh compile, then the same program loaded from the cache:
    # both lower once
    assert out.stdout.strip().splitlines()[-1] == "[(1, 0), (1, 1)]"


def test_spans_reach_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData
    sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED,
                              horizon_s=6 * 3600.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_mega(sc, backend="jax", compute_bound=False)
    finally:
        jax.profiler.stop_trace()
    found = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    assert found
    names = {e.name for p in ProfileData.from_file(found[0]).planes
             if p.name.startswith("/host:")
             for ln in p.lines for e in ln.events}
    assert {"mega.run", "mega.prepare", "mega.nextbig.call",
            "mega.meter.call", *PHASES} <= names


def test_numpy_backend_records_spans_without_jax(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)    # import -> error
    sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED,
                              horizon_s=6 * 3600.0)
    res, rec = _recorded(monkeypatch, sc, "numpy")
    names = [s.name for s in rec.spans]
    assert names[0] == "mega.run"
    assert [s.name for s in rec.spans if s.parent == 0] == list(PHASES)
    assert "mega.prepare" not in names
    assert not any(n.endswith(".call") for n in names)
    pt = res.phase_timings
    # nothing lowered or loaded without jax (the placement counters are
    # backend-blind)
    assert not any(k.startswith("compiles.") for k in res.counters)
    assert "cache_loads" not in res.counters
    assert pt["compile_s"] == 0.0 and pt["bulk_call_s"] == 0.0
    assert pt["bulk_host_s"] == pytest.approx(rec.wall("mega.finalize"))
    assert pt["bulk_scan_s"] >= pt["carbon_s"] > 0.0


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_service_counters_and_serve_s(monkeypatch, backend):
    """A day whose requests take service time counts every admission and
    completion, counts as slot waits only admissions that had waited,
    and books its service path in ``serve_s`` -- with no span per
    event: two days of different sizes open the same spans."""
    from repro.serving.service_model import (RequestShape,
                                             RooflineServiceTime)
    svc = RooflineServiceTime(RequestShape(1024, 256))
    runs = []
    for seed, rate in ((7, 300.0), (8, 900.0)):
        tr = flash_crowd(n_routes=6, fleet="h100+a100+l40s", seed=seed,
                         horizon_s=2 * 3600.0, base_rate_hr=rate,
                         spike_x=10.0, spike_start_s=1800.0)
        runs.append(_recorded(monkeypatch, tr.to_scenario(
            Breakeven, service_model=svc, max_batch=2), backend))
    (small, rec_small), (big, rec_big) = runs
    assert big.requests > 2 * small.requests
    for res in (small, big):
        ct, pt = res.counters, res.phase_timings
        waits = int((np.asarray(res.latencies_s) > 0.0).sum())
        assert ct["serve.admissions"] == res.requests
        assert ct["serve.completions"] == res.requests
        assert 0 < ct["serve.slot_waits"] <= waits
        assert 0.0 <= pt["serve_s"] <= pt["event_loop_s"]
    assert [s.name for s in rec_small.spans] == \
        [s.name for s in rec_big.spans]
    assert "mega.prepare" not in [s.name for s in rec_big.spans]


def _prewarmed(res) -> int:
    """Replicas resident at t=0 (the warm-start convention)."""
    return sum(log[0][1] for log in res.replica_timeline.values()
               if log and log[0][0] == 0.0)


@pytest.mark.parametrize("day", ["zero-service", "service"])
def test_placement_counters(day):
    """Every load starts from one cold placement; a deduplicated or raced
    load adds a placement but no cold start.  On the pinned zero-service
    day each placement is a load.  The heap's top answers every
    placement there, so no scan is counted."""
    from repro.serving.service_model import (RequestShape,
                                             RooflineServiceTime)
    if day == "zero-service":
        sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED,
                                  horizon_s=6 * 3600.0)
    else:
        sc = flash_crowd(n_routes=6, fleet="h100+a100+l40s", seed=7,
                         horizon_s=2 * 3600.0, base_rate_hr=300.0,
                         spike_x=10.0, spike_start_s=1800.0).to_scenario(
            Breakeven, service_model=RooflineServiceTime(
                RequestShape(1024, 256)), max_batch=2)
    res = run_mega(sc, compute_bound=False)
    ct = res.counters
    loads = res.cold_starts - _prewarmed(res)
    assert 0 < loads <= ct["place.calls"]
    assert 0 <= ct["place.scans"] <= ct["place.calls"]
    if day == "zero-service":
        assert loads == ct["place.calls"]
        assert ct["place.scans"] == 0


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_placement_falls_back_to_the_scan_on_a_full_fleet(backend):
    """Ten models of 5-36.5 GB on one 48 GB, six-slot L40S: the device is
    often out of slots or VRAM for the next model, so placements reach
    the scan, and the day still matches the event loop."""
    from repro.fleet import run_fleet
    sc = mixed_fleet_scenario(Breakeven, "warm-first", seed=PIN_SEED,
                              fleet="1xl40s", horizon_s=6 * 3600.0)
    got = run_mega(sc, compute_bound=False, backend=backend)
    ref = run_fleet(mixed_fleet_scenario(Breakeven, "warm-first",
                                         seed=PIN_SEED, fleet="1xl40s",
                                         horizon_s=6 * 3600.0))
    assert 0 < got.counters["place.scans"] <= got.counters["place.calls"]
    assert got.cold_starts - _prewarmed(got) <= got.counters["place.calls"]
    assert (got.requests, got.cold_starts) == (ref.requests, ref.cold_starts)
    assert got.energy_wh == pytest.approx(ref.energy_wh, rel=REL)
