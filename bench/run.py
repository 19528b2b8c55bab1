"""Benchmark of the fleet simulator on the accelerator: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, from the root of a checkout, on a machine that holds the
chips the cell asks for.  It

  1. checks the device: anything but enough TPU chips exits non-zero
     and prints no result;
  2. keeps JAX's compilation cache inside the checkout (through the
     program's ``use_compile_cache``);
  3. warms up (set-up): one job on warm-up seeds that are the same for
     every ``--seed`` and never a window's, so that every program it
     compiles or loads is in the cache after a cell's first run;
  4. measures whole jobs back to back, each on fresh seeds derived from
     ``--seed`` and its index, until the first job that ends after
     ``--seconds`` of measured time; inputs the benchmark generates are
     made between jobs, off the clock.  A program compiled inside the
     window (the simulator compiles one for each new day size) is not
     written to the persistent cache, so a run that repeats a seed pays
     what a run on a fresh seed pays;
  5. with ``--trace 1``, profiles the window's first job and reports the
     per-layer metrics instead of the end-to-end ones;
  6. reads peak device memory, frees the program's results, and holds a
     sample of the window's jobs, drawn from the seed, to the plain
     reference (``bench/reference.py``);
  7. prints each compared number beside its limit on standard error, and
     as its last line on standard output one JSON object.

What a job is, and what its work and end-to-end values are, is the
job kind's (``bench/kinds/<job>.py``); the harness knows no kind.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import check, manifest, trace_reduce  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def device_info(chips: int) -> dict:
    """The devices JAX sees; exits non-zero unless they are TPU chips,
    at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"bench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s) "
            f"({devs[0].device_kind}); there is no fallback")
        raise SystemExit(3)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_lowered = {"n": 0, "listening": False}


def _on_event(event, duration, **kw):
    if event == LOWERED_EVENT:
        _lowered["n"] += 1


def lowered() -> int:
    """Programs JAX has lowered in this process so far: one for every
    jit cache miss, whether it then compiles or loads from the
    persistent cache."""
    return _lowered["n"]


def use_cache() -> None:
    """The program's compile cache, with every program cached, and the
    count of programs lowered."""
    import jax
    from repro.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    persist_compiles(True)
    if not _lowered["listening"]:
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _lowered["listening"] = True


def persist_compiles(on: bool) -> None:
    """Write every program compiled from now on to the persistent cache
    (``on``), or none: JAX skips the write of a program that compiled
    faster than the threshold."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.0 if on else 1e9)


def job_seeds(seed: int, role: str, index: int):
    """The 32-bit seeds (one a day) of job ``index`` of a run's
    ``role``: "window" jobs derive theirs from the run's ``--seed``; the
    "warmup" job's are the same in every run."""
    if role == "warmup":
        ss = np.random.SeedSequence([1, int(index)])
    else:
        ss = np.random.SeedSequence([int(seed) % (1 << 64), 2, int(index)])
    return [int(x) for x in ss.generate_state(1, np.uint32)]


def memory_peak(n: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_job(kind, seeds, c, trace_dir=None):
    """Inputs (off the clock), then the timed call.  Returns (wall s,
    generation s, the program's results)."""
    import jax
    t0 = time.perf_counter()
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.generate"):
            inputs = kind.inputs(seeds, c)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_reduce.CALL_SPAN):
            results = kind.call(inputs)
        t2 = time.perf_counter()
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return t2 - t1, t1 - t0, results


def run(argv=None, require_tpu: bool = True, root: str = ROOT) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    c = manifest.cell(args.workload, root)
    chips = int(c["cell"]["chips"])
    if require_tpu:
        device = device_info(chips)
    else:
        import jax
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
    use_cache()
    kind = c["kind"]

    # ---- set-up: one warm-up job -----------------------------------------
    wall, gen_s, _ = run_job(kind, job_seeds(args.seed, "warmup", 0), c)
    log(f"warm-up job: {wall:.3f} s call, {gen_s:.3f} s inputs")
    setup_s = time.perf_counter() - T0

    # ---- measured window ----------------------------------------------
    persist_compiles(False)
    c0 = lowered()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    window, done, sums, extras = [], 0.0, [], {}
    try:
        while True:
            i = len(window)
            seeds = job_seeds(args.seed, "window", i)
            traced = bool(args.trace and i == 0)
            gc.collect()
            wall, gen_s, results = run_job(kind, seeds, c,
                                           trace_dir if traced else None)
            done += wall
            job_sums = [kind.summarize(r) for r in results]
            if traced:
                extras = kind.traced(results, c)
            del results
            window.append(dict(kind.record(job_sums), seeds=seeds,
                               wall_s=wall, gen_s=gen_s, traced=traced))
            sums.append(job_sums)
            log(f"job {i}: {wall:.3f} s call, {gen_s:.3f} s inputs (off "
                f"the clock), {len(job_sums)} answers, {lowered() - c0} "
                f"lowered so far")
            if done >= args.seconds:
                break
        compiles = lowered() - c0
        mem = memory_peak(chips)
        red = None
        if args.trace:
            red = trace_reduce.reduce_planes(trace_reduce.load(
                trace_reduce.find_xplane(trace_dir)))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    e2e = dict(kind.end_to_end(window, done), setup_s=setup_s)
    log(f"window: {len(window)} jobs, {done:.3f} s measured, "
        f"{sum(j['gen_s'] for j in window):.3f} s of inputs off the clock; "
        f"set-up {setup_s:.3f} s; lowered {compiles}; {e2e}")

    # ---- correctness: one window job, drawn from the seed --------------
    rng = np.random.default_rng([int(args.seed) % (1 << 64), 3])
    i = int(rng.integers(len(window)))
    t_ref = time.perf_counter()
    refs = kind.reference(window[i]["seeds"], c)
    readings = [kind.gaps(p, r) for p, r in zip(sums[i], refs)]
    if len(refs) != len(sums[i]) or not readings:
        readings.append({k: float("inf") for k in kind.NUMBERS})
    table = check.verdict(check.worst(readings, kind.NUMBERS), c["limits"],
                          kind.NUMBERS)
    correct = check.passed(table)
    log(f"reference: job {i}, {len(refs)} answers, "
        f"{time.perf_counter() - t_ref:.3f} s")

    # ---- metrics --------------------------------------------------------
    man = c["manifest"]
    metrics = {}
    if args.trace:
        untraced = [j for j in window if not j["traced"]]
        rec = dict(extras, jobs=untraced or window, compiles=compiles,
                   trace=red, peaks=(manifest.peaks(device["kind"], root)
                                     if require_tpu else None))
        for m in manifest.cell_metrics(man, args.workload, "per_layer"):
            v = manifest.metric_reader(m["name"], root)(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in manifest.cell_metrics(man, args.workload, "end_to_end"):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=mem)
    out = {"correct": bool(correct), "attempted": len(window),
           "failed": 0, "metrics": metrics, "device": device}
    if args.trace:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = trace_reduce.breakdown(red)
    out["checks"] = table
    for k, (v, lim) in table.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    return out


def main() -> int:
    out = run()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
