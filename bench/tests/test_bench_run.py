"""The command line refuses a machine without a TPU, and a cell,
configuration, traffic mix and per-layer metric added as new files only
are found by name."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run, testing  # noqa: E402


def test_cli_exits_nonzero_with_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "fleet600.flash-day", "--seed", "2147483651",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout
    assert "TPU" in p.stderr


def test_cli_exits_nonzero_for_an_unknown_cell():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "no.such-cell", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and "metrics" not in p.stdout


def test_window_pays_each_new_day_size_and_caches_none(tmp_path):
    """Poisson days differ in size and the program compiles its billing
    gather per size: the window counts those compiles and writes none of
    them to the persistent cache."""
    import jax
    root = testing.tiny_root(str(tmp_path))
    out = run.run(["--workload", "fleet600.flash-day", "--seed",
                   "2147483659", "--seconds", "1.5", "--trace", "1"],
                  require_tpu=False, root=root)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2
    assert out["metrics"]["compiles.sim"]["value"] >= out["attempted"]
    assert jax.config.jax_persistent_cache_min_compile_time_secs >= 1e9


def _snapshot(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[path] = fh.read()
    return out


KIND = """import os
from bench import manifest
_day = manifest.job_kind("day", os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
NUMBERS, gaps, reference = _day.NUMBERS, _day.gaps, _day.reference
inputs, call, summarize = _day.inputs, _day.call, _day.summarize
record, traced = _day.record, _day.traced


def end_to_end(jobs, measured_s):
    return {"sim_days_per_s": len(jobs) / measured_s}
"""

FAMILY = """from bench.gen import diurnal


def rates(p):
    base = float(p["base_rate_hr"])
    return [(lambda t: diurnal(base, t), base)] * 2
"""


def test_added_files_are_found_without_editing_any(tmp_path):
    """A configuration, a traffic mix with a generator family and a job
    kind of its own, an end-to-end metric and a per-layer metric, added
    as new files and new BENCHMARK.json entries."""
    root = testing.tiny_root(str(tmp_path))
    before = _snapshot(root)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "fleet600-3sku.json")) as fh:
        cfg = json.load(fh)
    cfg.update(name="fleet4-2sku", fleet="2xh100+2xl40s", n_routes=8)
    with open(os.path.join(b, "configs", "fleet4-2sku.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(b, "families", "diurnal-only.py"), "w") as fh:
        fh.write(FAMILY)
    with open(os.path.join(b, "kinds", "day-count.py"), "w") as fh:
        fh.write(KIND)
    with open(os.path.join(b, "traffic", "steady-day.json"), "w") as fh:
        json.dump({"job": "day-count", "generator": "diurnal-only",
                   "base_rate_hr": 30.0}, fh)
    shutil.copy(os.path.join(b, "limits", "fleet600.flash-day.json"),
                os.path.join(b, "limits", "fleet4.steady-day.json"))
    with open(os.path.join(b, "metrics", "jobs.count.sim.py"), "w") as fh:
        fh.write("def read(rec):\n    return len(rec['jobs'])\n")
    man_path = os.path.join(root, "BENCHMARK.json")
    with open(man_path) as fh:
        man = json.load(fh)
    man["configs"].append({"name": "fleet4-2sku", "source": "test",
                           "file": "bench/configs/fleet4-2sku.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "fleet4.steady-day",
                             "config": "fleet4-2sku",
                             "traffic": "steady-day", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("fleet4.steady-day")
    man["end_to_end"].append({"name": "sim_days_per_s", "unit": "days/s",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": ["fleet4.steady-day"]})
    man["per_layer"].append({"name": "jobs.count.sim", "unit": "count",
                             "better": "higher", "source": "host_clock",
                             "layer": "harness", "moves": "sim_days_per_s",
                             "workloads": ["fleet4.steady-day"]})
    with open(man_path, "w") as fh:
        json.dump(man, fh)
    after = _snapshot(root)
    assert all(after[p] == v for p, v in before.items())

    args = ["--workload", "fleet4.steady-day", "--seed", "77", "--seconds",
            "0.01", "--trace"]
    out = run.run(args + ["0"], require_tpu=False, root=root)
    assert out["correct"], out["checks"]
    assert out["metrics"]["sim_days_per_s"]["value"] > 0
    assert "setup_s" in out["metrics"]
    out = run.run(args + ["1"], require_tpu=False, root=root)
    assert out["metrics"]["jobs.count.sim"]["value"] >= 1
    assert "host_loop.us_per_req.sim" not in out["metrics"]
