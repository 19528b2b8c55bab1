"""The reduction from a device trace to busy time, idle share, kernel
time and roofline share, on hand-built traces."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import manifest, trace_reduce  # noqa: E402

MS = 1e6   # ns


def _trace(ops, host_extra=()):
    """A traced job, inputs over [0, 10] ms and the call over [10, 100]
    ms, with device ops [(name, start ms, len ms)]."""
    host = [("bench.generate", 0.0, 10 * MS), ("bench.call", 10 * MS, 90 * MS)]
    host += list(host_extra)
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [
                ("XLA Modules", [("jit_step", 0.0, 100 * MS)]),
                ("XLA Ops", [(n, s * MS, d * MS) for n, s, d in ops])])]


def test_busy_is_the_union_of_overlapping_ops():
    red = trace_reduce.reduce_planes(_trace(
        [("a", 10, 20), ("b", 20, 20), ("c", 60, 10)]))
    assert red["busy_s"] == pytest.approx(0.040)    # [10,40] + [60,70]
    assert red["window_s"] == pytest.approx(0.090)
    assert red["ops"]["b"] == pytest.approx(0.020)


def test_ops_outside_the_call_do_not_count_as_busy():
    red = trace_reduce.reduce_planes(_trace([("a", -50, 60), ("b", 95, 20)]))
    assert red["busy_s"] == pytest.approx(0.005)    # [95,100]


def test_idle_share_and_gap_attribution():
    spans = [("PjitFunction(_meter_fused)", 70 * MS, 5 * MS),
             ("np.asarray(jax.Array)", 92 * MS, 6 * MS)]
    red = trace_reduce.reduce_planes(_trace([("a", 10, 20), ("k", 80, 10)],
                                            spans))
    read = manifest.metric_reader("device.idle_pct.sim")
    assert read({"trace": red}) == pytest.approx(100.0 * (1.0 - 30 / 90))
    # [30,80] is the longest; its middle (55 ms) lies in bench.call only;
    # the middle of [90,100] lies in the innermost np.asarray span
    got = sorted((n, round(s, 6)) for n, s in red["gaps"])
    assert got == [("bench.call", 0.05), ("np.asarray(jax.Array)", 0.01)]
    assert red["gaps"][0][0] == "bench.call"
    bd = trace_reduce.breakdown(red)
    assert bd["device_ops"][0][0] in ("a",) and len(bd["idle_gaps"]) <= 10


def test_a_trace_without_the_call_span_is_refused():
    planes = [("/device:TPU:0", [("XLA Ops", [("a", 0.0, 1.0)])])]
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(planes)


def test_roofline_counts_needed_work_and_reads_kernel_events():
    read = manifest.metric_reader("fused_meter_roofline")
    period = 86400.0
    points = [(3600.0 * h, 0.3 + 0.01 * h) for h in range(24)]
    # two segments: one inside a knot interval, one over three intervals
    segs = np.array([[100.0, 200.0], [3500.0, 3600.0 * 3 + 10]])
    ops = {"%fused_meter.1 = f32[8192,128]{1,0} custom-call(f32[127] %a)":
           1e-6,
           "%fusion.3 = f32[8] fusion(f32[8] %fused_meter.1), kind=kLoop":
           5.0}
    pk = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    rec = {"trace": {"ops": ops}, "traced_segments": [segs],
           "carbon_points": points, "carbon_period_s": period, "peaks": pk}
    flops = 12 * (1 + 4) + 2 * 2          # overlaps: 1, then [0-1],...,[3-4]
    nbytes = 20 * 2
    least = max(flops / 1e12, nbytes / 1e9)
    assert read(rec) == pytest.approx(100.0 * least / 1e-6)


def test_roofline_reads_nothing_without_kernel_events():
    read = manifest.metric_reader("fused_meter_roofline")
    rec = {"trace": {"ops": {"fusion": 1.0}},
           "traced_segments": [np.zeros((1, 2))], "carbon_points": [(0, 1)],
           "carbon_period_s": 86400.0, "peaks": {}}
    assert read(rec) is None
    assert read({"trace": None}) is None


def test_span_metrics_over_untraced_jobs():
    jobs = [{"wall_s": 10.0, "bulk_s": 1.0, "requests": 1000},
            {"wall_s": 12.0, "bulk_s": 1.2, "requests": 1100}]
    host = manifest.metric_reader("host_loop.us_per_req.sim")
    share = manifest.metric_reader("bulk.wall_share.sim")
    assert host({"jobs": jobs}) == pytest.approx((22.0 - 2.2) / 2100 * 1e6)
    assert share({"jobs": jobs}) == pytest.approx(10.0)
    assert manifest.metric_reader("compiles.sim")({"compiles": 0}) == 0
