"""Idle gaps named by the simulator's own phase spans, on hand-built
traces shaped like one traced day: ``bench.call`` holds ``mega.run``,
which holds the phases, and the device works only in the compiled
calls."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace_reduce  # noqa: E402

MS = 1e6   # ns


def _day(ops):
    """Inputs over [0, 10] ms, the call over [10, 1000] ms, its phases
    inside; device ops [(name, start ms, len ms)]."""
    host = [("bench.generate", 0.0, 10 * MS),
            ("bench.call", 10 * MS, 990 * MS),
            ("mega.run", 11 * MS, 988 * MS),
            ("mega.scenario", 12 * MS, 8 * MS),
            ("mega.prepare", 20 * MS, 30 * MS),
            ("mega.nextbig.call", 25 * MS, 20 * MS),
            ("mega.event_loop", 50 * MS, 800 * MS),
            ("mega.finalize", 860 * MS, 100 * MS),
            ("mega.meter", 860 * MS, 60 * MS),
            ("mega.meter.call", 880 * MS, 40 * MS),
            ("mega.billing", 920 * MS, 40 * MS),
            ("mega.billing.call", 925 * MS, 35 * MS),
            ("mega.report", 960 * MS, 38 * MS)]
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [
                ("XLA Ops", [(n, s * MS, d * MS) for n, s, d in ops])])]


@pytest.mark.parametrize("ops, second", [
    ([("nextbig", 30, 10), ("meter", 890, 25), ("gather", 940, 15)],
     "mega.report"),
    # the gather compiles instead of running: the idle time falls
    # inside its call, and takes the call's name
    ([("nextbig", 30, 10), ("meter", 890, 25)], "mega.billing.call"),
], ids=["every-call-on-device", "gather-compiling"])
def test_longest_gap_is_the_event_loop(ops, second):
    red = trace_reduce.reduce_planes(_day(ops))
    gaps = red["gaps"]
    assert gaps[0][0] == "mega.event_loop"
    assert gaps[0][1] >= 0.800
    assert gaps[1][0] == second
    # no gap longer than 0.1 s is left to the harness's own span
    assert not [g for g in gaps if g[0] == "bench.call" and g[1] > 0.1]
