"""Roofline share of the metering kernel ``segment_trapz.fused_meter``
over one traced job.

Kernel time is the sum of the device events of its Pallas call, the
``custom-call`` XLA names after the kernel (``%fused_meter.1 = ...
custom-call(...)``).  The
least time is the larger of the operations and the bytes the metering
needs, over the chip's peaks: for each metered power segment of the
job, read its bounds, watts and trace group once and write its result
(4-byte values, as the kernel holds them), and add the trapezoid of
each carbon-knot interval the segment's in-period part overlaps
(12 operations: clip, two interpolated ends, area, accumulate) plus two
per segment (watts times integral, and the sum).  This counts the work,
not what one implementation does: padded entries and a walk over every
knot are not counted, so a faster algorithm raises the share."""
import re

import numpy as np

KERNEL = re.compile(r"^%fused_meter(\.\d+)? = .*custom-call\(")
BYTES_PER_SEGMENT = 5 * 4
FLOPS_PER_OVERLAP = 12
FLOPS_PER_SEGMENT = 2


def knot_times(points, period):
    ts = [t for t, _ in points]
    if ts[0] > 0.0:
        ts = [0.0] + ts
    one = np.array(ts + [period])
    return np.concatenate([one, one[1:] + period])   # two periods


def needed(segments, points, period):
    """(operations, bytes) the metering of ``segments`` ([n, 2] bounds)
    needs."""
    a, b = segments[:, 0], segments[:, 1]
    span = b - a
    rem = span - np.floor(span / period) * period
    p = a - np.floor(a / period) * period
    kt = knot_times(points, period)
    first = np.searchsorted(kt, p, side="right") - 1
    last = np.searchsorted(kt, p + rem, side="left") - 1
    overlaps = np.where(rem > 0.0, np.maximum(last - first + 1, 1), 0)
    n = len(a)
    return (FLOPS_PER_OVERLAP * float(overlaps.sum())
            + FLOPS_PER_SEGMENT * n, float(BYTES_PER_SEGMENT * n))


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    kernel_s = sum(s for name, s in tr["ops"].items() if KERNEL.match(name))
    segs = rec.get("traced_segments") or []
    if kernel_s <= 0.0 or not segs or not rec.get("peaks"):
        return None
    flops = nbytes = 0.0
    for sg in segs:
        f, by = needed(sg, rec["carbon_points"], rec["carbon_period_s"])
        flops += f
        nbytes += by
    pk = rec["peaks"]
    least = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
