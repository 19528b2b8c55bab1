"""Roofline share of the metering kernel ``segment_trapz.fused_meter``
over one traced job of the serving cell: ``fused_meter_roofline``'s
reader, with its count of the operations and bytes the metering needs,
read over this cell's metered power segments."""
import os

from bench import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
read = manifest.module("metrics", "fused_meter_roofline", _ROOT).read
