"""Device idle share over one traced job's call into the program, in the
serving cell: ``device.idle_pct.sim``'s reader."""
import os

from bench import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
read = manifest.module("metrics", "device.idle_pct.sim", _ROOT).read
