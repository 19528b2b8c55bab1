"""Programs JAX lowered inside the window of the serving cell:
``compiles.sim``'s reader."""
import os

from bench import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
read = manifest.module("metrics", "compiles.sim", _ROOT).read
