"""Public jit'd wrappers for the Pallas kernels.

One switch (``use_pallas``) selects the kernel or the pure-jnp reference;
the serving engine and benchmarks call through here.  Kernels compile
for the chip when the default backend is a TPU and run in Pallas
interpret mode (same kernel body, same f32 arithmetic) anywhere else;
the choice is made at call time, so importing this module starts no
backend.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pl
from repro.kernels.flash_attention import flash_attention as _flash_pl
from repro.kernels.rglru_scan import rglru_scan as _rglru_pl
from repro.kernels.segment_trapz import fused_meter as _fused_pl


def _interpret() -> bool:
    """Interpret kernels unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    use_pallas: bool = True) -> jnp.ndarray:
    if use_pallas:
        return _flash_pl(q, k, v, causal=causal, window=window,
                         interpret=_interpret())
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def decode_attention(q, k, v, length, *, use_pallas: bool = True
                     ) -> jnp.ndarray:
    if use_pallas:
        return _decode_pl(q, k, v, length, interpret=_interpret())
    return ref.decode_attention_ref(q, k, v, length)


def rglru_scan(a, b, h0, *, use_pallas: bool = True) -> jnp.ndarray:
    if use_pallas:
        return _rglru_pl(a, b, h0, interpret=_interpret())
    return ref.rglru_scan_ref(a, b, h0)


def fused_meter(a, b, dt, w, g, kt, kv, cum, periods):
    """Metering pass over the meter log: per-entry energy, seconds and
    carbon increment (see ``segment_trapz.fused_meter``).  Always the
    kernel: compiled on a TPU, interpreted elsewhere, so CPU runs check
    the arithmetic the chip runs."""
    return _fused_pl(a, b, dt, w, g, kt, kv, cum, periods,
                     interpret=_interpret())
