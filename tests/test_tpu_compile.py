"""The fleet backend's device programs compile for a TPU v5e chip.

Each test lowers one compiled phase of ``run_mega(backend="jax")`` at
the size of the 600-device, ~1M-request flash-crowd day (2^20 metered
charge-log entries, 24 hourly bins, 3 zone traces of up to 64 knots)
for a chip that is described, not attached, and compiles it with the
TPU compiler.  That catches what interpret mode cannot: a kernel Mosaic
refuses to lower, a layout it cannot tile, a program that does not fit
the chip's 16 GB.  Nothing runs, so these say nothing about results or
times.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fleet.mega import jaxback
from repro.kernels import ops

N_ENTRIES = 1 << 20        # charge-log entries, padded (790,542 real)
N_DEV = 600
N_BINS = 24
N_PAIRS = 1 << 14          # straddle pairs, padded (~devices x boundaries)
G, K = 4, 64               # 3 zone traces padded to 4 rows, 64 knots
HBM_BYTES = 16 * 10 ** 9   # one v5e chip


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, chip, args, **static):
    """Lower and compile ``fn`` (un-jitted, so no cached CPU trace is
    reused) for the described chip under x64."""
    with jax.enable_x64(True):
        specs = [_spec(chip, shape, dtype) for shape, dtype in args]
        lowered = jax.jit(fn, static_argnames=tuple(static)).lower(
            *specs, **static)
        return lowered, lowered.compile()


def _fits(compiled) -> bool:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    return used < HBM_BYTES


def test_meter_fused_compiles_with_the_kernel(chip, monkeypatch):
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    f64, i32 = jnp.float64, jnp.int32
    n, p = (N_ENTRIES,), (N_PAIRS,)
    args = [(n, i32),                                   # keys
            (n, f64), (n, f64), (n, f64), (n, f64),     # a, b, dt, pw
            (n, i32), (n, i32),                         # g, bucket
            ((N_DEV,), i32),                            # tdev
            (p, i32), (p, i32), (p, f64),               # pseg, pk, pwp
            ((G, K), f64), ((G, K), f64), ((G, K), f64),
            ((G,), f64), ((N_BINS - 1,), f64)]          # pers, tbr
    lowered, compiled = _compile(jaxback._meter_fused.__wrapped__, chip,
                                 args, n_dev=N_DEV, nb=N_BINS, n_tier=1)
    assert "tpu_custom_call" in lowered.as_text()
    assert _fits(compiled)


def test_bill_gather_compiles(chip):
    f64, i32 = jnp.float64, jnp.int32
    recs = (1 << 17,)                   # billing records (107,209 real)
    # the arrivals (1,032,473 real), padded to 2^20 like the log
    args = [((N_ENTRIES,), f64), ((N_DEV,), i32),
            (recs, i32), (recs, i32), (recs, i32), (recs, f64)]
    _, compiled = _compile(jaxback._bill_gather.__wrapped__, chip, args,
                           total_pad=N_ENTRIES)
    assert _fits(compiled)


def test_nextbig_rows_compiles(chip):
    # one bucket of (stream, timeout) rows: 600 streams x 3 SKU timeouts
    # of ~1.7k arrivals each, padded to 2048 x 2048
    args = [((2048, 2048), jnp.float64), ((2048,), jnp.float64)]
    _, compiled = _compile(jaxback._nextbig_rows.__wrapped__, chip, args)
    assert _fits(compiled)
