"""End-to-end serving driver: a REAL model served with batched requests
under energy-aware lifecycle management (the paper's technique live).

A reduced Qwen2.5-7B-family model decodes actual tokens on CPU through
the ServingEngine; the ModelManager makes keep-warm/evict decisions with
the breakeven policy and meters energy with the H100 profile.  A day of
bursty traffic is replayed in simulated time (decode compute runs for
real; waiting does not).

Run:  PYTHONPATH=src python examples/serve_parking.py
"""
import jax

from repro.compile_cache import use_compile_cache
from repro.configs import get_reduced
from repro.core import H100, QWEN25_7B_MEASURED
from repro.core.scheduler import AlwaysOn, Breakeven
from repro.core import traffic
from repro.models import RunFlags, build_param_specs, materialize
from repro.serving import ModelManager, ServingEngine, SimClock


def main() -> None:
    use_compile_cache()
    cfg = get_reduced("qwen2-5-7b")
    params = materialize(build_param_specs(cfg), jax.random.PRNGKey(0))
    # one warm engine reused across cold starts: in production the load
    # deserializes a checkpoint (ModelManager advances the sim clock by
    # t_load and charges P_load); rebuilding jit closures per cold start
    # would only measure XLA compile time
    engine = ServingEngine(cfg, params, max_batch=4, max_len=48,
                           flags=RunFlags(remat="none"))

    def load_engine():
        return engine

    arrivals = traffic.bursty(seed=1, horizon_s=6 * 3600.0)  # 6h demo
    print(f"replaying {len(arrivals)} requests over 6 h (simulated time, "
          f"real decode compute)")

    for policy in (AlwaysOn(), Breakeven(QWEN25_7B_MEASURED, H100)):
        mm = ModelManager(H100, clock=SimClock())
        mm.register("qwen", policy=policy, loader=QWEN25_7B_MEASURED,
                    load_fn=load_engine)
        tokens_out = 0

        def serve_one(engine):
            nonlocal tokens_out
            res = engine.generate([1, 2, 3, 4, 5], max_new=8)
            tokens_out += len(res.tokens)
            return res

        mm.handle_request("qwen", work_fn=serve_one)       # initial load
        for a in arrivals:
            mm._advance_with_evictions(max(float(a), mm.clock()))
            mm.handle_request("qwen", work_fn=serve_one)
        mm._advance_with_evictions(6 * 3600.0)

        m = mm.models["qwen"]
        wh = mm.meter.totals()
        print(f"  {policy.name:30s} energy {wh['total']:7.1f} Wh "
              f"(parked {wh.get('parked', 0.0):6.1f}, "
              f"bare {wh.get('bare', 0.0):6.1f}, "
              f"loading {wh.get('loading', 0.0):5.1f}) | "
              f"cold starts {m.cold_starts:3d} | "
              f"{tokens_out} real tokens decoded | "
              f"parking tax {mm.meter.parking_tax_wh():6.1f} Wh")


if __name__ == "__main__":
    main()
