"""Train a ~100M-parameter LM for a few hundred steps on CPU with the
full production path: sharded init, AdamW + microbatch accumulation,
int8 gradient compression, async fault-tolerant checkpoints, resumable
data pipeline.  Loss must descend on the structured synthetic corpus.

Run:  PYTHONPATH=src python examples/train_100m.py [--steps 300]
"""
import argparse
import dataclasses

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.models.config import dense_lm
from repro.models.model import RunFlags
from repro.training.optimizer import AdamWConfig
from repro.training.trainer import TrainConfig, train

# ~100M params: 12L x 512 with a 32k vocab (GPT-small-ish)
CONFIG = dense_lm(
    "lm-100m", n_layers=12, d_model=512, n_heads=8, n_kv_heads=8,
    d_ff=2048, vocab_size=32_768, family="dense",
    source="examples/train_100m")
CONFIG = dataclasses.replace(CONFIG, param_dtype=None or CONFIG.param_dtype)


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_train_100m")
    args = ap.parse_args()

    import jax.numpy as jnp
    cfg = dataclasses.replace(CONFIG, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32)
    print(f"params: {cfg.param_count()/1e6:.1f}M")
    tc = TrainConfig(
        steps=args.steps, batch_size=args.batch, seq_len=args.seq,
        checkpoint_dir=args.ckpt, checkpoint_every=100, log_every=20,
        grad_compression=True,
        opt=AdamWConfig(lr=3e-4, warmup_steps=50, total_steps=args.steps),
        flags=RunFlags(remat="full", grad_accum=2))
    hist = train(cfg, tc)
    first = float(np.mean(hist["loss"][:20]))
    last = float(np.mean(hist["loss"][-20:]))
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'DESCENDED' if last < first - 0.1 else 'check run length'})")


if __name__ == "__main__":
    main()
