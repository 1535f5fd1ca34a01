"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
checkpointing, then generate from it.

    PYTHONPATH=src python examples/train_lm.py [--steps 200] [--arch mamba2-130m]

Any assigned arch works via --arch (reduced "smoke" geometry unless
--full).  mamba2-130m trains at its FULL published config (~130M params)
by default budget.
"""
import argparse
import dataclasses
import tempfile

import jax.numpy as jnp
import numpy as np

import jax

from repro.configs import get_arch, smoke_config
from repro.launch.train import train
from repro.serve import generate
from repro.models import init_params


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="mamba2-130m")
    p.add_argument("--steps", type=int, default=120)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--full", action="store_true",
                   help="use the full published config (mamba2-130m only "
                        "is laptop-feasible)")
    args = p.parse_args()

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = smoke_config(cfg)
        # ~100M-class geometry for the end-to-end demo
        cfg = dataclasses.replace(cfg, d_model=256, n_layers=cfg.period * 4,
                                  vocab_size=8192,
                                  param_dtype=jnp.float32,
                                  compute_dtype=jnp.float32)
    print(f"training {cfg.name}: {cfg.param_count()/1e6:.1f}M params")

    with tempfile.TemporaryDirectory() as ckpt_dir:
        losses = train(cfg, steps=args.steps, batch=args.batch,
                       seq=args.seq, ckpt_dir=ckpt_dir, ckpt_every=50,
                       lr=1e-3)
        first, last = np.mean(losses[:10]), np.mean(losses[-10:])
        print(f"loss {first:.3f} -> {last:.3f} "
              f"({'LEARNING' if last < first - 0.1 else 'check config'})")

    params = init_params(cfg, jax.random.key(0))
    prompts = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)),
        jnp.int32)
    toks = generate(params, cfg, prompts, max_new_tokens=8)
    print("generated token ids:", toks.tolist())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
