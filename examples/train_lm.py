"""End-to-end LM training driver with checkpoint/restart.

Presets:
  tiny  - smoke-scale model, runs in ~a minute on CPU (default).
  100m  - ~100M-parameter llama-family model for a few hundred steps (the
          deliverable configuration; give it real hardware or patience).

    PYTHONPATH=src python examples/train_lm.py --preset tiny --steps 30
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""
import argparse
import dataclasses

import jax

from repro.configs.base import LMConfig
from repro.configs.registry import ARCHS
from repro.models.transformer import init_lm_params, lm_loss
from repro.train import data as data_lib
from repro.train.train_loop import run_training
from repro.compile_cache import enable_compile_cache

PRESETS = {
    "tiny": (ARCHS["tinyllama-1.1b"].smoke, 4, 32),
    # ~100M params: 12L x 768, llama-style, 16k vocab.
    "100m": (LMConfig(name="lm-100m", num_layers=12, d_model=768,
                      num_heads=12, num_kv_heads=4, head_dim=64,
                      d_ff=2048, vocab_size=16_384), 8, 512),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="tiny")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt_lm")
    args = ap.parse_args()

    cfg, batch, seq = PRESETS[args.preset]
    n_params = sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(lambda: init_lm_params(jax.random.key(0), cfg))))
    print(f"[train_lm] {cfg.name}: {n_params / 1e6:.1f}M params, "
          f"batch={batch} seq={seq}")

    def batch_fn(key):
        return data_lib.lm_batch(cfg, batch, seq, key)

    params, metrics = run_training(
        cfg=cfg, init_params_fn=lambda k: init_lm_params(k, cfg),
        loss_fn=lm_loss, batch_fn=batch_fn, num_steps=args.steps,
        ckpt_dir=args.ckpt_dir, ckpt_every=50, lr=args.lr, log_every=10)
    print(f"[train_lm] done: {metrics}")


if __name__ == "__main__":
    main()
