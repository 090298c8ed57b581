"""Batched LM serving demo: prefill-free decode loop with per-layer KV
caches (ring buffers for Gemma-2 local layers, MLA latent cache for
DeepSeek-V2).

    PYTHONPATH=src python examples/serve_lm.py --arch gemma2-27b --tokens 16
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS
from repro.models.transformer import init_cache, init_lm_params, serve_step
from repro.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-27b",
                    choices=[a for a, e in ARCHS.items()
                             if e.family == "lm"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = ARCHS[args.arch].smoke  # smoke config: runs on CPU
    key = jax.random.key(0)
    params = init_lm_params(key, cfg)
    caches = init_cache(cfg, args.batch, max_len=args.tokens + 8)

    step = jax.jit(lambda p, c, t, pos: serve_step(p, c, t, pos, cfg))
    tok = jax.random.randint(key, (args.batch,), 0, cfg.vocab_size)
    generated = [tok]
    t0 = time.perf_counter()
    for pos in range(args.tokens):
        logits, caches = step(params, caches, tok, jnp.int32(pos))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # greedy
        generated.append(tok)
    dt = time.perf_counter() - t0
    out = jnp.stack(generated, axis=1)
    print(f"[serve] {args.arch} (smoke cfg): generated {out.shape} tokens "
          f"in {dt:.2f}s ({args.tokens * args.batch / dt:.1f} tok/s)")
    print(out[:, :10])


if __name__ == "__main__":
    main()
