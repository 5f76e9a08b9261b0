"""Serving driver: batched prefill + decode with KV/state caches.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config, list_archs
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(remat=False)
    if cfg.family in ("audio",):
        raise SystemExit("serve demo targets decoder-only archs")
    key = jax.random.PRNGKey(0)
    params = T.init(cfg, key)

    B, P = args.batch, args.prompt_len
    max_len = P + args.gen
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)

    cache = T.init_cache(cfg, B, max_len)
    step = jax.jit(lambda p, tok, c, pos: T.decode_step(p, cfg,
                                                        {"token": tok}, c, pos))
    # prefill via decode steps (keeps one compiled program; production
    # prefill is the batched forward exercised in the dry-run)
    t0 = time.time()
    logits = None
    for t in range(P):
        logits, cache = step(params, prompts[:, t], cache, t)
    t_prefill = time.time() - t0

    toks = []
    t0 = time.time()
    cur = jnp.argmax(logits, -1)
    for t in range(P, max_len):
        toks.append(cur)
        logits, cache = step(params, cur, cache, t)
        if args.temperature > 0:
            key, k2 = jax.random.split(key)
            cur = jax.random.categorical(k2, logits / args.temperature, -1)
        else:
            cur = jnp.argmax(logits, -1)
    dt = time.time() - t0
    gen = jnp.stack(toks, 1)
    print(f"arch={cfg.name} batch={B} prefill={t_prefill:.2f}s "
          f"decode={args.gen / dt:.1f} tok/s/batch")
    print("generated token ids (first row):", gen[0].tolist())


if __name__ == "__main__":
    main()
