"""LM training driver with rAge-k gradient exchange (the paper's protocol
as a data-parallel collective — DESIGN.md §4).

CPU-scale by default (reduced configs); the full configs are exercised by
the dry-run. Example:

  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --smoke --steps 50 --method rage_k --r 4096 --k 512
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.configs.base import InputShape
from repro.data.pipeline import token_stream
from repro.dist.sparse_sync import init_age_state, make_sync_train_step
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.models.registry import input_specs
from repro.optim.optimizers import adam


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--method", choices=("rage_k", "dense"), default="rage_k")
    ap.add_argument("--r", type=int, default=2048)
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(remat=False)
    mesh = make_host_mesh(args.data_axis, 1)
    key = jax.random.PRNGKey(0)

    params = T.init(cfg, key)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params:,} method={args.method}")

    opt = adam(args.lr)
    opt_state = opt.init(params)
    ages = init_age_state(params)

    def loss_fn(p, batch):
        loss, _aux = T.loss_fn(p, cfg, batch)
        return loss

    step = jax.jit(make_sync_train_step(
        loss_fn, opt, mesh, method=args.method, r=args.r, k=args.k))

    stream = token_stream(cfg.vocab_size, args.batch, args.seq, seed=1)
    t0 = time.time()
    wire = 0
    for i in range(1, args.steps + 1):
        nb = next(stream)
        batch = {k_: jnp.asarray(v) for k_, v in nb.items()}
        params, opt_state, ages, loss, stats = step(
            params, opt_state, ages, batch)
        wire += int(stats["wire_bytes_per_shard"])
        if i % args.log_every == 0 or i == args.steps:
            dt = time.time() - t0
            print(f"step {i:5d} loss={float(loss):.4f} "
                  f"steps/s={i / dt:.2f} wire={wire/2**20:.2f}MiB/shard")
    if args.ckpt:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt, args.steps, params)
        print(f"saved checkpoint to {args.ckpt}")


if __name__ == "__main__":
    main()
