"""Shared benchmark plumbing: timing, result rows, artifact dirs."""
from __future__ import annotations

import json
import os
import time

import jax

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "experiments")


def art_dir(name: str) -> str:
    d = os.path.join(ARTIFACTS, name)
    os.makedirs(d, exist_ok=True)
    return d


def time_us(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    for _ in range(warmup):
        r = fn(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters * 1e6


def interleaved_best_us(fns: dict, *, iters: int, rounds: int) -> dict:
    """Best-of per-call timing (microseconds) with the candidate
    callables interleaved per round, so machine noise hits every variant
    alike (ratios stay meaningful on a loaded box). Compiles + warms each
    callable once before timing. fns: name -> nullary callable returning
    a jax value (blocked on per window)."""
    for fn in fns.values():                    # compile + warm
        jax.block_until_ready(fn())
    best = {name: float("inf") for name in fns}
    for _ in range(rounds):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)
            best[name] = min(best[name],
                             (time.perf_counter() - t0) / iters * 1e6)
    return best


def interleaved_best(fns: dict, *, repeats: int) -> dict:
    """Best-of wall-clock (seconds), one call per variant per repeat,
    variants interleaved. Callers warm their callables first — the first
    repeat still pays any residual compilation."""
    best = {name: float("inf") for name in fns}
    for _ in range(repeats):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            fn()
            best[name] = min(best[name], time.perf_counter() - t0)
    return best


def save_json(name: str, obj):
    path = os.path.join(art_dir("bench"), name + ".json")
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, default=float)
    return path


def row(name: str, us: float, derived: str = "") -> str:
    return f"{name},{us:.1f},{derived}"
