"""Plain reference of Table I Network 2 (the CIFAR-10 CNN), built from the
sizes in ``cifar_cnn.json``: 3x3 convolutions (SAME padding) each followed
by batch normalisation in training mode and a ReLU, a 2x2 max-pool after
the first, then fully connected layers with ReLUs between them. Parameters
are drawn from the seed as the paper's reconstruction draws them (He
normal weights, zero biases, unit BN scales)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init(key, cfg: dict, dtype=jnp.float32):
    """(params, bn_state), leaves in ``dtype``."""
    convs, fcs = cfg["convs"], cfg["fcs"]
    keys = jax.random.split(key, len(convs) + len(fcs))
    params, state = {}, {}
    for i, (ci, co, _) in enumerate(convs):
        w = jax.random.normal(keys[i], (3, 3, ci, co)) * math.sqrt(2 / (ci * 9))
        params[f"conv{i}"] = {"w": w, "b": jnp.zeros((co,)),
                              "bn_scale": jnp.ones((co,)),
                              "bn_bias": jnp.zeros((co,))}
        state[f"conv{i}"] = {"mean": jnp.zeros((co,)), "var": jnp.ones((co,))}
    for j, (fi, fo) in enumerate(fcs):
        w = jax.random.normal(keys[len(convs) + j], (fi, fo)) * math.sqrt(2 / fi)
        params[f"fc{j}"] = {"w": w, "b": jnp.zeros((fo,))}
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    return cast(params), cast(state)


def apply(params, state, x, cfg: dict):
    """Training-mode forward pass: x (B, H, W, C) -> (logits, new_state)."""
    new_state = {}
    h = x
    for i, (_, _, stride) in enumerate(cfg["convs"]):
        p, s = params[f"conv{i}"], state[f"conv{i}"]
        h = jax.lax.conv_general_dilated(
            h, p["w"], (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
        mu = jnp.mean(h, axis=(0, 1, 2))
        var = jnp.var(h, axis=(0, 1, 2))
        new_state[f"conv{i}"] = {"mean": 0.9 * s["mean"] + 0.1 * mu,
                                 "var": 0.9 * s["var"] + 0.1 * var}
        h = (h - mu) * jax.lax.rsqrt(var + 1e-5) * p["bn_scale"] + p["bn_bias"]
        h = jax.nn.relu(h)
        if i == cfg["pool_after_conv"]:
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    n_fc = len(cfg["fcs"])
    for j in range(n_fc):
        h = h @ params[f"fc{j}"]["w"] + params[f"fc{j}"]["b"]
        if j < n_fc - 1:
            h = jax.nn.relu(h)
    return h, new_state
