"""Plain reference of Table I Network 1 (the MNIST MLP), built from the
sizes in ``mnist_mlp.json``: FC(784, 50), ReLU, FC(50, 10). Weights are
normal with variance 1/fan_in, biases zero."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, cfg: dict, dtype=jnp.float32):
    """(params, {}), leaves in ``dtype``. Layers are named fc1, fc2."""
    keys = jax.random.split(key, len(cfg["fcs"]))
    params = {}
    for j, (fi, fo) in enumerate(cfg["fcs"]):
        params[f"fc{j + 1}"] = {
            "w": (jax.random.normal(keys[j], (fi, fo)) * (fi ** -0.5)
                  ).astype(dtype),
            "b": jnp.zeros((fo,), dtype)}
    return params, {}


def apply(params, state, x, cfg: dict):
    h = x.reshape(x.shape[0], -1)
    n_fc = len(cfg["fcs"])
    for j in range(n_fc):
        h = h @ params[f"fc{j + 1}"]["w"] + params[f"fc{j + 1}"]["b"]
        if j < n_fc - 1:
            h = jax.nn.relu(h)
    return h, state
