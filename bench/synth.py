"""The benchmark's own copy of the federation's inputs: a vectorised
class-prototype image generator and the label partition into client shards.

The generator follows the recipe of the program's offline stand-ins for
MNIST and CIFAR-10 (one smoothed random prototype per class inside a
circular "stroke" mask, a noisy copy of it per sample, a random
translation of up to ``shift`` pixels), with every sample drawn in bulk
rather than rolled one at a time. It lives here so that a change to the
program's data code cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np


def prototypes(shape: tuple, n_classes: int, rng) -> np.ndarray:
    """(n_classes, *shape) float32 class prototypes."""
    protos = rng.normal(0, 1, (n_classes,) + shape).astype(np.float32)
    for axis in (1, 2):
        protos = 0.5 * protos + 0.25 * (np.roll(protos, 1, axis=axis)
                                        + np.roll(protos, -1, axis=axis))
    hh, ww = shape[0], shape[1]
    yy, xx = np.meshgrid(np.arange(hh), np.arange(ww), indexing="ij")
    cy = rng.uniform(hh * 0.3, hh * 0.7, n_classes)
    cx = rng.uniform(ww * 0.3, ww * 0.7, n_classes)
    mask = ((yy[None] - cy[:, None, None]) ** 2
            + (xx[None] - cx[:, None, None]) ** 2 < (hh * 0.30) ** 2)
    return (protos * mask[..., None].astype(np.float32) * 2.0
            ).astype(np.float32)


def images(n: int, protos: np.ndarray, rng, *, noise: float, shift: int):
    """n samples: (x (n, *shape) float32, y (n,) int32). Each sample is its
    class prototype, noised, rolled by (dy, dx) in [-shift, shift]^2. The
    classes hold n // n_classes samples each (the first n % n_classes one
    more) in an order drawn from ``rng``, so every seed gives the same
    class counts, and with them the same shard sizes and shapes."""
    n_classes = protos.shape[0]
    y = rng.permutation(np.arange(n) % n_classes).astype(np.int32)
    x = rng.standard_normal((n,) + protos.shape[1:], dtype=np.float32)
    x *= np.float32(noise)
    x += protos[y] * np.float32(1 - noise)
    if shift:
        dy = rng.integers(-shift, shift + 1, n)
        dx = rng.integers(-shift, shift + 1, n)
        for sy in range(-shift, shift + 1):
            for sx in range(-shift, shift + 1):
                sel = np.nonzero((dy == sy) & (dx == sx))[0]
                if sel.size:
                    x[sel] = np.roll(x[sel], (sy, sx), axis=(1, 2))
    return x, y


# The class prototypes and the test set are the task; a run's seed draws the
# training samples. Held fixed, the per-client evaluation sets (which the
# engine bakes into its eval program as constants) are the same in every
# run, so that program is compiled once and then found in the cache.
TASK_SEED = 0


def dataset(spec: dict, seed: int):
    """Train and test sets of a configuration's ``dataset`` block: the
    training samples from the seed, the prototypes and the test set from
    ``TASK_SEED``."""
    protos = prototypes(tuple(spec["shape"]), spec["n_classes"],
                        np.random.default_rng([TASK_SEED, 0]))
    kw = dict(noise=spec["noise"], shift=spec["shift"])
    train = images(spec["n_train"], protos,
                   np.random.default_rng([seed, 1]), **kw)
    test = images(spec["n_test"], protos,
                  np.random.default_rng([TASK_SEED, 2]), **kw)
    return train, test


def label_partition(x, y, client_labels: list, *, seed: int):
    """One shard per client. The samples of a label are shuffled and split
    as evenly as possible among the clients that hold the label, so each
    client holds exactly its label set."""
    rng = np.random.default_rng([seed, 3])
    owners: dict = {}
    for c, labels in enumerate(client_labels):
        for lab in labels:
            owners.setdefault(lab, []).append(c)
    parts: list = [[] for _ in client_labels]
    for lab in sorted(owners):
        idx = np.nonzero(y == lab)[0]
        rng.shuffle(idx)
        for c, part in zip(owners[lab], np.array_split(idx, len(owners[lab]))):
            parts[c].append(part)
    out = []
    for p in parts:
        sel = np.sort(np.concatenate(p))
        out.append((x[sel], y[sel]))
    return out


def federation_data(config: dict, seed: int):
    """(shards, test) of a configuration: its synthetic dataset split by
    its clients' label sets."""
    (xtr, ytr), test = dataset(config["dataset"], seed)
    return label_partition(xtr, ytr, config["clients"], seed=seed), test
