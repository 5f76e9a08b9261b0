"""The benchmark's own copy of the federation's inputs: a vectorised
class-prototype image generator and the label partition into client shards.

Each client holds exactly its label set. By default a label's samples are
split as evenly as possible among the clients that hold it. A
configuration's ``dataset`` may instead state a ``shard_sizes`` block, the
law of the per-client sample counts and its source, e.g.
``{"law": "lognormal", "mean": 226.83, "std": 88.94, "source": "LEAF
FEMNIST writers, arXiv 1812.01097 Table 1"}``: each client's count is drawn
from that law (a log-normal with the stated mean and standard deviation,
rounded, at least one sample per label) and split as evenly as possible
among its labels; the training set then holds exactly those samples, and
``n_train`` is not read.

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


def images(n: int, protos: np.ndarray, rng, *, noise: float, shift: int,
           counts=None):
    """n samples: (x (n, *shape) float32, y (n,) int32). Each sample is its
    class prototype, noised, rolled by (dy, dx) in [-shift, shift]^2. The
    classes hold ``counts[c]`` samples each (by default n // n_classes, the
    first n % n_classes one more) in an order drawn from ``rng``, so every
    seed gives the same class counts, and with them the same shard sizes
    and shapes."""
    n_classes = protos.shape[0]
    y = (np.arange(n) % n_classes if counts is None
         else np.repeat(np.arange(n_classes), counts))
    y = rng.permutation(y).astype(np.int32)
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


# The class prototypes, the test set and the shard sizes are the task; a
# run's seed draws the training samples. Held fixed, the per-client
# evaluation sets (which the engine bakes into its eval program as
# constants) are the same in every run, so that program is compiled once
# and then found in the cache.
TASK_SEED = 0


def drawn_sizes(block: dict, client_labels: list) -> list:
    """Per-client sample counts from a ``shard_sizes`` block, drawn from
    ``TASK_SEED``: the same on every seed."""
    if block["law"] != "lognormal":
        raise ValueError(f"no shard-size law {block['law']!r}")
    mean, std = block["mean"], block["std"]
    var = np.log1p((std / mean) ** 2)
    rng = np.random.default_rng([TASK_SEED, 4])
    sizes = np.rint(rng.lognormal(np.log(mean) - var / 2, np.sqrt(var),
                                  len(client_labels))).astype(int)
    return [max(int(s), len(labels))
            for s, labels in zip(sizes, client_labels)]


def label_counts(config: dict) -> list:
    """``counts[c][j]``: client c's samples of its j-th label. The same on
    every seed."""
    clients, spec = config["clients"], config["dataset"]
    if "shard_sizes" in spec:
        return [[s // len(labels) + (j < s % len(labels))
                 for j in range(len(labels))]
                for s, labels in zip(drawn_sizes(spec["shard_sizes"],
                                                 clients), clients)]
    n, n_classes = spec["n_train"], spec["n_classes"]
    owners: dict = {}
    for c, labels in enumerate(clients):
        for j, lab in enumerate(labels):
            owners.setdefault(lab, []).append((c, j))
    counts = [[0] * len(labels) for labels in clients]
    for lab, holders in owners.items():
        total = n // n_classes + (lab < n % n_classes)
        for h, (c, j) in enumerate(holders):
            counts[c][j] = total // len(holders) + (h < total % len(holders))
    return counts


def shard_lengths(config: dict) -> list:
    """Each client's number of training samples."""
    return [sum(c) for c in label_counts(config)]


def dataset(spec: dict, seed: int, counts=None):
    """Train and test sets of a configuration's ``dataset`` block: the
    training samples from the seed (``counts[c]`` of class c where given),
    the prototypes and the test set from ``TASK_SEED``."""
    protos = prototypes(tuple(spec["shape"]), spec["n_classes"],
                        np.random.default_rng([TASK_SEED, 0]))
    kw = dict(noise=spec["noise"], shift=spec["shift"])
    n = spec["n_train"] if counts is None else int(np.sum(counts))
    train = images(n, protos, np.random.default_rng([seed, 1]),
                   counts=counts, **kw)
    test = images(spec["n_test"], protos,
                  np.random.default_rng([TASK_SEED, 2]), **kw)
    return train, test


def label_partition(x, y, client_labels: list, counts: list, *, seed: int):
    """One shard per client: ``counts[c][j]`` samples of client c's j-th
    label. The samples of a label are shuffled and dealt out in client
    order, so each client holds exactly its label set."""
    rng = np.random.default_rng([seed, 3])
    owners: dict = {}
    for c, labels in enumerate(client_labels):
        for lab, k in zip(labels, counts[c]):
            owners.setdefault(lab, []).append((c, k))
    parts: list = [[] for _ in client_labels]
    for lab in sorted(owners):
        idx = np.nonzero(y == lab)[0]
        rng.shuffle(idx)
        ends = np.cumsum([k for _, k in owners[lab]])
        for (c, _), part in zip(owners[lab], np.split(idx, ends[:-1])):
            parts[c].append(part)
    out = []
    for p in parts:
        sel = np.sort(np.concatenate(p))
        out.append((x[sel], y[sel]))
    return out


def federation_data(config: dict, seed: int):
    """(shards, test) of a configuration: its synthetic dataset split by
    its clients' label sets."""
    spec, clients = config["dataset"], config["clients"]
    counts = label_counts(config)
    per_class = None
    if "shard_sizes" in spec:
        per_class = np.zeros(spec["n_classes"], int)
        for labels, cs in zip(clients, counts):
            np.add.at(per_class, labels, cs)
    (xtr, ytr), test = dataset(spec, seed, per_class)
    return label_partition(xtr, ytr, clients, counts, seed=seed), test
