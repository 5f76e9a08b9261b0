"""The benchmark's own copy of the federation's data and splits."""
import numpy as np
import pytest

import bench_testkit as kit
import synth


@pytest.mark.parametrize("name", ["cifar_cnn", "mnist_mlp"])
def test_paper_split(name):
    cfg = kit.load(kit.BENCH / "configs" / f"{name}.json")
    cfg["dataset"]["n_train"] = 6_000
    shards, (xte, yte) = synth.federation_data(cfg, seed=2**31 + 7)
    spec = cfg["dataset"]
    assert sum(len(y) for _, y in shards) == spec["n_train"]
    assert xte.shape == (spec["n_test"], *spec["shape"])
    for (x, y), labels in zip(shards, cfg["clients"]):
        assert x.shape == (len(y), *spec["shape"]) and x.dtype == np.float32
        assert set(np.unique(y).tolist()) == set(labels)
    for lab in range(spec["n_classes"]):
        counts = [int(np.sum(y == lab)) for _, y in shards
                  if np.any(y == lab)]
        assert len(counts) == 2 and abs(counts[0] - counts[1]) <= 1


def test_shard_sizes_do_not_depend_on_the_seed():
    cfg = kit.load(kit.BENCH / "configs" / "cifar_cnn.json")
    cfg["dataset"]["n_train"] = 5_000
    sizes = [[len(y) for _, y in synth.federation_data(cfg, seed)[0]]
             for seed in (1, 2**31 + 11)]
    assert sizes[0] == sizes[1] == [750, 750, 750, 750, 1000, 1000]


def test_same_seed_same_data_other_seed_other_data():
    cfg = kit.load(kit.BENCH / "configs" / "mnist_mlp.json")
    cfg["dataset"]["n_train"] = 500
    a, _ = synth.federation_data(cfg, seed=3)
    b, _ = synth.federation_data(cfg, seed=3)
    c, _ = synth.federation_data(cfg, seed=4)
    assert all(np.array_equal(x1, x2) for (x1, _), (x2, _) in zip(a, b))
    assert not np.array_equal(a[0][0][:10], c[0][0][:10])


def test_shift_rolls_each_sample_by_its_own_offset():
    rng = np.random.default_rng(0)
    protos = np.arange(2 * 5 * 5, dtype=np.float32).reshape(2, 5, 5, 1)
    x, y = synth.images(50, protos, rng, noise=0.0, shift=1)
    for xi, yi in zip(x, y):
        assert any(np.array_equal(xi, np.roll(protos[yi], (dy, dx), (0, 1)))
                   for dy in (-1, 0, 1) for dx in (-1, 0, 1))
