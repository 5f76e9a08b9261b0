"""The benchmark's own copy of the federation's data and splits."""
import hashlib

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


def skewed(n_clients: int) -> dict:
    cfg = kit.load(kit.BENCH / "configs" / "mnist_mlp.json")
    cfg["clients"] = [[2 * (c % 5), 2 * (c % 5) + 1]
                      for c in range(n_clients)]
    cfg["dataset"]["shard_sizes"] = kit.FEMNIST_SIZES
    return cfg


def test_skewed_shard_sizes_do_not_depend_on_the_seed():
    cfg = skewed(24)
    a, _ = synth.federation_data(cfg, seed=5)
    b, _ = synth.federation_data(cfg, seed=2**31 + 13)
    assert [x.shape for x, _ in a] == [x.shape for x, _ in b]
    assert [len(y) for _, y in a] == synth.shard_lengths(cfg)
    assert len(set(synth.shard_lengths(cfg))) > 12
    assert not np.array_equal(a[0][0], b[0][0])
    for (x, y), labels, counts in zip(a, cfg["clients"],
                                      synth.label_counts(cfg)):
        assert x.shape == (len(y), *cfg["dataset"]["shape"])
        assert [int(np.sum(y == lab)) for lab in labels] == counts
        assert set(np.unique(y).tolist()) == set(labels)


def test_skewed_shard_sizes_have_the_stated_moments():
    sizes = np.array(synth.shard_lengths(skewed(3550)))
    assert sizes.mean() == pytest.approx(226.83, rel=0.03)
    assert sizes.std() == pytest.approx(88.94, rel=0.1)
    assert sizes.min() >= 2


def test_an_unknown_shard_size_law_is_refused():
    cfg = skewed(4)
    cfg["dataset"]["shard_sizes"] = dict(kit.FEMNIST_SIZES, law="zipf")
    with pytest.raises(ValueError):
        synth.shard_lengths(cfg)


def test_even_split_is_pinned():
    """Without ``shard_sizes`` the shards and the test set are bit for bit
    those the benchmark drew before shard sizes could be skewed."""
    cfg = kit.load(kit.BENCH / "configs" / "mnist_mlp.json")
    cfg["dataset"]["n_train"] = 500
    shards, test = synth.federation_data(cfg, seed=3)
    h = hashlib.sha256()
    for a in [a for s in shards for a in s] + list(test):
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == \
        "c68bcf0271f1104e97b9d4a28ab5df916333f66df4261e909c802d4c70ba89a2"
    assert [len(y) for _, y in shards] == synth.shard_lengths(cfg)
