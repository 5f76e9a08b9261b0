"""Work counts and the peak table of the benchmark."""
import pytest

import bench_testkit as kit
import peaks
import work

CNN = kit.load(kit.BENCH / "configs" / "cifar_cnn.json")
MLP = kit.load(kit.BENCH / "configs" / "mnist_mlp.json")


@pytest.mark.parametrize("cfg, macs", [(CNN, 16_885_760), (MLP, 39_700)])
def test_forward_macs_per_sample(cfg, macs):
    assert sum(work.forward_macs(cfg)) == macs


def test_train_flops_leave_out_the_first_layers_input_gradient():
    first = work.forward_macs(CNN)[0]
    assert first == 32 * 32 * 9 * 3 * 64
    assert work.train_flops_per_sample(CNN) == 6 * 16_885_760 - 2 * first
    assert work.train_flops_per_sample(MLP) == 6 * 39_700 - 2 * 784 * 50


@pytest.mark.parametrize("cfg", [CNN, MLP])
def test_parameter_count_matches_the_widths(cfg):
    h, w, c = cfg["input"]
    convs = sum(9 * ci * co + 3 * co for ci, co, _ in cfg["convs"])
    fcs = sum(fi * fo + fo for fi, fo in cfg["fcs"])
    assert convs + fcs == cfg["n_params"]


def test_least_bytes_come_from_the_call_shapes():
    n, d = 6, 2_515_338
    assert work.maghist_least(n, d) == (4 * n * d, n * d)
    assert work.sparse_aggregate_least(600, d) == (8 * 600 + 4 * d, 600)
    v5e = peaks.peak("TPU v5 lite")
    # the histogram pass is bound by memory: 60 MB at 819 GB/s
    assert work.least_seconds(*work.maghist_least(n, d), v5e) == \
        pytest.approx(4 * n * d / 819e9)
    assert work.least_seconds(1.0, 197e12, v5e) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("TPU v9 imaginary")
