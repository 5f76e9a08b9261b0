"""The benchmark's reduction and per-layer readers on the recorded TPU
trace, pinned to the numbers they give: a change to how a trace is read
(a fourth element on device events, more host spans) must leave them."""
import pytest

import bench_testkit as kit
import harness
import scopes
import trace_reduce as tr

CELL = "mnist_mlp.paper_fig3"
ROUNDS, STALL_S = 20, 1e-4


@pytest.fixture(scope="module")
def recorded():
    return kit.load(kit.BENCH / "tests" / "data" / "tpu_trace_mnist.json")


def test_busy_window_and_op_time_are_pinned(recorded):
    s = tr.reduce(recorded["planes"], "bench.call")
    assert s["window_s"] == pytest.approx(0.064632358, rel=1e-12)
    assert s["busy_s"] == pytest.approx(0.051130882, rel=1e-12)
    assert len(s["op_s"]) == 28
    assert sum(s["op_s"].values()) == pytest.approx(0.052090412, rel=1e-12)
    assert s["op_s"]["while"] == pytest.approx(0.05132755, rel=1e-12)
    assert s["op_s"]["sort"] == pytest.approx(0.000380422, rel=1e-12)
    assert s["op_s"]["copy"] == pytest.approx(0.000306327, rel=1e-12)
    assert s["idle_by_span"] == {"bench.call": pytest.approx(0.013501476)}


def test_existing_readers_are_pinned(recorded):
    spec = harness.load_json(kit.ROOT / "BENCHMARK.json")
    cell = harness.resolve(spec, CELL)
    s = tr.reduce(recorded["planes"], "bench.call")
    got = {k: v["value"] for k, v in
           harness.per_layer(cell, s, ROUNDS, STALL_S, "TPU v5 lite").items()}
    # the slice holds no maghist_batch, sparse_aggregate or
    # segmented_age_topk call: those readers find nothing
    assert got == {
        "device_idle_share": pytest.approx(20.88965406460955, rel=1e-12),
        "train_mfu": pytest.approx(0.2570339559620968, rel=1e-12),
        "recluster_stall_share": pytest.approx(0.15472126206504797,
                                               rel=1e-12)}


def test_plain_planes_reduce_the_same(recorded):
    """Cutting events to three elements leaves a three-element trace as
    it was."""
    planes = recorded["planes"]
    assert tr.reduce(scopes.plain(planes), "bench.call") \
        == tr.reduce(planes, "bench.call")
