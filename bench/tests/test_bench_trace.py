"""The reduction from a profiler trace to busy time, kernel time and
labelled idle gaps, on a hand-built trace (no TPU topology is described)."""
import pytest

import bench_testkit as kit
import trace_reduce as tr

MS = 1_000_000  # ns


def ev(name, start_ms, dur_ms):
    return [name, start_ms * MS, dur_ms * MS]


def trace(ops, spans, device="/device:TPU:0"):
    return [
        {"name": device, "lines": [
            {"name": "XLA Modules", "events": [ev("jit(chunk)", 0, 100)]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": spans + [ev("other", 0, 100)]}]},
    ]


OPS = [ev("fusion.1", 10, 20), ev("maghist_batch.3", 25, 10),
       ev("sparse_aggregate", 50, 5), ev("maghist_batch.7", 60, 10)]
SPANS = [ev("bench.window", 0, 100), ev("bench.call", 0, 55),
         ev("bench.call", 55, 45)]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    s = tr.reduce(trace(OPS, SPANS), "bench.window")
    # [10, 35] + [50, 55] + [60, 70] = 40 ms busy of 100
    assert s["window_s"] == pytest.approx(0.100)
    assert s["busy_s"] == pytest.approx(0.040)
    assert s["op_s"]["maghist_batch"] == pytest.approx(0.020)
    assert s["device_ops"][0] == ["fusion", pytest.approx(0.020)]


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    s = tr.reduce(trace(OPS, SPANS), "bench.window")
    gaps = {round(d * 1e3): lab for lab, d in s["idle_gaps"]}
    # longest gap first: [70, 100] inside the second call
    assert s["idle_gaps"][0] == ["bench.call", pytest.approx(0.030)]
    assert gaps == {30: "bench.call", 10: "bench.call", 15: "bench.call",
                    5: "bench.call"}
    assert sum(s["idle_by_span"].values()) == pytest.approx(0.060)


def test_ops_outside_the_window_are_cut_off():
    ops = OPS + [ev("fusion.9", 95, 20)]
    s = tr.reduce(trace(ops, SPANS), "bench.window")
    assert s["busy_s"] == pytest.approx(0.045)
    assert s["op_s"]["fusion"] == pytest.approx(0.025)


def test_several_devices_are_averaged():
    planes = trace(OPS, SPANS)
    planes.append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [ev("fusion.1", 0, 100)]}]})
    s = tr.reduce(planes, "bench.window")
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx((0.040 + 0.100) / 2)


def test_nothing_to_read_without_a_device_plane_or_a_window():
    assert tr.reduce(trace(OPS, SPANS, device="/host:GPU"), "bench.window") \
        is None
    assert tr.reduce(trace(OPS, SPANS), "bench.missing") is None


def test_recorded_tpu_trace():
    """A slice of a real TPU trace: operations named by their HLO text,
    a scan loop (`while`) that holds the others."""
    rec = kit.load(kit.BENCH / "tests" / "data" / "tpu_trace_mnist.json")
    s = tr.reduce(rec["planes"], "bench.call")
    assert s is not None and s["devices"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    names = {n for n, _ in s["device_ops"]}
    assert names and not any("%" in n or "=" in n for n in names)
    assert "while" not in names and "fusion" in names
    assert sum(v for _, v in s["device_ops"]) <= sum(s["op_s"].values())
