"""Every cell of BENCHMARK.json resolves to its files; a new configuration,
mix, limits or metric is found by its name alone; and the benchmark refuses
to report from a CPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_testkit as kit
import harness

SPEC = kit.load(kit.ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_workload_resolves(workload):
    cell = harness.resolve(SPEC, workload)
    assert cell["model"].is_file()
    assert cell["config"]["name"] == \
        next(w for w in SPEC["workloads"] if w["name"] == workload)["config"]
    assert {"eval_every", "warm_calls", "check_calls", "trace_calls"} <= \
        set(cell["mix"])
    assert cell["mix"]["check_calls"] <= cell["mix"]["warm_calls"]
    assert cell["limits"]
    names = {m["name"] for m in cell["end_to_end"]}
    assert {"rounds_per_s", "peak_hbm_gb", "setup_s"} <= names
    for m in cell["per_layer"]:
        assert m["reader"].is_file()
        assert m["moves"] in names


def test_paths_hold_every_file_the_spec_names():
    for c in SPEC["configs"]:
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        assert (kit.ROOT / c["file"]).is_file()
        assert kit.load(kit.ROOT / c["file"])["source"] == c["source"]


def test_new_files_and_entries_are_found_without_edits(tmp_path):
    bench = kit.tiny_bench(tmp_path)
    spec = kit.load(tmp_path / "BENCHMARK.json")
    # a further configuration, mix, limits file and per-layer metric
    shutil.copy(bench / "configs" / "mnist_mlp.json",
                bench / "configs" / "mnist_wide.json")
    shutil.copy(bench / "configs" / "mnist_mlp.py",
                bench / "configs" / "mnist_wide.py")
    (bench / "traffic" / "bursty.json").write_text(json.dumps(
        {"eval_every": 5, "warm_calls": 1, "check_calls": 1,
         "trace_calls": 1, "overrides": {"H": 2}}))
    (bench / "limits" / "mnist_wide.bursty.json").write_text('{"loss_gap": 1}')
    (bench / "metrics" / "rounds_traced.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    spec["configs"].append({"name": "mnist_wide", "source": "s",
                            "file": "bench/configs/mnist_wide.json",
                            "reduced": [], "why": "w"})
    spec["workloads"].append({"name": "mnist_wide.bursty",
                              "config": "mnist_wide", "traffic": "bursty",
                              "chips": 1, "why": "w"})
    spec["per_layer"].append({"name": "rounds_traced", "unit": "rounds",
                              "better": "higher", "source": "host_clock",
                              "layer": "round driver",
                              "moves": "rounds_per_s",
                              "workloads": ["mnist_wide.bursty"]})
    cell = harness.resolve(spec, "mnist_wide.bursty", bench)
    assert harness.protocol(cell)["H"] == 2
    assert cell["model"] == bench / "configs" / "mnist_wide.py"
    assert [m["name"] for m in cell["per_layer"]][-1] == "rounds_traced"
    got = harness.per_layer(cell, None, 40, 0.0, "TPU v5 lite")
    assert got == {"rounds_traced": {"value": 40.0, "unit": "rounds"}}
    # the metric restricted to the new cell is not the tiny cell's
    tiny = harness.resolve(spec, kit.TINY, bench)
    assert "rounds_traced" not in [m["name"] for m in tiny["per_layer"]]


def run_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_a_cpu_is_refused_before_any_result():
    p = run_cli(kit.ROOT, "--workload", "mnist_mlp.paper_fig3", "--seed",
                "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_the_benchmark_alone_cannot_run(tmp_path):
    shutil.copy(kit.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(kit.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    p = run_cli(tmp_path, "--workload", "mnist_mlp.paper_fig3", "--seed",
                "1", "--seconds", "1")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
