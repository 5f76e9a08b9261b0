"""Shared set-up of the benchmark's CPU tests: import paths, and a small
copy of the benchmark's layout in which a cell runs in seconds on the CPU."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = "mnist_mlp.tiny"


def load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def tiny_bench(tmp: Path, limits_of: str = "mnist_mlp.paper_fig3") -> Path:
    """A benchmark layout under ``tmp`` with one cell, ``mnist_mlp.tiny``:
    the MNIST MLP federation (all ten clients, published widths and
    budgets r, k, H) on 3,000 samples, M = 4 and an evaluation every 2
    rounds, so that the 4 checked rounds hold a recluster. Its limits are
    those of ``limits_of``. Returns the layout's ``bench`` directory."""
    bench = tmp / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    shutil.copy(BENCH / "configs" / "mnist_mlp.py", bench / "configs")
    cfg = load(BENCH / "configs" / "mnist_mlp.json")
    cfg["dataset"].update(n_train=3000, n_test=500)
    cfg["protocol"]["M"] = 4
    (bench / "configs" / "mnist_mlp.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(
        {"eval_every": 2, "overrides": {}, "warm_calls": 2, "check_calls": 2,
         "trace_calls": 1}))
    shutil.copy(BENCH / "limits" / f"{limits_of}.json",
                bench / "limits" / f"{TINY}.json")
    spec = load(ROOT / "BENCHMARK.json")
    spec["configs"] = [c for c in spec["configs"] if c["name"] == "mnist_mlp"]
    spec["workloads"] = [{"name": TINY, "config": "mnist_mlp",
                          "traffic": "tiny", "chips": 1, "why": "CPU test"}]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


# the per-writer sample counts of LEAF's FEMNIST (arXiv 1812.01097, Table 1)
FEMNIST_SIZES = {"law": "lognormal", "mean": 226.83, "std": 88.94,
                 "source": "LEAF FEMNIST, arXiv 1812.01097 Table 1"}


def participation_bench(tmp: Path, layout: str) -> Path:
    """``tiny_bench``'s layout with its cell's federation replaced: N = 16
    two-label clients with FEMNIST's shard sizes, UniformM m = 4 a round,
    the given age layout, M = 4 and an evaluation every 4 rounds, so that
    the 8 checked rounds hold two reclusters. Limits as ``tiny_bench``'s."""
    bench = tiny_bench(tmp)
    path = bench / "configs" / "mnist_mlp.json"
    cfg = load(path)
    cfg["clients"] = [[2 * (c % 5), 2 * (c % 5) + 1] for c in range(16)]
    cfg["dataset"]["shard_sizes"] = FEMNIST_SIZES
    del cfg["dataset"]["n_train"]
    cfg["protocol"].update(schedule="uniform", participation_m=4,
                           age_layout=layout)
    path.write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(
        {"eval_every": 4, "overrides": {}, "warm_calls": 2, "check_calls": 2,
         "trace_calls": 1}))
    return bench
