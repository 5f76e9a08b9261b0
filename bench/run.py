"""Run one cell of the benchmark on the chip it finds.

    python bench/run.py --workload cifar_cnn.paper_h100 --seed 7 \
        --seconds 30 --trace 0

Set-up (data from the seed, the engine, the first calls of the timed
``run_scanned``), then either a measured window (``--trace 0``: the cell's
end-to-end metrics) or a traced one (``--trace 1``: its per-layer metrics),
then the check of the set-up's first calls against the plain reference.
The last line of standard output is one JSON object:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``; the checks also end standard error, one per line. Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits non-zero.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402

import compare  # noqa: E402
import harness  # noqa: E402

CACHE_DIR = BENCH / ".jax_cache"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache():
    """JAX's persistent cache at one fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept, so a
    second run compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int) -> dict:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return device_info()


def device_info() -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def run(argv=None, *, require_chip: bool = True, t0: float = T0,
        bench: Path = BENCH) -> dict:
    """One run; returns the result object (also printed by ``main``).
    ``bench`` is the directory that holds the cell's files, beside its
    ``BENCHMARK.json``."""
    args = parse(argv)
    spec = harness.load_json(bench.parent / "BENCHMARK.json")
    cell = harness.resolve(spec, args.workload, bench)
    use_compile_cache()
    device = require_chips(cell["chips"]) if require_chip else device_info()

    r = harness.Run(cell, args.seed, t0)
    r.setup()
    log = sys.stderr
    print(f"setup_s={r.setup_s:.3f}, seconds from start at the end of each "
          f"phase: {json.dumps(r.phases)}", file=log)
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}}
    if args.trace:
        summary, rounds, stall, active = r.traced()
        result["attempted"] = rounds
        result["metrics"] = harness.per_layer(cell, summary, rounds, stall,
                                              device["kind"], active)
        if summary:
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
            print("idle by host span: " + json.dumps(summary["idle_by_span"]),
                  file=log)
    else:
        w = r.window(args.seconds)
        result.update(attempted=w["rounds"], failed=w["failed"])
        rate = w["rounds"] / w["wall_s"]
        e2e = {"rounds_per_s": rate, "setup_s": r.setup_s}
        print(f"window: {w['rounds']} rounds in {w['wall_s']:.6f} s",
              file=log)
    stats = r.memory_stats()
    print(f"memory_stats: {json.dumps(stats)}", file=log)
    # the runtime reserves the programs' temporaries apart from the
    # buffers it counts as in use; the chip holds both at its peak
    peak_bytes = (stats["peak_bytes_in_use"]
                  + stats.get("peak_bytes_reserved", 0)
                  if "peak_bytes_in_use" in stats else None)
    device["memory_peak_bytes"] = peak_bytes
    if not args.trace:
        if peak_bytes is not None:
            e2e["peak_hbm_gb"] = peak_bytes / 1e9
        wanted = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        result["metrics"] = {k: {"value": v, "unit": wanted[k]}
                             for k, v in e2e.items() if k in wanted}
    print(f"compiles_in_window={len(r.compiles.names)} "
          f"{sorted(set(r.compiles.names))}", file=log)
    r.compiles.close()

    numbers = r.check()
    print(f"check_s={r.check_s:.3f}", file=log)
    ok, checks = compare.judge(numbers, cell["limits"])
    result["correct"] = ok and result["failed"] == 0
    result["device"] = device
    result["checks"] = checks
    for name in sorted(set(numbers) - set(checks)):
        print(f"reading {name} = {numbers[name]!r} (not compared)", file=log)
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}", file=log)
    return result


def main(argv=None):
    result = run(argv)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
