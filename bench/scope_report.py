"""Where a cell's traced window goes, by the program's own names: device
time by the round's named scopes, the engine's ``fl.*`` host spans, idle
time put down to the innermost span, and the chunk programs the engine
built inside the window (its ``retraces`` counter).

    python bench/scope_report.py --workload mnist_mlp.paper_fig3 --seed 7

Set-up as ``bench/run.py`` makes it, but compiled afresh, then the cell's
``trace_calls`` calls under the profiler inside a ``bench.window`` span,
as ``--trace 1`` records them; no check against the reference. The last line of standard
output is one JSON object. Without a TPU it exits non-zero; a test
rehearses it on the CPU, where the trace has no device plane, so only the
counter and the rounds read.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402

import harness  # noqa: E402
import run as bench_run  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    return ap.parse_args(argv)


def retraces_of(engine) -> dict | None:
    counts = getattr(engine, "retraces", None)
    return dict(counts) if counts is not None else None


def traced_planes(r: harness.Run) -> tuple:
    """The cell's ``trace_calls`` calls under the profiler: (planes with
    scope paths, rounds, recluster wait, chunk programs built,
    participants summed over the rounds), the fourth None for an engine
    without the counter."""
    wait0, built0 = r.engine.recluster_wait_s, retraces_of(r.engine)
    rounds = active = 0
    with tempfile.TemporaryDirectory(prefix="bench-scopes-") as d:
        jax.profiler.start_trace(d)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(r.mix["trace_calls"]):
                    n_active = r._call().n_active
                    rounds += len(n_active)
                    active += sum(n_active)
        finally:
            jax.profiler.stop_trace()
        # the op_name metadata a TPU trace's op events lack
        texts = getattr(r.engine, "program_texts", None)
        for i, text in enumerate(texts() if texts else []):
            Path(d, f"program{i}.hlo.txt").write_text(text)
        planes = scopes.load_xplane(d)
    built1 = retraces_of(r.engine)
    built = (None if built0 is None
             else {k: built1[k] - built0.get(k, 0) for k in built1})
    return planes, rounds, r.engine.recluster_wait_s - wait0, built, active


def report(cell: dict, planes: list, rounds: int, stall: float, built,
           device_kind: str, active: int | None = None) -> dict:
    """The window's readings: the benchmark's own reduction and per-layer
    metrics beside the scope and span readings."""
    summary = trace_reduce.reduce(scopes.plain(planes), "bench.window")
    by_name = scopes.reduce(planes, "bench.window")
    out = {"rounds": rounds, "retraces": built,
           "metrics": harness.per_layer(cell, summary, rounds, stall,
                                        device_kind, active)}
    if summary is None or by_name is None:
        return out
    scope_s = by_name["scope_s"]
    idle = by_name["idle_by_span"]
    idle_s = sum(idle.values())
    stops = by_name["span_s"].get("fl.host_stop")
    out.update(
        window_s=summary["window_s"], busy_s=summary["busy_s"],
        scope_s=scope_s,
        us_per_round={k: 1e6 * v / rounds for k, v in scope_s.items()},
        scope_ops=by_name["scope_ops"],
        eval_share=100.0 * scope_s.get("eval", 0.0) / summary["window_s"],
        phase_cover=scopes.phase_cover(scope_s),
        span_s=by_name["span_s"],
        host_stop_ms_per_call=(1e3 * stops["s"] / stops["count"]
                               if stops else None),
        idle_by_span=idle,
        idle_fl_share=(sum(v for k, v in idle.items()
                           if k.startswith("fl.") and k != "fl.chunk")
                       / idle_s if idle_s else None),
        idle_gaps=by_name["idle_gaps"])
    return out


def main(argv=None, *, require_chip: bool = True,
         bench: Path = BENCH) -> dict:
    args = parse(argv)
    spec = harness.load_json(bench.parent / "BENCHMARK.json")
    cell = harness.resolve(spec, args.workload, bench)
    # JAX's persistent cache keys leave named scopes out, so a cached
    # executable may predate them: this run compiles afresh
    jax.config.update("jax_enable_compilation_cache", False)
    device = (bench_run.require_chips(cell["chips"]) if require_chip
              else bench_run.device_info())
    r = harness.Run(cell, args.seed, T0)
    r.setup()
    r.compiles.on = True
    planes, rounds, stall, built, active = traced_planes(r)
    r.compiles.on = False
    out = {"workload": args.workload, "seed": args.seed, "device": device,
           "setup_s": r.setup_s, "compiles_in_window": len(r.compiles.names)}
    out.update(report(cell, planes, rounds, stall, built, device["kind"],
                      active))
    r.compiles.close()
    r.engine.close()
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
