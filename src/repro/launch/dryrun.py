import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first init.

'''Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination and extract the roofline terms from the compiled module.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

Artifacts: one JSON per combo under experiments/dryrun/ with bytes/FLOPs/
collective-bytes, memory analysis and the derived roofline terms —
benchmarks/roofline.py renders EXPERIMENTS.md tables from these.
'''
import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.hlo_cost import collective_bytes, cost_dict
from repro.launch.mesh import make_production_mesh, peaks
from repro.launch.steps import lower_combo

# combinations that do not exist architecturally (DESIGN.md §4)
SKIPS = {
    ("whisper-large-v3", "long_500k"): "audio encoder capped at 1500 frames;"
                                       " 500k-frame context does not exist",
}


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float) -> dict:
    pk = peaks("TPU v5 lite")          # the production meshes are v5e
    return {
        "compute_s": flops_per_dev / pk["flops_bf16"],
        "memory_s": bytes_per_dev / pk["hbm_bw"],
        "collective_s": coll_bytes_per_dev / pk["ici_bw"],
    }


def _metrics(compiled) -> dict:
    ca = cost_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll}


def probe_roofline(cfg, shape, mesh, sync: str = "auto") -> dict:
    """Exact per-device cost totals via layer-count extrapolation.

    XLA's cost analysis counts while-loop bodies once, so the full-size
    (scanned) compile under-reports. We compile 1- and 2-unit UNROLLED
    probes (unit = attn_every for hybrids, 1 layer otherwise; enc+dec
    together for enc-dec) and extrapolate:
        total = p1 + (n_units - 1) * (p2 - p1).
    Probes run the full global batch with grad-accum disabled (weight
    re-reads under accumulation are therefore underestimated; noted in
    EXPERIMENTS.md).
    """
    from repro.models.scan_util import set_probe_unroll
    from repro.launch.steps import lower_combo as _lower

    u = cfg.attn_every if cfg.family == "hybrid" else 1
    n_units = cfg.n_layers // u

    def probe_cfg(units):
        kw = dict(n_layers=u * units, grad_accum={}, remat=cfg.remat)
        if cfg.is_encoder_decoder:
            kw["encoder_layers"] = units
        return cfg.replace(**kw)

    set_probe_unroll(True)
    try:
        p = []
        for units in (1, 2):
            lowered, _ = _lower(probe_cfg(units), shape, mesh, sync=sync)
            p.append(_metrics(lowered.compile()))
    finally:
        set_probe_unroll(False)
    p1, p2 = p
    out = {"flops": p1["flops"] + (n_units - 1) * (p2["flops"] - p1["flops"]),
           "bytes": p1["bytes"] + (n_units - 1) * (p2["bytes"] - p1["bytes"]),
           "coll": {k: p1["coll"][k] + (n_units - 1) * (p2["coll"][k] - p1["coll"][k])
                    for k in p1["coll"]}}
    # guard against fusion-noise negatives
    out["flops"] = max(out["flops"], p2["flops"])
    out["bytes"] = max(out["bytes"], p2["bytes"])
    out["coll"] = {k: max(v, 0.0) for k, v in out["coll"].items()}
    return out


def run_combo(arch: str, shape_name: str, *, multi_pod: bool,
              out_dir: str | None = None, verbose: bool = True,
              sync: str = "auto", tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "ok", "sync": sync}
    if (arch, shape_name) in SKIPS:
        rec["status"] = "skip"
        rec["reason"] = SKIPS[(arch, shape_name)]
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    try:
        lowered, kind = lower_combo(cfg, shape, mesh, sync=sync)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        # exact cost totals via unrolled layer-count probes (see docstring)
        pm = probe_roofline(cfg, shape, mesh, sync=sync)
        coll = pm["coll"]
        coll_total = float(sum(coll.values()))
        flops = pm["flops"]
        byt = pm["bytes"]
        terms = roofline_terms(flops, byt, coll_total)
        dom = max(terms, key=terms.get)

        n_model = cfg.param_count()
        n_active = cfg.param_count(active_only=True)
        tokens = shape.global_batch * (shape.seq_len if kind == "train" else 1)
        if kind == "train":
            model_flops = 6 * n_active * tokens
        elif kind == "prefill":
            model_flops = 2 * n_active * shape.global_batch * shape.seq_len
        else:
            model_flops = 2 * n_active * shape.global_batch
        rec.update({
            "kind": kind,
            "n_chips": n_chips,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "flops_per_dev": flops,
            "bytes_per_dev": byt,
            "collective_bytes_per_dev": coll,
            "collective_total_per_dev": coll_total,
            "roofline": terms,
            "dominant": dom,
            "params": n_model,
            "params_active": n_active,
            "model_flops_total": model_flops,
            "useful_flops_ratio": (model_flops / (flops * n_chips)
                                   if flops else 0.0),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "per_device_total": (ma.argument_size_in_bytes
                                     + ma.temp_size_in_bytes
                                     + ma.output_size_in_bytes
                                     - ma.alias_size_in_bytes),
            },
        })
        if verbose:
            print(f"[OK] {arch} x {shape_name} x {mesh_name} ({kind}) "
                  f"lower={t_lower:.0f}s compile={t_compile:.0f}s "
                  f"dom={dom} "
                  f"terms=({terms['compute_s']:.2e},{terms['memory_s']:.2e},"
                  f"{terms['collective_s']:.2e})s "
                  f"mem/dev={rec['memory']['per_device_total']/2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[FAIL] {arch} x {shape_name} x {mesh_name}: "
                  f"{rec['error'][:300]}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fn = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_name}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ASSIGNED_ARCHS + ["all"], default="all")
    ap.add_argument("--shape", choices=list(INPUT_SHAPES) + ["all"],
                    default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--sync", choices=("auto", "dense", "rage_k"),
                    default="auto")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_combo(arch, shape, multi_pod=mp, out_dir=args.out,
                                sync=args.sync, tag=args.tag)
                n_fail += rec["status"] == "fail"
    print(f"\ndone; failures: {n_fail}")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
