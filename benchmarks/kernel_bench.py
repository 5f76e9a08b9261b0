"""Kernel microbenchmarks (interpret-mode timings are CPU-emulation numbers
— the derived column reports the work size; real-TPU perf comes from the
roofline analysis, not wall clock here). Also times the jnp reference to
show the oracle agrees at identical math.

The SELECTION bench (always run; CI smoke) compares the sequential
all-clients `rage_select` scan against the segmented per-cluster
formulation at N=64 clients on the fig3 MNIST config (d=39,760, r=75,
k=10; 8 clusters x 8 clients), sweeps the CANDIDATE plane (full-sort
`client_candidates` vs the histogram-threshold `threshold_topk_batch`)
at N in {64, 128, 256}, runs the 5-round engine A/B, and records
everything to experiments/bench/BENCH_selection.json.

The AUTOTUNE sweep drives every tiled kernel (`sparse_aggregate`
BLOCK_D/NK_TILE, `maghist_batch` block size, `segmented_age_topk` lane
width) through `kernels.autotune.sweep`, persisting the winners to
experiments/bench/AUTOTUNE.json — the registry `kernels.ops` consults
whenever a caller leaves the tiling unspecified.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (interleaved_best, interleaved_best_us,
                               save_json, time_us)
from repro.kernels import autotune, ops, ref


def _candidate_bench(fast: bool, rows: list, out: dict) -> None:
    """The per-client top-r candidate report: full-sort plane vs the
    histogram-threshold plane (bit-identical indices), N-swept on the
    fig3 config. On CPU the exact rank still pays a full-width
    `lax.top_k` (XLA CPU's TopK custom call is a single fast partial
    sort), so the recorded CPU speedup is < 1 — the threshold plane is
    the TPU play: the d-sized work collapses to ONE streaming
    `maghist_batch` pass instead of a full sort (see DESIGN.md §8)."""
    from repro.core.strategies import client_candidates

    d, r = 39_760, 75
    iters, bo_rounds = (3, 6) if fast else (5, 12)
    rng = np.random.default_rng(7)
    sweep = {}
    for n in (64, 128, 256):
        G = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        cand_sort = jax.jit(lambda G, r=r: client_candidates(G, r, "sort"))
        cand_thr = jax.jit(
            lambda G, r=r: client_candidates(G, r, "threshold"))
        np.testing.assert_array_equal(          # the bit-identity pin
            np.asarray(cand_sort(G)), np.asarray(cand_thr(G)))
        best = interleaved_best_us(
            {"sort": lambda: cand_sort(G), "threshold": lambda: cand_thr(G)},
            iters=iters, rounds=bo_rounds)
        sweep[f"n{n}"] = {
            "sort_us": best["sort"], "threshold_us": best["threshold"],
            "threshold_speedup": best["sort"] / best["threshold"],
            "rows_per_s_sort": n / best["sort"] * 1e6,
            "rows_per_s_threshold": n / best["threshold"] * 1e6,
        }
        rows.append((f"candidate_report_n{n}_sort", best["sort"],
                     f"d={d},r={r}"))
        rows.append((f"candidate_report_n{n}_threshold", best["threshold"],
                     f"speedup=x{best['sort'] / best['threshold']:.2f}"))
    # paper CIFAR scale, recorded so the N-sweep isn't mistaken for a
    # small-d artifact: the CPU ratio is flat in d (both planes stay
    # bound by the same full-width exact rank)
    d_c, r_c, n_c = 2_515_456, 2500, 4
    G = jnp.asarray(rng.normal(size=(n_c, d_c)).astype(np.float32))
    cand_sort = jax.jit(lambda G: client_candidates(G, r_c, "sort"))
    cand_thr = jax.jit(lambda G: client_candidates(G, r_c, "threshold"))
    np.testing.assert_array_equal(np.asarray(cand_sort(G)),
                                  np.asarray(cand_thr(G)))
    best = interleaved_best_us(
        {"sort": lambda: cand_sort(G), "threshold": lambda: cand_thr(G)},
        iters=2, rounds=3 if fast else 6)
    cifar = {"n": n_c, "d": d_c, "r": r_c,
             "sort_us": best["sort"], "threshold_us": best["threshold"],
             "threshold_speedup": best["sort"] / best["threshold"]}
    rows.append(("candidate_report_cifar_threshold", best["threshold"],
                 f"n={n_c},d={d_c},r={r_c},"
                 f"speedup=x{best['sort'] / best['threshold']:.2f}"))

    out["candidate_phase"] = {
        "config": {"d": d, "r": r},
        "n_sweep": sweep,
        "cifar_scale": cifar,
        "note": "bit-identical planes; CPU pays the full-width exact "
                "rank either way (XLA CPU TopK is one fast partial "
                "sort), so the recorded CPU speedup is < 1 at every "
                "scale — the threshold plane is the TPU lever, where "
                "the maghist kernel streams d once instead of sorting "
                "it (interpret-mode timing would be Python-speed "
                "emulation, the jnp binary-search tau is timed here)",
    }


def _autotune_bench(fast: bool, rows: list, out: dict) -> None:
    """Sweep the kernel tilings through the persistent registry."""
    backend = ops.backend_tag()
    key = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(key)

    # sparse_aggregate at the fig3 PS scale (NK = N*k = 640, d = 39,760)
    nk, d = 640, 39_760
    idx = jax.random.randint(k1, (nk,), 0, d)
    vals = jax.random.normal(k2, (nk,))
    age = jnp.zeros((d,), jnp.int32)
    tilings = ([{"block_d": 512, "nk_tile": 2048},
                {"block_d": 1024, "nk_tile": 2048}] if fast else
               [{"block_d": 256, "nk_tile": 1024},
                {"block_d": 512, "nk_tile": 2048},
                {"block_d": 1024, "nk_tile": 2048},
                {"block_d": 512, "nk_tile": 4096}])

    def time_agg(block_d, nk_tile):
        return time_us(
            jax.jit(lambda i, v, a, b=block_d, t=nk_tile:
                    ops.sparse_aggregate(i, v, a, block_d=b, nk_tile=t)),
            idx, vals, age, warmup=1, iters=2)

    best_agg, res_agg = autotune.sweep(
        "sparse_aggregate", (nk, d), "float32", backend, tilings, time_agg)

    # batched maghist at a reduced row count (interpret emulation is
    # Python-speed per grid cell; nearest-shape lookup serves bigger N)
    n_h = 4 if fast else 8
    G = jax.random.normal(key, (n_h, d))
    blocks = ([{"block_d": 4096}] if fast
              else [{"block_d": 2048}, {"block_d": 4096},
                    {"block_d": 8192}])

    def time_hist(block_d):
        return time_us(
            jax.jit(lambda g, b=block_d: ops.maghist_batch(g, block_d=b)),
            G, warmup=1, iters=2)

    best_hist, res_hist = autotune.sweep(
        "maghist_batch", (n_h, d), "float32", backend, blocks, time_hist)

    # segmented_age_topk lane width at the fig3 cluster layout
    C, S, r, k = 8, 8, 75, 10
    cand = jax.random.randint(k1, (C, S, r), 0, d, jnp.int32)
    cage = jax.random.randint(k2, (C, S, r), 0, 50, jnp.int32)
    valid = jnp.ones((C, S), bool)
    lanes = [{"lane": 128}] if fast else [{"lane": 128}, {"lane": 256}]

    def time_topk(lane):
        return time_us(
            jax.jit(lambda c, a, v, l=lane:
                    ops.segmented_age_topk(c, a, v, k, lane=l)),
            cand, cage, valid, warmup=1, iters=2)

    best_topk, res_topk = autotune.sweep(
        "segmented_age_topk", (C, S, r), "int32", backend, lanes, time_topk)

    out["autotune"] = {
        "registry": autotune.path(),
        "backend": backend,
        "sparse_aggregate": {"best": best_agg, "sweep": res_agg},
        "maghist_batch": {"best": best_hist, "sweep": res_hist},
        "segmented_age_topk": {"best": best_topk, "sweep": res_topk},
        "note": "interpret mode is CPU emulation (Python-speed); the "
                "registry keys carry the backend tag so real-TPU sweeps "
                "never collide with these",
    }
    rows.append(("autotune_sparse_aggregate_best",
                 min(r_["us"] for r_ in res_agg),
                 f"block_d={best_agg['block_d']},"
                 f"nk_tile={best_agg['nk_tile']}"))


def _selection_bench(fast: bool, rows: list) -> None:
    from repro.configs.base import RAgeKConfig
    from repro.core.strategies import client_candidates, segmented_age_topk
    from repro.data.federated import PAPER_MNIST_LABELS, label_partition
    from repro.data.synthetic import mnist_like
    from repro.fl import FederatedEngine
    from repro.fl.engine import (DeviceAgeState, rage_select,
                                 rage_select_segmented)

    # fig3 MNIST config scaled to N=64 clients: the paper's MLP d and
    # (r, k), 8 clusters of 8 (the label-pair structure at this N)
    n, d, r, k = 64, 39_760, 75, 10
    c, s = 8, 8
    iters = 15 if fast else 40
    # the 2-vCPU CI boxes are bimodal per 5-iter window; the min over
    # >= 12 interleaved windows is what converges (ratios were observed
    # swinging 0.7-1.6x at 5 windows, stable at 12)
    bo_rounds = 12 if fast else 20
    rng = np.random.default_rng(0)

    def mk_state(n_, c_, s_):
        a = DeviceAgeState(
            cluster_age=jnp.asarray(rng.integers(0, 50, (n_, d)),
                                    jnp.int32),
            freq=jnp.zeros((n_, d), jnp.int32),
            cluster_of=jnp.asarray(np.repeat(np.arange(c_), s_),
                                   jnp.int32))
        return a, jnp.asarray(rng.normal(size=(n_, d)).astype(np.float32))

    age, g = mk_state(n, c, s)
    cand_fn = jax.jit(client_candidates, static_argnames=("r", "impl"))
    cands = cand_fn(g, r=r)

    # PS selection phase (Algorithm 2 coordination given the client
    # candidate reports — the part the refactor parallelizes) and the
    # end-to-end select (candidate report + PS phase). Interleave ONLY
    # the A/B pair under comparison: mixing more programs into the
    # rotation perturbs the ratios via cache churn from their ~20MB
    # state outputs.
    best = interleaved_best_us({
        "seq": lambda: rage_select(g, age, r=r, k=k, cands=cands),
        "seg": lambda: rage_select_segmented(
            g, age, r=r, k=k, num_segments=c, max_seg=s, cands=cands),
    }, iters=max(iters // 3, 5), rounds=bo_rounds)
    best_e2e = interleaved_best_us({
        "seq_e2e": lambda: rage_select(g, age, r=r, k=k),
        "seg_e2e": lambda: rage_select_segmented(
            g, age, r=r, k=k, num_segments=c, max_seg=s),
    }, iters=max(iters // 3, 5), rounds=bo_rounds)
    best_cand = interleaved_best_us(
        {"sort": lambda: cand_fn(g, r=r),
         "thr": lambda: cand_fn(g, r=r, impl="threshold")},
        iters=max(iters // 3, 5), rounds=3)
    us_cand, us_cand_thr = best_cand["sort"], best_cand["thr"]
    us_seq, us_seg = best["seq"], best["seg"]
    us_seq_e2e = best_e2e["seq_e2e"]
    us_seg_e2e = best_e2e["seg_e2e"]

    # N-scaling of the PS phase: the sequential scan grows with N, the
    # segmented plane with max cluster size
    age2, g2 = mk_state(128, 16, 8)
    cands2 = cand_fn(g2, r=r)
    best2 = interleaved_best_us({
        "seq": lambda: rage_select(g2, age2, r=r, k=k, cands=cands2),
        "seg": lambda: rage_select_segmented(
            g2, age2, r=r, k=k, num_segments=16, max_seg=8,
            cands=cands2),
    }, iters=max(iters // 3, 5), rounds=bo_rounds)

    # Pallas segmented_age_topk (interpret = CPU emulation) vs its XLA
    # baseline (the jnp argmax/top_k formulation) on the same candidates
    seg_cand = cands[jnp.arange(n, dtype=jnp.int32).reshape(c, s)]
    seg_age = jax.vmap(lambda row, cnd: row[cnd])(
        age.cluster_age[:c], seg_cand)
    valid = jnp.ones((c, s), bool)
    topk_jnp = jax.jit(lambda a, b, v: segmented_age_topk(a, b, v, k))
    us_topk_jnp = time_us(topk_jnp, seg_cand, seg_age, valid, iters=iters)
    us_topk_pl = time_us(
        jax.jit(lambda a, b, v: ops.segmented_age_topk(a, b, v, k)),
        seg_cand, seg_age, valid, warmup=1, iters=2)

    # the XLA scatter baseline the autotuned sparse_aggregate runs against
    nk = n * k
    idx = jax.random.randint(jax.random.PRNGKey(0), (nk,), 0, d)
    vals = jax.random.normal(jax.random.PRNGKey(1), (nk,))
    age_vec = jnp.zeros((d,), jnp.int32)
    us_scatter = time_us(
        jax.jit(lambda i, v, a: ref.sparse_aggregate_ref(i, v, a)),
        idx, vals, age_vec, iters=iters)

    # 5-round engine A/B at N=64 (scan vs segmented selection plane):
    # rounds/sec and the selection-phase share of a round
    labels = [PAPER_MNIST_LABELS[i % 10] for i in range(n)]
    (xtr, ytr), test = mnist_like(n_train=128 * n, n_test=512, seed=0)
    shards = label_partition(xtr, ytr, labels, seed=0)
    hp = RAgeKConfig(r=r, k=k, H=1, M=1000, lr=2e-3, batch_size=32,
                     method="rage_k")
    rounds, repeats = (5, 3) if fast else (5, 7)
    engines = {}
    for sel in ("scan", "segmented"):
        e = FederatedEngine("mlp", shards, test, hp, seed=0, selection=sel)
        # pin the engine's cluster state to the benched 8x8 regime (the
        # microbench's) instead of relying on DBSCAN forming it; M is
        # large so no recluster rewrites it mid-run
        e.age = DeviceAgeState(e.age.cluster_age, e.age.freq,
                               age.cluster_of)
        e._num_seg, e._max_seg = c, s
        e.run(rounds, eval_every=rounds)            # compile + warm
        engines[sel] = e
    best_eng = interleaved_best(
        {sel: (lambda e_=e: e_.run(rounds, eval_every=rounds))
         for sel, e in engines.items()},
        repeats=repeats)
    round_us = {sel: best_eng[sel] / rounds * 1e6 for sel in best_eng}

    out = {
        "config": {"n_clients": n, "d": d, "r": r, "k": k,
                   "clusters": c, "max_cluster": s,
                   "engine_rounds": rounds, "engine_repeats": repeats,
                   "note": "fig3 MNIST config at N=64 clients; engine "
                           "cluster state pinned to 8 clusters x 8"},
        "candidate_report_us": us_cand,
        "candidate_report_threshold_us": us_cand_thr,
        # candidate-report share of the end-to-end select, before
        # (sort plane) and after (threshold plane) the switch
        "candidate_phase_share": {
            "sort": us_cand / (us_cand + us_seg),
            "threshold": us_cand_thr / (us_cand_thr + us_seg)},
        "selection_phase": {
            "sequential_us": us_seq, "segmented_us": us_seg,
            "sequential_selects_per_s": 1e6 / us_seq,
            "segmented_selects_per_s": 1e6 / us_seg,
            "segmented_speedup": us_seq / us_seg},
        "selection_phase_n128": {
            "clusters": 16, "max_cluster": 8,
            "sequential_us": best2["seq"], "segmented_us": best2["seg"],
            "segmented_speedup": best2["seq"] / best2["seg"]},
        "end_to_end_select": {
            "sequential_us": us_seq_e2e, "segmented_us": us_seg_e2e,
            "segmented_speedup": us_seq_e2e / us_seg_e2e},
        "segmented_age_topk": {
            "xla_jnp_us": us_topk_jnp,
            "pallas_interpret_us": us_topk_pl,
            "note": "interpret mode is CPU emulation (Python-speed)"},
        "sparse_aggregate": {
            "xla_scatter_us": us_scatter,
            "note": "tiling sweep moved to the autotune section "
                    "(registry-driven); interpret mode is CPU emulation"},
        "engine_round": {
            "scan": {"rounds_per_s": 1e6 / round_us["scan"],
                     "selection_phase_share":
                         us_seq / round_us["scan"]},
            "segmented": {"rounds_per_s": 1e6 / round_us["segmented"],
                          "selection_phase_share":
                              us_seg / round_us["segmented"]},
            "segmented_speedup":
                round_us["scan"] / round_us["segmented"]},
    }
    _candidate_bench(fast, rows, out)
    _autotune_bench(fast, rows, out)
    save_json("BENCH_selection", out)
    rows.append(("selection_phase_seq", us_seq, f"N={n},d={d},r={r},k={k}"))
    rows.append(("selection_phase_segmented", us_seg,
                 f"speedup=x{us_seq / us_seg:.2f}"))
    rows.append(("select_end_to_end_segmented", us_seg_e2e,
                 f"speedup=x{us_seq_e2e / us_seg_e2e:.2f}"))
    rows.append(("engine_round_segmented", round_us["segmented"],
                 f"vs_scan=x{round_us['scan'] / round_us['segmented']:.2f};"
                 f"sel_share={us_seg / round_us['segmented']:.3f}"))


def main(fast: bool = True):
    key = jax.random.PRNGKey(0)
    rows = []
    _selection_bench(fast, rows)

    # sparse aggregate: paper CIFAR scale (d=2.5M padded, N*k=600)
    d, nk = 2_515_456, 600
    idx = jax.random.randint(key, (nk,), 0, d)
    vals = jax.random.normal(key, (nk,))
    age = jnp.zeros(d, jnp.int32)
    f = jax.jit(lambda i, v, a: ref.sparse_aggregate_ref(i, v, a))
    rows.append(("sparse_aggregate_ref_jnp", time_us(f, idx, vals, age,
                                                     iters=5),
                 f"d={d},nk={nk}"))
    if not fast:
        g = jax.jit(lambda i, v, a: ops.sparse_aggregate(i, v, a))
        rows.append(("sparse_aggregate_pallas_interp",
                     time_us(g, idx, vals, age, warmup=1, iters=2),
                     "interpret=True (CPU emulation)"))

    # maghist + threshold topk at CIFAR scale
    g_vec = jax.random.normal(key, (d,))
    th = jax.jit(lambda g: ops.threshold_topk(g, 2500))
    rows.append(("threshold_topk_r2500", time_us(th, g_vec, iters=3),
                 f"d={d}"))
    ex = jax.jit(lambda g: jax.lax.top_k(jnp.abs(g), 2500))
    rows.append(("exact_topk_r2500", time_us(ex, g_vec, iters=3), f"d={d}"))

    # decode attention (model-scale slice)
    B, H, G, D, S = 4, 16, 8, 128, 4096
    q = jax.random.normal(key, (B, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, G, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, G, D), jnp.bfloat16)
    fr = jax.jit(jax.vmap(lambda a, b, c: ref.decode_attention_ref(
        a, b, c, jnp.array([S]))))
    rows.append(("decode_attention_ref_jnp", time_us(fr, q, k, v, iters=5),
                 f"B{B} H{H} S{S} D{D}"))
    return rows


if __name__ == "__main__":
    for r in main(fast=False):
        print(r)
