"""Engine driver bench on the fig3 MNIST config, three axes:

* DRIVER: step (one dispatch per round) vs scan (chunked lax.scan) —
  records rounds/sec (where the driver's host time goes is read from a
  profiler trace: the engine's ``fl.*`` spans);
* SELECTION plane (rage_k): segmented per-cluster parallel (default) vs
  the sequential all-clients scan — both under the scan driver;
* ASYNC RECLUSTER: a short run whose final round triggers the every-M
  DBSCAN, measuring how much of the host clustering wall each driver
  HIDES behind chunk-boundary work (the scan driver submits it to a
  worker thread when the chunk metrics arrive; step computes inline);
* PARTICIPATION plane (DESIGN.md §9): seeded full / UniformM /
  AoIBalanced / Deadline runs at m = N/4, recording the new AoI metrics
  (client-level mean/peak AoI, coordinate-level cluster_age mean/peak)
  — at EQUAL uplink bytes the AoI-balancing scheduler should show the
  lower peak client AoI than uniform sampling;
* COMPUTE plane (DESIGN.md §11): gathered (train only the m active
  clients) vs masked (train all N, discard) on a 32-client split at
  m ∈ {N, N/4, N/16} — measured rounds/sec plus the compiled round's
  HLO FLOPs, which must scale with the scheduler's static m bound;
* ASYNC SERVICE plane (DESIGN.md §10): the event-driven buffered PS
  under a straggler-heavy latency draw vs the lockstep engine on the
  SAME LatencyModel, at EQUAL uplink bytes (equal landings): the sync
  round's virtual wall is the slowest client's dispatch, the async
  PS's aggregation cadence is set by MEAN latency — aggregations per
  virtual second should beat sync rounds per virtual second, with the
  staleness histogram showing what that throughput costs;
* AGE-MEMORY plane (DESIGN.md §12): hierarchical (C, d) cluster-keyed
  age rows + sparse update log vs the dense (N, d) matrices at
  N ∈ {64, 256, 1024} — measured device bytes before/after the first
  compaction (the C/N shrink) and rounds/sec parity at N=256 (the
  layouts must tie; the log append is O(m·k) against the dense
  layout's (N, d) scatter);
* RESILIENCE plane (DESIGN.md §13): (a) checkpoint overhead — the
  scanned driver at ckpt-every ∈ {0, 1, 4} through the async
  double-buffered writer vs blocking saves (the async writer at
  every-4 must cost < 10% rounds/sec); (b) accuracy vs NaN rate — the
  fig3 run under p_nan ∈ {0, 0.05, 0.2} with the validation gate on
  vs off (gate-on must finish finite and beat gate-off at the worst
  rate).

Results land in experiments/bench/BENCH_engine.json. Fast mode is the
5-round CI smoke; --slow grows the round count.
"""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import interleaved_best, save_json
from repro.configs.base import RAgeKConfig
from repro.core.compression import bytes_per_index, bytes_per_round
from repro.data.federated import paper_mnist_split
from repro.data.synthetic import mnist_like
from repro.fl import AsyncService, FederatedEngine, LatencyModel
from repro.fl.engine import DeviceAgeState


# (name, driver, selection plane)
VARIANTS = (("step", "step", "segmented"),
            ("scan", "scan", "segmented"),
            ("scan_seqsel", "scan", "scan"))


def _recluster_overlap(shards, test, rounds: int, repeats: int) -> dict:
    """Both drivers through a run whose LAST round reclusters (M =
    rounds): recluster_s is the host DBSCAN+merge wall, recluster_wait_s
    the part the driver blocked on; scan hides the difference behind the
    chunk-boundary metrics drain + bookkeeping."""
    hp = RAgeKConfig(r=75, k=10, H=4, M=rounds, lr=2e-3, batch_size=64,
                     method="rage_k")
    out = {}
    for name, use_scan in (("step", False), ("scan", True)):
        engine = FederatedEngine("mlp", shards, test, hp, seed=0)
        run = engine.run_scanned if use_scan else engine.run
        run(rounds, eval_every=rounds)               # compile + warm
        comp = wait = 0.0
        for _ in range(repeats):
            engine.recluster_s = engine.recluster_wait_s = 0.0
            run(rounds, eval_every=rounds)
            comp += engine.recluster_s
            wait += engine.recluster_wait_s
        out[name] = {
            "recluster_s": comp / repeats,
            "recluster_wait_s": wait / repeats,
            "recluster_hidden_s": max(0.0, comp - wait) / repeats,
            "hidden_fraction": (max(0.0, comp - wait) / comp
                                if comp else 0.0),
        }
    return out


def _participation(shards, test, rounds: int) -> dict:
    """Seeded schedule A/B on the fig3 config (DESIGN.md §9): full vs
    UniformM vs AoIBalanced at m = N/4 (EQUAL uplink bytes — same m,
    same rounds) plus the Deadline straggler profile. Records the
    participation metrics the engine now tracks: client-level AoI
    (mean over rounds / peak over the run) and the coordinate-level
    cluster_age field (final mean / peak). AoI balancing should beat
    uniform sampling on peak client AoI at identical uplink spend."""
    n = len(shards)
    m = max(n // 4, 1)
    base = dict(r=75, k=10, H=4, M=20, lr=2e-3, batch_size=64,
                method="rage_k")
    variants = (("full", dict(schedule="full"), n),
                ("uniform", dict(schedule="uniform", participation_m=m), m),
                ("aoi", dict(schedule="aoi", participation_m=m), m),
                ("deadline", dict(schedule="deadline", deadline_s=1.0), n))
    out = {"m": m, "n_clients": n, "rounds": rounds}
    for name, kw, m_bound in variants:
        hp = RAgeKConfig(**base, **kw)
        engine = FederatedEngine("mlp", shards, test, hp, seed=0)
        res = engine.run_scanned(rounds, eval_every=rounds)
        out[name] = {
            "schedule": hp.schedule,
            "participation_bound": m_bound,
            "uplink_bytes": res.uplink_bytes[-1],
            "mean_n_active": float(np.mean(res.n_active)),
            "aoi_mean": float(np.mean(res.aoi_mean)),
            "aoi_peak": int(max(res.aoi_peak)),
            "age_mean_final": float(res.age_mean[-1]),
            "age_peak_final": int(res.age_peak[-1]),
            "final_acc": res.acc[-1],
        }
        engine.close()
    out["equal_uplink"] = (out["aoi"]["uplink_bytes"]
                           == out["uniform"]["uplink_bytes"])
    out["aoi_beats_uniform_peak_aoi"] = (out["aoi"]["aoi_peak"]
                                         < out["uniform"]["aoi_peak"])
    return out


def _async_service(shards, test, sync_rounds: int) -> dict:
    """The async PS service plane vs the lockstep engine in VIRTUAL time
    (DESIGN.md §10), on the fig3 config under a straggler-heavy latency
    draw (hetero=1.0: client base speeds span ~e^2x). Both sides price
    time with the SAME LatencyModel: a sync round costs the slowest
    client's dispatch (``sync_round_s``); the async PS aggregates every
    K landings and its clock advances with arrivals. The comparison is
    at EQUAL UPLINK: K divides N*sync_rounds, so the async run lands
    exactly the same number of (identically priced) client updates the
    sync run would."""
    n = len(shards)
    K, V, eta = 5, 4, 0.5                 # K=5 divides N*rounds exactly
    hp = RAgeKConfig(r=75, k=10, H=4, M=20, lr=2e-3, batch_size=64,
                     method="rage_k", buffer_k=K, staleness_eta=eta,
                     version_window=V)
    latency = LatencyModel(n, hetero=1.0, jitter=0.25, seed=0)
    aggs = sync_rounds * n // K
    svc = AsyncService("mlp", shards, test, hp, seed=0, latency=latency)
    res = svc.run_async(aggs, eval_every=aggs)
    s = res.summary()

    # the lockstep engine on the SAME latency draw: round t waits for
    # the slowest client's t-th dispatch
    sync_walls = np.asarray(latency.sync_round_s(jax.random.PRNGKey(0),
                                                 sync_rounds))
    sync_virtual_s = float(sync_walls.sum())
    sync_rps = sync_rounds / sync_virtual_s if sync_virtual_s else 0.0
    # equal-uplink check against the engine's per-client-round ledger
    # (k entries + the r-candidate report, identical per landing)
    per_client = (bytes_per_round(hp.k, svc.d, wire_dtype=hp.wire_dtype)
                  + hp.r * bytes_per_index(svc.d))
    sync_uplink = per_client * n * sync_rounds
    return {
        "buffer_k": K, "version_window": V, "staleness_eta": eta,
        "latency": {"hetero": 1.0, "jitter": 0.25,
                    "base_s": [float(b) for b in np.asarray(
                        latency.base_s)]},
        "aggregations": s["aggregations"],
        "events": s["events"],
        "virtual_s": s["virtual_s"],
        "aggs_per_virtual_s": s["aggs_per_virtual_s"],
        "sync_rounds": sync_rounds,
        "sync_virtual_s": sync_virtual_s,
        "sync_rounds_per_virtual_s": sync_rps,
        "virtual_speedup": (s["aggs_per_virtual_s"] / sync_rps
                            if sync_rps else 0.0),
        "async_beats_sync": s["aggs_per_virtual_s"] > sync_rps,
        "staleness_hist": {str(k_): v for k_, v in
                           res.staleness_hist().items()},
        "staleness_mean": s["staleness_mean"],
        "uplink_bytes": res.uplink_bytes[-1],
        "sync_uplink_bytes": sync_uplink,
        "uplink_matched": res.uplink_bytes[-1] == sync_uplink,
        "downlink_bytes": res.downlink_bytes[-1],
        "wall_aggs_per_s": (s["aggregations"] / s["wall_s"]
                            if s["wall_s"] else 0.0),
        "final_acc": s["final_acc"],
    }


def _active_compute(rounds: int, repeats: int) -> dict:
    """The compute plane (DESIGN.md §11) at scale: a 32-client equal
    split, uniform participation at m ∈ {N, N/4, N/16}, gathered vs
    masked. Two measurements per point:

    * rounds/sec of the scanned driver (interleaved best-of) — the
      wall-clock win of training m rows instead of N;
    * the compiled round's HLO FLOPs (``cost_analysis`` on the jitted
      program) — the structural claim that local-phase cost scales with
      the scheduler's static m bound, independent of machine noise.

    The m=N row runs the masked program (auto: no cut to exploit) and
    doubles as the reference denominator."""
    from repro.launch.hlo_cost import cost_dict

    n, per = 32, 100
    (xtr, ytr), test = mnist_like(n_train=n * per, n_test=500, seed=0)
    shards = [(xtr[i * per:(i + 1) * per], ytr[i * per:(i + 1) * per])
              for i in range(n)]

    def build(m, compute):
        hp = RAgeKConfig(r=75, k=10, H=4, M=rounds + 1, lr=2e-3,
                         batch_size=32, method="rage_k",
                         schedule="uniform", participation_m=m)
        return FederatedEngine("mlp", shards, test, hp, seed=0,
                               compute=compute)

    def flops(engine):
        ns, ms = engine._seg_bounds()
        compiled = engine._round.lower(engine._data, engine._pack(),
                                       num_segments=ns,
                                       max_seg=ms).compile()
        return float(cost_dict(compiled).get("flops", 0.0))

    variants = {"masked_m32": build(n, "masked"),
                "masked_m8": build(n // 4, "masked"),
                "gathered_m8": build(n // 4, "gathered"),
                "gathered_m2": build(n // 16, "gathered")}
    out = {"n_clients": n, "rounds": rounds,
           "m_values": [n, n // 4, n // 16]}
    for name, engine in variants.items():
        out[name] = {"m_bound": engine._scheduler.m_bound,
                     "compute": engine._compute,
                     "round_flops": flops(engine)}
        engine.run_scanned(rounds, eval_every=rounds)   # compile + warm
    best = interleaved_best(
        {name: (lambda e_=engine: e_.run_scanned(rounds,
                                                 eval_every=rounds))
         for name, engine in variants.items()},
        repeats=repeats)
    for name in variants:
        out[name]["rounds_per_s"] = rounds / best[name]
        out[name]["wall_s"] = best[name]
    ref = out["masked_m8"]
    out["speedup_m8"] = (out["gathered_m8"]["rounds_per_s"]
                         / ref["rounds_per_s"])
    out["flops_ratio_m8"] = (out["gathered_m8"]["round_flops"]
                             / ref["round_flops"])
    out["flops_ratio_m2"] = (out["gathered_m2"]["round_flops"]
                             / ref["round_flops"])
    out["gathered_beats_masked_at_m8"] = out["speedup_m8"] > 1.0
    # the structural claim: FLOPs follow the m bound (m/N + the
    # m-independent selection/aggregation tail keeps it below 1/2 at
    # m = N/4)
    out["flops_scale_with_m"] = (
        out["gathered_m2"]["round_flops"]
        < out["gathered_m8"]["round_flops"]
        < ref["round_flops"]) and out["flops_ratio_m8"] < 0.5
    for engine in variants.values():
        engine.close()
    return out


def _age_memory(rounds: int, repeats: int) -> dict:
    """The hierarchical age plane (DESIGN.md §12) on the client axis,
    N ∈ {64, 256, 1024} grouped synthetic shards (few hidden label
    groups, so the every-M DBSCAN actually merges). Two measurements:

    * ``DeviceAgeState.device_bytes`` dense vs hierarchical — at init
      (singletons: both layouts carry N rows) and after the first
      compaction (live C rows; the dense layout never shrinks). The
      ratio should track C/N plus the O(M·m·k) log ring.
    * rounds/sec parity at N=256 — the round programs differ only in
      the O(m·k) log append vs the (N, d) freq scatter, so the layouts
      must tie (the acceptance bar is within 5%).

    Drives ``engine.step()`` directly: ``run()`` would pay the
    per-client eval loop, which is N-unrolled and would drown the
    age-plane signal at N=1024."""
    groups = 4

    def mk(n):
        rng = np.random.default_rng(0)
        shards = []
        for i in range(n):
            lab = i % groups
            x = rng.normal(size=(8, 28 * 28)).astype(np.float32) + lab
            y = np.full((8,), lab, np.int64)
            shards.append((x, y))
        xte = rng.normal(size=(64, 28 * 28)).astype(np.float32)
        yte = rng.integers(0, 10, size=(64,)).astype(np.int64)
        return shards, (xte, yte)

    def build(n, layout, M):
        hp = RAgeKConfig(method="rage_k", age_layout=layout, r=16, k=4,
                         H=1, M=M, lr=2e-3, batch_size=8)
        shards, test = mk(n)
        return FederatedEngine("mlp", shards, test, hp, seed=0)

    out = {"n_values": [64, 256, 1024], "window_M": 3, "groups": groups}
    for n in out["n_values"]:
        eng = build(n, "hierarchical", M=3)
        init_b = eng.age.device_bytes
        dense_b = DeviceAgeState.create(eng.d, n).device_bytes
        for _ in range(3):
            eng.step()                 # 3rd step crosses the boundary
        c = int(eng.cluster_of.max()) + 1
        hier_b = eng.age.device_bytes
        out[f"n{n}"] = {"dense_bytes": dense_b,
                        "hier_bytes_init": init_b,
                        "hier_bytes_compacted": hier_b,
                        "live_clusters": c,
                        "c_over_n": c / n,
                        "bytes_ratio_vs_dense": hier_b / dense_b}
        eng.close()
    out["shrinks_with_c"] = (
        out["n1024"]["bytes_ratio_vs_dense"]
        < out["n256"]["bytes_ratio_vs_dense"] < 1.0)

    # rounds/sec parity at N=256; M past the total step count keeps the
    # boundary (and its layout-specific host work) out of the timed
    # window — that cost is priced by comm_table's clustering_input row
    n = 256
    total = 2 + rounds * repeats + 1
    engines = {lay: build(n, lay, M=total + 1)
               for lay in ("dense", "hierarchical")}
    for e in engines.values():
        for _ in range(2):
            e.step()                               # compile + warm
    best = interleaved_best(
        {lay: (lambda e_=e: [e_.step() for _ in range(rounds)])
         for lay, e in engines.items()},
        repeats=repeats)
    rps = {lay: rounds / best[lay] for lay in engines}
    out["n256_rounds_per_s"] = rps
    out["parity_ratio"] = rps["hierarchical"] / rps["dense"]
    out["parity_within_5pct"] = out["parity_ratio"] > 0.95
    for e in engines.values():
        e.close()
    return out


def _resilience(shards, test, rounds: int, repeats: int,
                acc_rounds: int) -> dict:
    """The resilience plane (DESIGN.md §13), two measurements:

    * CHECKPOINT OVERHEAD: the scanned fig3 run with ckpt_every ∈
      {0, 1, 4}, saving the complete round state (params, opt state,
      ages, sampler, PRNG) through the AsyncCheckpointer's worker
      thread vs blocking in-line writes. The async writer only pays
      the device_get snapshot on the driver thread; at every-4 it must
      stay within 10% of the no-checkpoint rounds/sec.
    * NaN-RATE GRID: final accuracy under fault injection at p_nan ∈
      {0, 0.05, 0.2}, validation gate on vs off. Gate-off lets a
      single non-finite update poison the global params (every later
      loss is NaN); gate-on quarantines those rows — eq.-2 ages keep
      counting, so the coordinates are re-solicited — and the run must
      end finite and beat gate-off at the worst rate."""
    import os
    import shutil
    import tempfile

    from repro.checkpoint import AsyncCheckpointer
    from repro.fl import FaultModel

    hp = RAgeKConfig(r=75, k=10, H=4, M=20, lr=2e-3, batch_size=64,
                     method="rage_k")
    # round count aligned to the every-4 cadence so each timed segment
    # sees the SAME chunk split (4,4,...) — misaligned segments would
    # shift the split every repeat and compile new chunk lengths inside
    # the timed region
    ck_rounds = max(8, rounds - rounds % 4)
    out = {"rounds": ck_rounds, "keep": 2}

    tmp = tempfile.mkdtemp(prefix="bench_ckpt_")
    variants = {"none": (0, None),
                "async_every4": (4, False),
                "async_every1": (1, False),
                "blocking_every4": (4, True),
                "blocking_every1": (1, True)}
    engines = {}
    for name, (every, blocking) in variants.items():
        eng = FederatedEngine("mlp", shards, test, hp, seed=0)
        ck = (None if blocking is None else
              AsyncCheckpointer(os.path.join(tmp, name), keep=2,
                                blocking=blocking))
        # warm with the SAME ckpt cadence: compiles this variant's
        # chunk lengths and leaves a write in flight to join, as in
        # steady state
        eng.run_scanned(ck_rounds, eval_every=ck_rounds,
                        checkpointer=ck, ckpt_every=every)
        engines[name] = (eng, ck, every)
    best = interleaved_best(
        {name: (lambda e_=eng, c_=ck, ev_=every:
                e_.run_scanned(ck_rounds, eval_every=ck_rounds,
                               checkpointer=c_, ckpt_every=ev_))
         for name, (eng, ck, every) in engines.items()},
        repeats=repeats)
    ref = ck_rounds / best["none"]
    for name, (every, blocking) in variants.items():
        rps = ck_rounds / best[name]
        out[name] = {"ckpt_every": every, "blocking": bool(blocking),
                     "rounds_per_s": rps, "wall_s": best[name],
                     "overhead_frac": max(0.0, 1.0 - rps / ref)}
    out["async_every4_within_10pct"] = (
        out["async_every4"]["rounds_per_s"] >= 0.9 * ref)
    for eng, ck, _ in engines.values():
        if ck is not None:
            ck.close()
        eng.close()
    shutil.rmtree(tmp, ignore_errors=True)

    n = len(shards)
    grid = []
    for p in (0.0, 0.05, 0.2):
        row = {"p_nan": p}
        for gate in (True, False):
            flt = FaultModel(n=n, p_nan=p, seed=11) if p else None
            eng = FederatedEngine("mlp", shards, test, hp, seed=0,
                                  faults=flt, quarantine=gate)
            res = eng.run_scanned(acc_rounds, eval_every=acc_rounds)
            row["gate_on" if gate else "gate_off"] = {
                "final_acc": res.acc[-1],
                "final_loss_finite": bool(np.isfinite(res.loss[-1])),
                "quarantined": int(sum(res.n_quarantined)),
            }
            eng.close()
        grid.append(row)
    out["acc_rounds"] = acc_rounds
    out["nan_grid"] = grid
    worst = grid[-1]
    out["gate_rescues_worst_case"] = (
        worst["gate_on"]["final_loss_finite"]
        and worst["gate_on"]["final_acc"]
        > worst["gate_off"]["final_acc"])
    return out


def main(fast: bool = True):
    # 5-round smoke for CI; more repeats because short walls are noisy
    rounds, repeats = (5, 9) if fast else (20, 5)
    (xtr, ytr), test = mnist_like(n_train=2_000, n_test=500, seed=0)
    shards = paper_mnist_split(xtr, ytr)
    # fig3 MNIST config (CPU-reduced data, paper r/k/H/M)
    hp = RAgeKConfig(r=75, k=10, H=4, M=20, lr=2e-3, batch_size=64,
                     method="rage_k")

    # one warmed engine per variant; repeats interleaved (best-of) so
    # machine noise hits all variants alike and the systematic per-round
    # dispatch savings aren't drowned by scheduler jitter
    runs = {}
    for name, driver, sel in VARIANTS:
        engine = FederatedEngine("mlp", shards, test, hp, seed=0,
                                 selection=sel)
        run = engine.run if driver == "step" else engine.run_scanned
        run(rounds, eval_every=rounds)                # compile + warm
        runs[name] = (engine, run)
    best = interleaved_best(
        {name: (lambda r_=run: r_(rounds, eval_every=rounds))
         for name, (engine, run) in runs.items()},
        repeats=repeats)

    out = {"config": {"rounds": rounds, "repeats": repeats,
                      "method": hp.method, "r": hp.r, "k": hp.k,
                      "H": hp.H, "M": hp.M, "batch_size": hp.batch_size}}
    rows = []
    for name, driver, sel in VARIANTS:
        m = {"rounds_per_s": rounds / best[name],
             "wall_s": best[name], "driver": driver, "selection": sel}
        out[name] = m
        rows.append((f"engine_{name}", 1e6 / m["rounds_per_s"],
                     f"rounds_per_s={m['rounds_per_s']:.2f}"))
    speedup = out["scan"]["rounds_per_s"] / out["step"]["rounds_per_s"]
    out["scan_speedup"] = speedup
    out["selection_speedup"] = (out["scan"]["rounds_per_s"]
                                / out["scan_seqsel"]["rounds_per_s"])

    # async-recluster overlap (ROADMAP lever): the hidden host time
    out["recluster_overlap"] = _recluster_overlap(
        shards, test, rounds, max(repeats // 3, 2))
    hid = out["recluster_overlap"]["scan"]
    rows.append(("recluster_hidden_scan", hid["recluster_hidden_s"] * 1e6,
                 f"hidden_frac={hid['hidden_fraction']:.3f};"
                 f"dbscan_s={hid['recluster_s']:.4f}"))

    # participation plane (DESIGN.md §9): the AoI/uplink trade-off
    out["participation"] = part = _participation(
        shards, test, 16 if fast else 40)
    rows.append(("participation_peak_aoi", 0.0,
                 f"aoi={part['aoi']['aoi_peak']} "
                 f"uniform={part['uniform']['aoi_peak']} "
                 f"(m={part['m']}, equal_uplink={part['equal_uplink']}, "
                 f"aoi_beats_uniform="
                 f"{part['aoi_beats_uniform_peak_aoi']})"))

    # async service plane (DESIGN.md §10): virtual-time throughput at
    # equal uplink under the straggler-heavy draw
    out["async_service"] = asv = _async_service(
        shards, test, 10 if fast else 40)
    rows.append(("async_aggs_per_virtual_s",
                 1e6 / max(asv["aggs_per_virtual_s"], 1e-9),
                 f"async={asv['aggs_per_virtual_s']:.3f}/s "
                 f"sync={asv['sync_rounds_per_virtual_s']:.3f}/s "
                 f"x{asv['virtual_speedup']:.2f} "
                 f"(K={asv['buffer_k']}, "
                 f"stale_mean={asv['staleness_mean']:.2f}, "
                 f"uplink_matched={asv['uplink_matched']})"))

    # compute plane (DESIGN.md §11): gathered vs masked at m < N
    out["active_compute"] = ac = _active_compute(
        rounds, max(repeats // 3, 2))
    rows.append(("active_compute_m8",
                 1e6 / max(ac["gathered_m8"]["rounds_per_s"], 1e-9),
                 f"gathered={ac['gathered_m8']['rounds_per_s']:.2f}/s "
                 f"masked={ac['masked_m8']['rounds_per_s']:.2f}/s "
                 f"x{ac['speedup_m8']:.2f} "
                 f"(flops_ratio={ac['flops_ratio_m8']:.3f}, "
                 f"scales={ac['flops_scale_with_m']})"))

    # age plane (DESIGN.md §12): device bytes vs N, parity at 256
    out["age_memory"] = am = _age_memory(rounds, max(repeats // 3, 2))
    rows.append(("age_memory_n1024",
                 1e6 / max(am["n256_rounds_per_s"]["hierarchical"], 1e-9),
                 f"bytes={am['n1024']['hier_bytes_compacted']}/"
                 f"{am['n1024']['dense_bytes']} "
                 f"(C={am['n1024']['live_clusters']}/1024, "
                 f"ratio={am['n1024']['bytes_ratio_vs_dense']:.3f}); "
                 f"parity@256={am['parity_ratio']:.3f} "
                 f"within5pct={am['parity_within_5pct']}"))

    # resilience plane (DESIGN.md §13): ckpt overhead + NaN-rate grid
    out["resilience"] = rs = _resilience(
        shards, test, rounds, max(repeats // 3, 2), 16 if fast else 40)
    rows.append(("resilience_ckpt_every4",
                 1e6 / max(rs["async_every4"]["rounds_per_s"], 1e-9),
                 f"overhead={rs['async_every4']['overhead_frac']:.3f} "
                 f"(blocking={rs['blocking_every4']['overhead_frac']:.3f}"
                 f", within10pct={rs['async_every4_within_10pct']}, "
                 f"gate_rescues={rs['gate_rescues_worst_case']})"))

    save_json("BENCH_engine", out)
    rows.append(("engine_scan_speedup", 0.0, f"x{speedup:.2f}"))
    rows.append(("engine_selection_speedup", 0.0,
                 f"x{out['selection_speedup']:.2f}"))
    return rows


if __name__ == "__main__":
    for r in main(fast=False):
        print(r)
