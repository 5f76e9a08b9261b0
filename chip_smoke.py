"""Smoke test of the federated engine on one TPU chip (or of the sharded
exchange on four).

    python chip_smoke.py             # one chip: the CIFAR-CNN federation
    python chip_smoke.py --chips 4   # four chips: the sparse_sync exchange

One chip: builds the federation exactly as ``repro.launch.fl_train`` does
for ``--dataset cifar --paper-hparams`` (Table I Network 2 at its full
2,515,338 parameters; the paper's CIFAR split into N=6 clients, two per
label set; r=2500, k=100; the scan driver), with the recluster period cut
to M=2 so that four rounds include two every-M reclusters and the repacks
of the segmented selection that follow. It then
checks that the losses are finite, that the round program holds the three
main-path Pallas kernels as compiled TPU custom calls, and that a round run
with the jnp aggregation (and selection) from the same state picks the same
indices, leaves the same ages and bills the same uplink. Each kernel is
also held to its jnp reference at the federation's shapes.

Four chips: runs ``dist.sparse_sync.make_manual_sync`` on a (data=4,
model=1) mesh over the CIFAR-CNN gradients of four different batches, one
per chip, and compares it with the same exchange run shard by shard on one
chip and union-summed.

Exits non-zero without a TPU. The last line of standard output is one JSON
object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch import fl_train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

FEDERATION = ["--dataset", "cifar", "--paper-hparams", "--M", "2",
              "--seed", "0"]
ROUNDS = 4
KERNELS = ("maghist_batch", "segmented_age_topk", "sparse_aggregate")
EPS32 = float(np.finfo(np.float32).eps)


def require_tpu(chips: int) -> dict:
    """The device JAX reports; anything but a TPU with enough chips ends
    the run before any result is printed."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def compiled_kernels(hlo: str) -> set:
    """Names of the Pallas kernels compiled into an HLO module as TPU
    custom calls (interpret mode leaves none)."""
    calls = re.findall(
        r'%([\w\-]+?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        hlo)
    return set(calls)


def assert_kernels_compiled(hlo: str) -> None:
    found = compiled_kernels(hlo)
    print(f"round program tpu_custom_call kernels: {sorted(found)}")
    check(set(KERNELS) <= found,
          f"round program lacks compiled kernels "
          f"{sorted(set(KERNELS) - found)}")


def _aggregate_tol(idx: np.ndarray, vals: np.ndarray, d: int) -> np.ndarray:
    """Per-coordinate bound on a reordered f32 sum: (terms) * eps * the
    sum of the magnitudes landing there."""
    flat = idx.reshape(-1)
    keep = flat < d
    mag = np.zeros(d)
    np.add.at(mag, flat[keep], np.abs(vals.reshape(-1)[keep]))
    terms = np.bincount(flat[keep], minlength=d)
    return terms * EPS32 * mag


def kernel_checks(engine, idx: np.ndarray, seed: int) -> None:
    """Each main-path kernel against its jnp reference on the chip, at the
    federation's shapes: sparse_aggregate on the round's own selected
    indices, maghist_batch (and the candidate report built on it) on an
    (N, d) gradient-like matrix. segmented_age_topk is held to its
    reference by the pallas-vs-jnp round in :func:`federation_phase`."""
    from repro.fl.server import aggregate_sparse_fused
    from repro.kernels import maghist as MH
    from repro.kernels import ops

    n, d, r = engine.n, engine.d, engine.hp.r
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    vals = jax.random.normal(k1, idx.shape, jnp.float32)
    age = jax.random.randint(k2, (d,), 0, 100, jnp.int32)
    dp, ap = aggregate_sparse_fused(jnp.asarray(idx), vals, age,
                                    impl="pallas")
    dj, aj = aggregate_sparse_fused(jnp.asarray(idx), vals, age, impl="jnp")
    err = np.abs(np.asarray(dp) - np.asarray(dj))
    tol = _aggregate_tol(idx, np.asarray(vals), d)
    print(f"sparse_aggregate vs jnp: max |diff| {err.max():.3e}, "
          f"bound (terms * eps * sum|v|) max {tol.max():.3e}")
    check(bool(np.all(err <= tol)), "sparse_aggregate sum outside its bound")
    check(bool(np.array_equal(np.asarray(ap), np.asarray(aj))),
          "sparse_aggregate ages differ from jnp")

    G = jax.random.normal(k3, (n, d), jnp.float32) * jnp.exp2(
        jax.random.randint(k3, (n, d), -12, 8).astype(jnp.float32))
    hist = np.array(ops.maghist_batch(G))
    want = np.asarray(MH.hist_rows(G))
    pad = hist.sum(1) - d          # the zero padding lands in bin 0
    hist[:, 0] -= pad
    check(bool(np.array_equal(hist, want)),
          "maghist_batch histograms differ from jnp")
    check(bool(np.array_equal(
        np.asarray(ops.threshold_topk_batch(G, r, hist_impl="pallas")),
        np.asarray(ops.threshold_topk_batch(G, r, hist_impl="jnp")))),
        "threshold candidate report (pallas hist) differs from jnp")
    print("maghist_batch vs jnp: histograms and top-r report identical")


def federation_phase(argv: list, rounds: int) -> None:
    """Drive the fl_train federation ``argv`` describes for ``rounds``
    rounds on the scan driver, then one more round each with the engine's
    own aggregation and with the jnp one, from the same state."""
    parser = fl_train.build_parser()
    args = parser.parse_args(argv)
    engine = fl_train.build_engine(args)
    hp = engine.hp
    print(f"federation: {engine.kind} d={engine.d} N={engine.n} r={hp.r} "
          f"k={hp.k} H={hp.H} batch={hp.batch_size} M={hp.M} "
          f"aggregate={engine._agg_impl} candidates={hp.candidates}")
    res = None
    for _ in range(rounds):
        t0 = time.perf_counter()
        res = engine.run_scanned(1, eval_every=1, result=res)
        wall = time.perf_counter() - t0
        print(f"round {engine.round_idx}: loss={res.loss[-1]:.6f} "
              f"uplink_bytes={res.uplink_bytes[-1]} "
              f"wall_s={wall:.3f} (host clock, compiles included)")
        check(bool(np.isfinite(res.loss[-1])), "non-finite loss")
        if engine.round_idx % hp.M == 0:
            print(f"  recluster: labels {engine.cluster_of.tolist()}, "
                  f"segmented packing (C, S) = {engine._seg_bounds()}")
    check(sum(res.n_quarantined) == 0, "updates quarantined as non-finite")

    # the check round: the engine's own round program, compiled ahead of
    # time so its HLO can be read, against the jnp engine's round
    data, carry = engine._data, engine.state_tree()["carry"]
    ns, ms = engine._seg_bounds()
    compiled = engine._round.lower(data, carry, num_segments=ns,
                                   max_seg=ms).compile()
    assert_kernels_compiled(compiled.as_text())
    carry_p, m_p = compiled(data, carry)
    ref = fl_train.build_engine(parser.parse_args(argv + ["--aggregate",
                                                          "jnp"]))
    carry_j, m_j = ref._round(data, carry, num_segments=ns, max_seg=ms)

    losses = np.asarray(m_p["losses"])
    check(bool(np.all(np.isfinite(losses))), "non-finite client loss")
    check(bool(np.array_equal(losses, np.asarray(m_j["losses"]))),
          "local phase differs between the two rounds")
    idx = np.asarray(m_p["idx"])
    check(bool(np.array_equal(idx, np.asarray(m_j["idx"]))),
          "selected indices differ from the jnp round")
    for name, a, b in zip(carry_p[5]._fields, carry_p[5], carry_j[5]):
        if a is not None:
            check(bool(np.array_equal(np.asarray(a), np.asarray(b))),
                  f"ages ({name}) differ from the jnp round")
    up_p = engine._per_client_bytes * int(m_p["n_active"])
    up_j = ref._per_client_bytes * int(m_j["n_active"])
    check(up_p == up_j, f"uplink differs: {up_p} vs {up_j}")
    dparam = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(carry_p[0]),
        jax.tree_util.tree_leaves(carry_j[0])))
    print(f"check round {engine.round_idx + 1}: {engine._agg_impl} vs jnp: "
          f"selected indices, ages and uplink ({up_p} B) identical; "
          f"losses {losses.tolist()}; max |global param diff| {dparam:.3e}")
    kernel_checks(engine, idx, args.seed)
    ref.close()
    engine.close()
    print(f"final clusters: {res.cluster_labels[-1].tolist()}")


def exchange_phase(n_shards: int, batch: int, *, r: int = 2500,
                   k: int = 100, seed: int = 0) -> None:
    """make_manual_sync on a (data=n_shards, model=1) mesh over the CIFAR
    CNN's gradients of ``n_shards`` different batches, one per device,
    against the same exchange run shard by shard on one device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.data.synthetic import cifar10_like
    from repro.dist.sparse_sync import make_manual_sync
    from repro.fl.client import softmax_xent
    from repro.launch.mesh import make_host_mesh
    from repro.models import paper_nets as PN

    mesh = make_host_mesh(n_shards, 1)
    devices = list(mesh.devices.flat)
    params, state = PN.cnn_init(jax.random.PRNGKey(seed))
    (x, y), _ = cifar10_like(n_train=n_shards * batch, n_test=1, seed=seed)

    def loss(p, xb, yb):
        logits, _ = PN.cnn_apply(p, state, xb, train=True)
        return softmax_xent(logits, yb)

    grad = jax.jit(jax.grad(loss))
    per_shard = []
    for i, dev in enumerate(devices):
        sl = slice(i * batch, (i + 1) * batch)
        per_shard.append(grad(jax.device_put(params, dev),
                              jax.device_put(x[sl], dev),
                              jax.device_put(y[sl], dev)))
    rep = NamedSharding(mesh, P())
    # each device holds ITS OWN gradient under the replicated spec: the
    # exchange's input is every shard's local view
    grads = jax.tree_util.tree_map(
        lambda *g: jax.make_array_from_single_device_arrays(
            g[0].shape, rep, list(g)), *per_shard)
    specs = jax.tree_util.tree_map(lambda _: P(), params)
    shapes = jax.tree_util.tree_map(
        lambda g: jax.ShapeDtypeStruct(g.shape, g.dtype), params)
    ages = jax.tree_util.tree_map(
        lambda p: jax.random.randint(jax.random.PRNGKey(seed + 1), p.shape,
                                     0, 50, jnp.int32), params)

    for leaf in jax.tree_util.tree_leaves(grads):
        held = {s.device for s in leaf.addressable_shards}
        check(held == set(devices), f"gradient shards on {held}, "
                                    f"not on the mesh's {set(devices)}")
    leaf0 = jax.tree_util.tree_leaves(grads)[-1]
    views = {s.device: np.asarray(s.data) for s in leaf0.addressable_shards}
    check(all(not np.array_equal(views[devices[0]], views[dv])
              for dv in devices[1:]),
          "the per-device gradients are not distinct")

    sync = jax.jit(make_manual_sync(mesh, specs, shapes, method="rage_k",
                                    r=r, k=k))
    t0 = time.perf_counter()
    synced, new_ages, stats = sync(grads, ages)
    jax.block_until_ready(synced)
    print(f"exchange on {n_shards} devices: wall_s="
          f"{time.perf_counter() - t0:.3f} (host clock, compile included), "
          f"wire_bytes_total={int(stats['wire_bytes_total'])}")
    for leaf in jax.tree_util.tree_leaves(synced):
        held = {s.device for s in leaf.addressable_shards}
        check(held == set(devices), f"synced result held on {held}")

    # reference: each shard's exchange alone on one device, union-summed
    one = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    sync1 = jax.jit(make_manual_sync(one, specs, shapes, method="rage_k",
                                     r=r, k=k))
    on0 = lambda t: jax.device_put(t, devices[0])  # noqa: E731
    ages0 = on0(ages)
    parts = [sync1(on0(g), ages0)[:2] for g in per_shard]
    n_diff = 0
    for path_leaves in zip(jax.tree_util.tree_leaves(synced),
                           jax.tree_util.tree_leaves(new_ages),
                           *[jax.tree_util.tree_leaves(p[0]) for p in parts],
                           *[jax.tree_util.tree_leaves(p[1]) for p in parts]):
        got, got_age = (np.asarray(a) for a in path_leaves[:2])
        s_i = [np.asarray(a, np.float64) for a in
               path_leaves[2:2 + n_shards]]
        a_i = [np.asarray(a) for a in path_leaves[2 + n_shards:]]
        want = sum(s_i) / n_shards
        tol = n_shards * EPS32 * sum(np.abs(s) for s in s_i) / n_shards
        n_diff += int(np.sum(np.abs(got - want) > tol))
        check(bool(np.array_equal(got_age, np.minimum.reduce(a_i))),
              "synced ages differ from the one-device union")
    check(n_diff == 0, f"{n_diff} synced gradient entries outside the f32 "
                       f"sum-order bound")
    nnz = sum(int(np.count_nonzero(np.asarray(a)))
              for a in jax.tree_util.tree_leaves(synced))
    print(f"exchange vs one-device union: synced gradients within "
          f"(shards * eps * sum|v|), ages identical; {nnz} nonzero "
          f"coordinates in the union")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the federation on one chip; 4: the sharded "
                         "exchange across four chips and its one-chip "
                         "comparison, and nothing else")
    args = ap.parse_args(argv)
    device = require_tpu(args.chips)
    use_compile_cache()
    if args.chips == 4:
        exchange_phase(4, batch=64)
    else:
        federation_phase(FEDERATION, ROUNDS)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
