"""FederatedEngine — the unified federated-round API (paper Algorithm 1).

One global round is ONE jitted device program: batch draw from the
device-resident shard store, local phase (H vmapped client steps),
candidate top-r, age-based index selection, sparse aggregation, global
update, broadcast. The parameter server's age state lives on DEVICE as a
jnp pytree (``DeviceAgeState``): per-cluster age vectors (eq. 2),
per-client request frequencies (eq. 3 inputs), and the cluster
assignment. Client data lives on device too (``data.DeviceShardStore``,
uploaded once at construction); per-round batches come from PRNG-derived
permutations inside the program, so a round consumes NO host input.

Two drivers share the identical round body (``_round_impl``):

  * :meth:`step` / :meth:`run` — one dispatch per round, metrics pulled
    every round (host-paced; the debugging/inspection driver);
  * :meth:`run_scanned` — chunks of rounds executed as one ``lax.scan``
    per dispatch, chunk boundaries aligned to the every-``M`` recluster
    host round-trip (and eval/heatmap rounds), metrics stacked on device
    and pulled ONCE per chunk. Bit-identical to repeated :meth:`step`
    (pinned by tests/test_scan_driver.py, which also wraps a chunk in
    ``jax.transfer_guard("disallow")``).

Only two things ever cross to host:

  * per-round metrics — losses (N,), requested indices (N, k) — pulled
    per round (step) or per chunk (scan);
  * the every-M DBSCAN input (eq. 3) — the one genuinely host-shaped
    step: the whole (N, d) int32 frequency matrix under
    ``age_layout='dense'``, or just the bounded sparse update log
    (O(m_bound·k·M) int32) under ``'hierarchical'``, from which the
    host rebuilds the identical matrix (DESIGN.md §12).

The dense (N, d) float gradient matrix never leaves the accelerator
(pinned by tests/test_engine_golden.py). Method dispatch goes through
``core.strategies`` batched protocol (``select_batch``) — a new
selection rule is a new Strategy, not a new ``elif``.
``fl.simulation.run_fl`` is a thin compatibility wrapper.

The rAge-k selection plane has two implementations (DESIGN.md §7):

  * ``selection='segmented'`` (default) — the per-cluster parallel
    formulation: clients grouped by cluster on device, clusters padded
    to the largest live cluster, the in-cluster disjointness recursion
    scans member positions (max cluster size, not N) and clusters run
    in parallel (:func:`rage_select_segmented`);
  * ``selection='scan'`` — the sequential all-clients ``lax.scan``
    reference (:func:`rage_select`), kept reachable for A/B debugging.

Both are bit-identical (tests/test_segmented_selection.py); the static
packing bounds (live cluster count, max cluster size) come from the
host-side DBSCAN labels at every recluster — no extra transfer.

WHO takes part in a round is the participation plane's decision
(``fl.schedule``, DESIGN.md §9): every round the engine asks its
``Scheduler`` for a ``RoundPlan`` ((N,) active mask, per-client
staleness, aggregation weights) and applies it uniformly across
strategies — non-participants' local state holds, they contribute
nothing, and their ages keep growing (eq. (2), no reset). The
scheduler's state (PRNG key, device round counter, client AoI) threads
through the scan carry; ``schedule='full'`` (default) is bit-identical
to the pre-plane engine.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint.io import load_checkpoint
from repro.configs.base import RAgeKConfig
from repro.core.age import AgeState
from repro.core.clustering import (cluster_clients, connectivity_matrix,
                                   fold_request_log)
from repro.core.compression import bytes_per_index, bytes_per_round
from repro.core.strategies import (CANDIDATE_IMPLS, client_candidates,
                                   make_strategy, segmented_rage_select)
from repro.data.pipeline import DeviceShardStore
from repro.fl import client as C
from repro.fl.schedule import RoundPlan, SchedState, make_scheduler
from repro.fl.server import aggregate_sparse, aggregate_sparse_fused
from repro.models import paper_nets as P
from repro.optim.optimizers import adam, sgd, apply_updates


class DeviceAgeState(NamedTuple):
    """PS age state as a device pytree (threaded through the jitted round).

    Two layouts share this container (``age_layout='dense'|
    'hierarchical'``, DESIGN.md §12). In BOTH, ``cluster_age`` rows are
    keyed by CLUSTER id — eq. (2) makes ages cluster-shared, so a
    per-client row never existed; the dense layout merely allocates the
    static bound N rows (every client its own singleton), while the
    hierarchical one re-allocates exactly the live-cluster count at
    every recluster boundary and keeps only O(N) per-client metadata:

    field        dense                hierarchical
    -----------  -------------------  ---------------------------------
    cluster_age  (N, d) int32         (C_max, d) int32 — C_max is the
                 rows >= live count   live cluster count, a STATIC
                 stay zero            bound recomputed per recluster
                                      (like the packing bounds)
    freq         (N, d) int32         None — replaced by the sparse
                 (eq. 3 inputs)       update log below; the host keeps
                                      the cumulative matrix
    cluster_of   (N,) int32           (N,) int32 (unchanged)
    cost         None                 cafe only: (N, d) int32 CAFe
                                      per-coordinate upload-cost rows
                                      (cafe clusters stay singletons,
                                      so these are already
                                      cluster-keyed; dense stores them
                                      in ``freq``)
    upload_cost  None                 (N,) int32 — cumulative uploaded
                                      entries per client, the O(N)
                                      scalar cost signal (CAFe-style
                                      solicitation / cost-aware
                                      scheduling reads this, never the
                                      dense matrix)
    log_idx      None                 (L, m_bound, k) int32 ring of the
                                      per-round requested indices
                                      (sentinel d = no request)
    log_mem      None                 (L, m_bound) int32 requesting
                                      client ids (sentinel N = padded
                                      participant slot)
    log_ptr      None                 () int32 — MONOTONE write
                                      pointer; the host tracks how far
                                      it has drained (ring length L
                                      covers one recluster window)

    The log replaces the dense ``freq`` as the every-M DBSCAN input:
    O(m_bound·k·L) device memory and boundary pull instead of O(N·d)
    (``core.clustering.fold_request_log`` rebuilds the identical
    matrix host-side).
    """

    cluster_age: jnp.ndarray
    freq: jnp.ndarray | None
    cluster_of: jnp.ndarray
    cost: jnp.ndarray | None = None
    upload_cost: jnp.ndarray | None = None
    log_idx: jnp.ndarray | None = None
    log_mem: jnp.ndarray | None = None
    log_ptr: jnp.ndarray | None = None

    @classmethod
    def create(cls, d: int, n_clients: int) -> "DeviceAgeState":
        """Dense layout at t=0: ``n_clients`` singleton cluster rows
        (the first axis holds CLUSTER rows that happen to coincide with
        client ids until a recluster merges some) plus the dense (N, d)
        frequency matrix."""
        return cls(cluster_age=jnp.zeros((n_clients, d), jnp.int32),
                   freq=jnp.zeros((n_clients, d), jnp.int32),
                   cluster_of=jnp.arange(n_clients, dtype=jnp.int32))

    @classmethod
    def create_hierarchical(cls, d: int, n_clients: int, *,
                            log_len: int = 0, m_bound: int = 0,
                            k: int = 0,
                            with_cost: bool = False) -> "DeviceAgeState":
        """Hierarchical layout at t=0: singleton clusters, so C_max
        starts at N and shrinks at the first merging recluster.
        ``log_len``/``m_bound``/``k`` size the sparse update log ring
        (log_len=0 — methods that never recluster — allocates no log);
        ``with_cost`` adds the CAFe per-coordinate cost rows."""
        log = log_len > 0
        return cls(
            cluster_age=jnp.zeros((n_clients, d), jnp.int32),
            freq=None,
            cluster_of=jnp.arange(n_clients, dtype=jnp.int32),
            cost=(jnp.zeros((n_clients, d), jnp.int32) if with_cost
                  else None),
            upload_cost=jnp.zeros((n_clients,), jnp.int32),
            log_idx=(jnp.full((log_len, m_bound, k), d, jnp.int32)
                     if log else None),
            log_mem=(jnp.full((log_len, m_bound), n_clients, jnp.int32)
                     if log else None),
            log_ptr=jnp.int32(0) if log else None)

    @property
    def device_bytes(self) -> int:
        """Device bytes of the age plane (every array leaf) — the
        quantity the hierarchical layout shrinks ~C/N."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(self))


@dataclass
class FLResult:
    rounds: list = field(default_factory=list)       # global round index
    loss: list = field(default_factory=list)
    acc: list = field(default_factory=list)
    uplink_bytes: list = field(default_factory=list) # cumulative
    cluster_labels: list = field(default_factory=list)
    heatmaps: dict = field(default_factory=dict)     # round -> (N,N)
    requested: list = field(default_factory=list)    # per round: (N,k)|None
    # participation-plane metrics, one entry per ROUND (DESIGN.md §9):
    # client-level AoI (rounds since the PS last heard from each client)
    # and the coordinate-level cluster_age field (max/mean over live rows)
    n_active: list = field(default_factory=list)     # participants
    aoi_mean: list = field(default_factory=list)
    aoi_peak: list = field(default_factory=list)
    age_mean: list = field(default_factory=list)     # over cluster_age
    age_peak: list = field(default_factory=list)     # max over cluster_age
    # resilience-plane counters, one entry per ROUND (DESIGN.md §13):
    # updates quarantined by the validation gate, clients crashed by the
    # fault model, wire-dropped updates (all zero when faults are off)
    n_quarantined: list = field(default_factory=list)
    n_crashed: list = field(default_factory=list)
    n_dropped: list = field(default_factory=list)
    wall_s: float = 0.0

    def summary(self) -> dict:
        return {
            "final_acc": self.acc[-1] if self.acc else float("nan"),
            "final_loss": self.loss[-1] if self.loss else float("nan"),
            "total_uplink_mb": (self.uplink_bytes[-1] / 2**20
                                if self.uplink_bytes else 0.0),
            "peak_aoi": max(self.aoi_peak) if self.aoi_peak else 0.0,
            "mean_aoi": (float(np.mean(self.aoi_mean))
                         if self.aoi_mean else 0.0),
            "peak_coord_age": (max(self.age_peak)
                               if self.age_peak else 0.0),
            "total_quarantined": int(sum(self.n_quarantined)),
            "total_crashed": int(sum(self.n_crashed)),
            "total_dropped": int(sum(self.n_dropped)),
            "wall_s": self.wall_s,
        }


def _result_to_json(res: FLResult) -> dict:
    """FLResult -> a JSON-able dict rode along in the checkpoint meta
    (DESIGN.md §13): Python floats round-trip JSON exactly (repr is the
    shortest round-trip), so a resumed run's final curves JSON can be
    BYTE-equal to the uninterrupted run's."""
    return {
        "rounds": list(res.rounds), "loss": list(res.loss),
        "acc": list(res.acc), "uplink_bytes": list(res.uplink_bytes),
        "cluster_labels": [np.asarray(c).tolist()
                           for c in res.cluster_labels],
        "heatmaps": {str(t): np.asarray(h).tolist()
                     for t, h in res.heatmaps.items()},
        "requested": [None if r is None else np.asarray(r).tolist()
                      for r in res.requested],
        "n_active": list(res.n_active), "aoi_mean": list(res.aoi_mean),
        "aoi_peak": list(res.aoi_peak), "age_mean": list(res.age_mean),
        "age_peak": list(res.age_peak),
        "n_quarantined": list(res.n_quarantined),
        "n_crashed": list(res.n_crashed),
        "n_dropped": list(res.n_dropped),
    }


def _result_from_json(d: dict | None) -> FLResult:
    res = FLResult()
    if not d:
        return res
    for k in ("rounds", "loss", "acc", "uplink_bytes", "n_active",
              "aoi_mean", "aoi_peak", "age_mean", "age_peak",
              "n_quarantined", "n_crashed", "n_dropped"):
        setattr(res, k, list(d[k]))
    res.cluster_labels = [np.asarray(c, np.int64)
                          for c in d["cluster_labels"]]
    res.heatmaps = {int(t): np.asarray(h)
                    for t, h in d["heatmaps"].items()}
    res.requested = [None if r is None else np.asarray(r, np.int32)
                     for r in d["requested"]]
    return res


def _build_model(kind: str, key):
    if kind == "mlp":
        params = P.mlp_init(key)
        state: dict = {}

        def apply_loss(params, state, batch):
            x, y = batch
            logits = P.mlp_apply(params, x)
            return C.softmax_xent(logits, y), state

        def predict(params, state, x):
            return P.mlp_apply(params, x)
        return params, state, apply_loss, predict
    if kind == "cnn":
        params, state = P.cnn_init(key)

        def apply_loss(params, state, batch):
            x, y = batch
            logits, new_state = P.cnn_apply(params, state, x, train=True)
            return C.softmax_xent(logits, y), new_state

        def predict(params, state, x):
            logits, _ = P.cnn_apply(params, state, x, train=False)
            return logits
        return params, state, apply_loss, predict
    raise ValueError(kind)


def _where_clients(mask: jnp.ndarray, new, old):
    """Per-client select over a stacked-client pytree: leaves are
    (N, ...) arrays; take ``new`` where mask, keep ``old`` elsewhere.
    An all-True mask returns ``new`` bitwise (the Full-plan no-op)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(
            mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b),
        new, old)


# ---------------------------------------------------------------------------
# round pieces shared with the async service plane (fl.service)
# ---------------------------------------------------------------------------

def select_member_topk(cluster_age, taken, cand, cl, *, k: int,
                       disjoint: bool):
    """One member's age-top-k pick against the in-window disjointness
    set — the shared inner of :func:`rage_select`'s member scan and the
    async service's per-landing selection. Reads the CURRENT
    ``cluster_age``; under disjoint=True the result is invariant to the
    interleaved per-member +1/reset (a landed member's +1 shifts its
    whole cluster row uniformly and its resets are ``taken``-masked
    anyway), which is what makes the event-loop selection bit-identical
    to the round-start-ages reference in the degenerate setting."""
    ages = cluster_age[cl, cand]
    if disjoint:
        ages = jnp.where(taken[cl, cand], jnp.int32(-1), ages)
    _, sel = jax.lax.top_k(ages, k)             # stable: |g| tie-break
    return cand[sel]


def member_age_row(row, idx):
    """Eq. (2) for one member/landing: the cluster row advances by one
    and the requested coordinates reset (sentinel/OOB indices drop)."""
    return (row + 1).at[idx].set(0, mode="drop")


def apply_global(g_opt, unflatten, g_sum, g_params, g_opt_state):
    """The PS's global update from an aggregated flat gradient — shared
    tail of the engine round and the service's buffer flush."""
    updates, g_opt_state = g_opt.update(unflatten(g_sum), g_opt_state,
                                        g_params)
    return apply_updates(g_params, updates), g_opt_state


def build_eval_sets(shards, test, *, cap: int = 1024):
    """Per-client eval subsets (the labels each client holds), shared by
    the engine and the async service."""
    xte, yte = test
    out = []
    for (_, ys) in shards:
        labels = np.unique(ys)
        sel = np.isin(yte, labels)
        out.append((jnp.asarray(xte[sel][:cap]), jnp.asarray(yte[sel][:cap])))
    return out


# ---------------------------------------------------------------------------
# device-side rAge-k selection (the PS control loop, on accelerator)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("r", "k", "disjoint", "candidates", "d"))
def rage_select(g: jnp.ndarray, age: DeviceAgeState, *, r: int, k: int,
                disjoint: bool = True, cands=None,
                candidates: str = "sort", active=None,
                d: int | None = None):
    """Algorithm 1 steps 2-3 + eq. (2), entirely on device.

    g: (N, d) client gradients. Clients are processed in order; within a
    cluster, indices already requested this round are excluded for the
    remaining members (disjointness, §II). Selection reads ROUND-START
    ages for every client; eq. (2) is then applied sequentially per
    member (+1 per member, requested set to 0) — bit-identical to the
    host ``core.protocol.ParameterServer`` reference. ``cands`` takes a
    precomputed ``client_candidates`` report (PS-only entry point);
    ``candidates`` picks the plane computing it here ('sort' |
    'threshold', bit-identical).

    ``active`` is the participation plane's (N,) mask (DESIGN.md §9):
    inactive clients request nothing (their idx rows return the
    sentinel d), update neither freq nor the disjointness set, and
    their eq.-2 "+1" applies with NO reset — ages keep growing while a
    client is unheard from. Inactive +1s are order-independent (nothing
    resets them), so they are applied up front and the member scan
    touches only active clients' requests — the same semantics the
    segmented plane's closed form implements. active=None == all-True
    (bit-identical to the unmasked path).

    ``g`` may be None when ``cands`` is precomputed and the static
    gradient dim ``d`` is given (the fused-report hand-off, DESIGN.md
    §11) — selection then never reads an (N, d) gradient matrix.

    Returns (idx (N, k) int32, new DeviceAgeState).
    """
    if g is None:
        if cands is None or d is None:
            raise ValueError("rage_select: g=None needs a precomputed "
                             "cands report AND the static gradient dim d")
        n = cands.shape[0]
    else:
        n, d = g.shape
    if cands is None:
        cands = client_candidates(g, r, candidates)
    if active is None:
        active = jnp.ones((n,), bool)

    def sel_body(taken, inp):
        cand, cl, act = inp
        idx = select_member_topk(age.cluster_age, taken, cand, cl, k=k,
                                 disjoint=disjoint)
        idx = jnp.where(act, idx, jnp.int32(d))     # inactive: no request
        if disjoint:
            taken = taken.at[cl, idx].set(True, mode="drop")
        return taken, idx

    # cluster-indexed scratch is sized by the age plane's ROW count —
    # N under the dense layout, the C_max bound under the hierarchical
    nrows = age.cluster_age.shape[0]
    taken0 = jnp.zeros((nrows, d), bool)
    _, idx = jax.lax.scan(sel_body, taken0,
                          (cands, age.cluster_of, active))

    # inactive members' +1s first (they commute — no reset), then the
    # active members' sequential +1-and-reset in client order
    inact = jnp.zeros((nrows,), jnp.int32).at[age.cluster_of].add(
        (~active).astype(jnp.int32))

    def age_body(ca, inp):
        idx_i, cl, act = inp
        row = ca[cl]
        new_row = member_age_row(row, idx_i)
        return ca.at[cl].set(jnp.where(act, new_row, row)), None

    cluster_age, _ = jax.lax.scan(
        age_body, age.cluster_age + inact[:, None],
        (idx, age.cluster_of, active))
    freq = (age.freq.at[jnp.arange(n)[:, None], idx].add(1, mode="drop")
            if age.freq is not None else None)   # hierarchical: logged
    return idx.astype(jnp.int32), age._replace(cluster_age=cluster_age,
                                               freq=freq)


@partial(jax.jit, static_argnames=("r", "k", "disjoint", "num_segments",
                                   "max_seg", "impl", "return_seg",
                                   "candidates", "d"))
def rage_select_segmented(g: jnp.ndarray, age: DeviceAgeState, *, r: int,
                          k: int, num_segments: int | None = None,
                          max_seg: int | None = None,
                          disjoint: bool = True, impl: str = "jnp",
                          cands=None, return_seg: bool = False,
                          candidates: str = "sort", active=None,
                          d: int | None = None):
    """Segmented per-cluster formulation of :func:`rage_select` — same
    contract (idx (N, k) int32, new DeviceAgeState), BIT-IDENTICAL output
    (pinned by tests/test_segmented_selection.py), but the disjointness
    recursion scans only member positions WITHIN each padded cluster
    (length = max_seg, not N) and clusters run in parallel.

    num_segments/max_seg are STATIC bounds on the live cluster count /
    largest cluster (defaults N/N always fit; the engine tightens them
    from the host-side DBSCAN labels at every recluster — no new device
    ->host transfer, the labels were already on host). impl='pallas'
    routes the masked top-k through ``kernels.ops.segmented_age_topk``.
    ``return_seg=True`` appends the ``SegmentedSelection`` (the engine's
    fused-aggregation hand-off). ``active`` is the participation
    plane's (N,) mask — only active clients are packed/select/reset;
    inactive ones age with no reset and return sentinel-d idx rows
    (DESIGN.md §9; max_seg may then be tightened to the scheduler's
    static m bound). ``g`` may be None when ``cands`` is precomputed and
    the static gradient dim ``d`` is given (fused report, DESIGN.md §11).
    """
    n = age.cluster_of.shape[0] if g is None else g.shape[0]
    idx, new_ca, seg = segmented_rage_select(
        g, age.cluster_age, age.cluster_of, r=r, k=k,
        num_segments=num_segments, max_seg=max_seg, disjoint=disjoint,
        impl=impl, cands=cands, candidates=candidates, active=active, d=d)
    freq = (age.freq.at[jnp.arange(n)[:, None], idx].add(1, mode="drop")
            if age.freq is not None else None)   # hierarchical: logged
    idx = idx.astype(jnp.int32)
    new_age = age._replace(cluster_age=new_ca, freq=freq)
    if return_seg:
        return idx, new_age, seg
    return idx, new_age


def _recluster_host(freq: np.ndarray, cluster_age: np.ndarray,
                    cluster_of: np.ndarray, eps: float, min_pts: int,
                    compact: bool = False):
    """The host-shaped part of a recluster, pure numpy (thread-safe —
    the scan driver runs it on a worker thread overlapped with the chunk
    boundary work): eq. (3) similarity -> DBSCAN -> merge/reset of the
    cluster age rows via ``core.age.AgeState.apply_clusters`` (the one
    place those semantics live). ``cluster_age`` rows are keyed by
    cluster id under BOTH layouts ((N, d) dense, (C_max, d)
    hierarchical — :meth:`AgeState.from_cluster_rows` is
    layout-agnostic). Returns (new int32 cluster_age — (N, d) rows by
    default, the compact (C_new, d) live rows when ``compact`` — and
    the (N,) labels)."""
    n, d = freq.shape
    labels = cluster_clients(freq, eps, min_pts)
    st = AgeState.from_cluster_rows(cluster_age, cluster_of)
    st.apply_clusters(labels)
    rows = (int(st.cluster_of.max()) + 1) if compact else n
    new_ca = np.zeros((rows, d), np.int32)
    for c, v in st.ages.items():
        new_ca[c] = v
    return new_ca, st.cluster_of


def _recluster_host_packed(age: DeviceAgeState, eps: float, min_pts: int,
                           freq: np.ndarray | None = None,
                           compact: bool = False):
    """Device->host pull of the age state + :func:`_recluster_host` —
    the single marshalling point shared by the sync path, the async
    worker and :func:`recluster_packed`. Under the hierarchical layout
    the caller hands in the host-accumulated ``freq`` matrix (rebuilt
    from the drained sparse log — the device has no dense matrix to
    pull) and asks for compact (C_new, d) rows."""
    if freq is None:
        freq = np.asarray(age.freq)
    return _recluster_host(freq, np.asarray(age.cluster_age),
                           np.asarray(age.cluster_of), eps, min_pts,
                           compact=compact)


def recluster_packed(age: DeviceAgeState, eps: float, min_pts: int):
    """Eq. (3) similarity -> DBSCAN -> merge/reset of cluster age vectors.

    The ONE host round-trip of the control loop (every M rounds): the
    (N, d) int32 freq matrix comes down, labels go back up. Returns
    (new state, host-side (N,) labels) — the labels are the engine's
    source for the segmented packing bounds (live cluster count, max
    cluster size) without any extra transfer."""
    new_ca, labels = _recluster_host_packed(age, eps, min_pts)
    return age._replace(
        cluster_age=jnp.asarray(new_ca),
        cluster_of=jnp.asarray(labels, dtype=jnp.int32)), labels


def recluster(age: DeviceAgeState, eps: float, min_pts: int) -> DeviceAgeState:
    """:func:`recluster_packed` without the label return (compat surface)."""
    return recluster_packed(age, eps, min_pts)[0]


def drain_request_log(age: DeviceAgeState, freq_host: np.ndarray,
                      seen: int, *, n: int, d: int) -> int:
    """Pull the sparse update-log slots written since watermark ``seen``
    (hierarchical layout) and fold them into the host-side cumulative
    (N, d) frequency matrix — the O(m_bound·k·M) device->host transfer
    that replaces the dense layout's O(N·d) freq pull. Returns the new
    watermark (the current ``log_ptr``). Shared by the engine and the
    async service; the caller guarantees no concurrent reader of
    ``freq_host`` (both drain before handing it to the DBSCAN
    worker)."""
    ptr = int(age.log_ptr)
    if ptr == seen:
        return seen
    L = int(age.log_idx.shape[0])
    # the ring covers exactly one recluster window and every recluster
    # drains, so the device writer can never lap the host watermark
    assert ptr - seen <= L, (
        f"update log overran: ptr={ptr} seen={seen} L={L}")
    slots = np.array([p % L for p in range(seen, ptr)])
    fold_request_log(freq_host, np.asarray(age.log_mem)[slots],
                     np.asarray(age.log_idx)[slots], n_clients=n, d=d)
    return ptr


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class FederatedEngine:
    """Owns the paper's round loop as a single jitted step.

    Usage::

        engine = FederatedEngine("mlp", shards, test, hp, seed=0)
        result = engine.run(rounds=200, eval_every=5)

    or round-at-a-time via :meth:`step` for custom drivers. ``hp.method``
    picks the Strategy ('rage_k' | 'rtop_k' | 'top_k' | 'random_k' |
    'dense'); all five share the same engine, state layout and metrics.
    """

    def __init__(self, kind: str, shards: list, test: tuple,
                 hp: RAgeKConfig, *, seed: int = 0, ef: bool = False,
                 global_opt: str = "adam", aggregate_impl: str = "auto",
                 selection: str = "segmented", compute: str = "auto",
                 faults=None, quarantine: bool = True,
                 gate_bound: float = 1e4):
        if hp.method in ("rage_k", "rtop_k", "cafe") and hp.r < hp.k:
            raise ValueError(
                f"method {hp.method!r} selects k of the top-r candidates; "
                f"need r >= k (got r={hp.r}, k={hp.k})")
        if selection not in ("scan", "segmented"):
            raise ValueError(f"selection must be 'scan' or 'segmented', "
                             f"got {selection!r}")
        if compute not in ("auto", "gathered", "masked"):
            raise ValueError(f"compute must be 'auto', 'gathered' or "
                             f"'masked', got {compute!r}")
        if hp.candidates not in CANDIDATE_IMPLS:
            raise ValueError(f"candidates must be one of "
                             f"{CANDIDATE_IMPLS}, got {hp.candidates!r}")
        self.hp = hp
        self.kind = kind
        self.n = len(shards)
        self.seed = seed
        self.ef = ef
        # rage_k selection plane: 'segmented' (per-cluster parallel,
        # default) or 'scan' (the sequential all-clients reference,
        # bit-identical — kept reachable for A/B debugging)
        self._selection = selection
        key = jax.random.PRNGKey(seed)
        g_params, state0, apply_loss, predict = _build_model(kind, key)
        self._predict = predict
        self._state0 = state0
        self.d = sum(int(x.size)
                     for x in jax.tree_util.tree_leaves(g_params))
        self._unflatten = C.unflattener(g_params)
        self._strategy = make_strategy(hp.method, r=hp.r, k=hp.k,
                                       lam=hp.cafe_lam,
                                       candidates=hp.candidates)
        # rage_k fuses the top-r candidate report into the local phase's
        # tail, on the last step's gradient that the phase carries
        # through its scan (DESIGN.md §11): the report comes out of the SAME
        # batched client_candidates call selection would have made on
        # the same post-ef gradients, so the (N, d) grad matrix is
        # never re-materialized for the selection plane — and the fused
        # values are bitwise the unfused ones
        self._report_r = hp.r if hp.method == "rage_k" else None
        self._local_phase = C.make_local_phase(
            apply_loss, hp.lr, report_r=self._report_r,
            report_impl=hp.candidates)
        self._g_opt = adam(hp.lr) if global_opt == "adam" else sgd(hp.lr)
        if aggregate_impl == "auto":
            aggregate_impl = ("pallas" if jax.default_backend() == "tpu"
                              else "jnp")
        self._agg_impl = aggregate_impl
        self._sel_impl = "pallas" if aggregate_impl == "pallas" else "jnp"
        # participation plane (fl.schedule, DESIGN.md §9): the scheduler
        # decides WHO takes part each round; its state (PRNG key, device
        # round counter, client AoI) threads through the scan carry
        self._scheduler = make_scheduler(
            hp.schedule, self.n, participation_m=hp.participation_m,
            deadline_s=hp.deadline_s, seed=seed + 41)
        # compute plane (DESIGN.md §11): 'gathered' compacts the active
        # clients to the scheduler's STATIC m_bound and trains only
        # those rows (local-phase FLOPs ∝ m_bound, not N); 'masked' is
        # the full-N train-everyone-discard-inactive reference. 'auto'
        # gathers exactly when the bound is a real cut (m_bound < N), so
        # the Full plan keeps the pre-plane program bit-for-bit.
        if compute == "auto":
            compute = ("gathered" if self._scheduler.m_bound < self.n
                       else "masked")
        self._compute = compute
        # resilience plane (fl.faults, DESIGN.md §13): a seeded
        # FaultModel injects crash/corrupt/drop faults into the round;
        # the validation gate quarantines non-finite or out-of-band
        # updates PS-side (excluded from the aggregate, eq.-2 no-reset
        # ages like any non-participant). faults=None is the hard
        # identity path: no fault op is ever traced.
        if faults is not None and faults.n != self.n:
            raise ValueError(f"FaultModel.n={faults.n} != {self.n} clients")
        self._faults = faults
        self._quarantine = bool(quarantine)
        self._gate_bound = float(gate_bound)
        self._fault_key = jax.random.PRNGKey(seed + 77)
        # segmented packing bounds: live cluster count / largest cluster.
        # STATIC (recompile keys) — recomputed from the host-side DBSCAN
        # labels at every recluster; singletons at t=0.
        self._num_seg = self.n
        self._max_seg = 1
        # uploaded values take the protocol's wire form (fp32 paper
        # default; bf16 beyond-paper) — the cast round-trip below keeps
        # curves and the byte accounting talking about the same payload
        self._wire_dtype = jnp.dtype(hp.wire_dtype)

        # --- device state --------------------------------------------------
        n = self.n
        self.g_params = g_params
        self.g_opt_state = self._g_opt.init(g_params)
        self.params_s = C.broadcast_global(g_params, n)
        self.opt_s = jax.vmap(adam(hp.lr).init)(self.params_s)
        self.state_s = C.stack_clients([state0] * n) if state0 else {}
        # age plane layout (DESIGN.md §12): 'dense' keeps the (N, d)
        # matrices on device; 'hierarchical' keys cluster_age by live
        # cluster id ((C_max, d), compacted at every recluster) and
        # replaces the dense freq with the bounded sparse update log —
        # the host accumulates the cumulative (N, d) matrix from the
        # drained log (bit-identical eq.-3 features, O(m·k·M) pull)
        self._age_layout = hp.age_layout
        if self._age_layout == "hierarchical":
            rage = hp.method == "rage_k"
            self.age = DeviceAgeState.create_hierarchical(
                self.d, n, log_len=hp.M if rage else 0,
                m_bound=self._scheduler.m_bound, k=hp.k,
                with_cost=hp.method == "cafe")
            self._freq_host = (np.zeros((n, self.d), np.int32)
                               if rage else None)
        else:
            self.age = DeviceAgeState.create(self.d, n)
            self._freq_host = None
        self._log_seen = 0               # host drain watermark (log_ptr)
        self.ef_mem = (jnp.zeros((n, self.d), jnp.float32) if ef else None)
        self._key = jax.random.PRNGKey(seed + 99)
        self.sched = SchedState.create(n, seed + 23)
        self.round_idx = 0

        # --- device-resident data plane + per-client eval sets -------------
        self._store = DeviceShardStore(shards, hp.batch_size,
                                       seed=seed + 17)
        self._data = self._store.data
        self.samp = self._store.init_state()
        self._eval_sets = build_eval_sets(shards, test)

        # --- uplink accounting (per client per round) -----------------------
        ib = bytes_per_index(self.d)
        if hp.method == "dense":
            self._per_client_bytes = bytes_per_round(
                0, self.d, dense=True, wire_dtype=hp.wire_dtype)
        elif hp.method in ("rage_k", "cafe"):
            # + the top-r candidate report uploaded for PS selection
            self._per_client_bytes = bytes_per_round(
                hp.k, self.d, wire_dtype=hp.wire_dtype) + hp.r * ib
        else:
            self._per_client_bytes = bytes_per_round(
                hp.k, self.d, wire_dtype=hp.wire_dtype)
        self.cum_bytes = 0

        self._round = jax.jit(self._round_impl,
                              static_argnames=("num_segments", "max_seg"))
        self._chunks: dict = {}          # scan length -> jitted chunk
        # (length, num_segments, max_seg) keys the chunk programs were
        # built for; a new one compiles, and ``retraces`` counts it by
        # cause: a new scan "length", or new "packing" bounds
        self._chunk_keys: set = set()
        self.retraces = {"length": 0, "packing": 0}
        self._eval = jax.jit(self._eval_impl)

        # --- async recluster (scan driver overlaps the every-M DBSCAN) ----
        self._recluster_pool: ThreadPoolExecutor | None = None
        self._recluster_future = None
        # claims of the in-flight future (and the pool shutdown) are
        # serialized: close() may race __del__ (GC runs it on another
        # thread) or a driver blown out of a chunk mid-scan — the worker
        # result must be joined and applied EXACTLY once
        self._recluster_lock = threading.Lock()
        # a worker-thread DBSCAN failure is captured here and re-raised
        # at EVERY subsequent label consumer (and in close()) — the
        # first raise may be swallowed (__del__, a bare except in a
        # driver), and a swallowed failure must not silently freeze the
        # cluster assignments forever
        self._recluster_exc: BaseException | None = None
        self.recluster_s = 0.0           # total host DBSCAN+merge wall
        self.recluster_wait_s = 0.0      # the part the driver blocked on

    # ------------------------------------------------------------------
    # jitted bodies
    # ------------------------------------------------------------------
    def _aggregate(self, idx, vals):
        if self._agg_impl == "pallas":
            # The kernel always produces its hit-based age lane in the
            # same pass as the scatter; the engine only consumes the
            # dense sum (cluster ages follow the sequential eq.-2
            # semantics in rage_select, which the hit-based update
            # cannot express for multi-member clusters).
            dense, _ = aggregate_sparse_fused(
                idx, vals, jnp.zeros((self.d,), jnp.int32), impl="pallas")
            return dense
        return aggregate_sparse(idx, vals, self.d)

    def _round_impl(self, data, carry, num_segments=None, max_seg=None):
        """One global round, device-pure: (data, carry) -> (carry, metrics).

        ``data`` is the uploaded shard store; ``carry`` threads all
        mutable engine state (params, opt, ages, ef memory, PRNG keys,
        sampler, scheduler state). num_segments/max_seg are the STATIC
        segmented-packing bounds (rage_k + selection='segmented' only).
        The SAME traced body backs both drivers, which is what makes
        run_scanned bit-identical to repeated step().

        The round opens by asking the scheduler for its RoundPlan
        (DESIGN.md §9). Non-participants: local-phase state (optimizer,
        BatchNorm, sampler) and ef memory HELD, no contribution to the
        aggregate, ages advance with no reset, sentinel-d idx rows.
        Stale arrivals (Deadline) contribute with discounted weight.
        Under the Full plan every mask is all-True and every ``where``
        below is a bitwise no-op — the pre-plane engine exactly.

        HOW MUCH work the round does is the compute plane's decision
        (DESIGN.md §11). compute='gathered' compacts the active client
        ids to the scheduler's STATIC m_bound (sentinel n pads short
        rounds), gathers params/opt/BatchNorm/ef/sampler rows, draws
        only the active shards' batches, trains an (m, ...) batch and
        scatters results back with mode='drop' — held state and
        unconsumed data streams come out bit-identical to the masked
        full-N path (per-client math is row-independent; pinned by
        tests/test_active_compute.py). compute='masked' trains all N
        and discards inactive rows. Either way the top-r candidate
        report is FUSED into the local phase (rage_k), so selection
        below never re-reads an (N, d) gradient matrix.

        Each phase runs under a ``jax.named_scope`` — ``local_phase``
        (with ``candidate_report`` nested in it by the client phase),
        ``selection``, ``aggregation``, ``global_update`` — so a
        profiler trace puts every device op down to its layer. Scopes
        are HLO metadata only; the program is unchanged.
        """
        (g_params, g_opt_state, params_s, opt_s, state_s, age, ef_mem,
         key, samp, sched) = carry
        hp = self.hp
        n, d = self.n, self.d
        plan: RoundPlan = self._scheduler.plan(sched, age)
        act = plan.active
        stale = plan.staleness > 0
        # resilience plane (fl.faults, DESIGN.md §13). Crashed clients
        # never start the round — they become full PR 5 non-participants
        # (state held, data unconsumed, eq.-2 no-reset ages) by simply
        # shrinking the plan's active mask before the compute plane
        # looks at it. Wire faults (nan/inf/byz corruption, drops) act
        # AFTER the local phase, below. faults=None traces none of this.
        flt = self._faults
        if flt is not None and flt.any:
            crashed, f_nan, f_inf, f_byz, f_drop = flt.round_masks(
                self._fault_key, sched.rnd)
            n_crashed = (act & crashed).sum().astype(jnp.int32)
            act = act & ~crashed
        else:
            f_nan = f_inf = f_byz = f_drop = None
            n_crashed = jnp.int32(0)
        gathered = self._compute == "gathered"
        with jax.named_scope("local_phase"):
            if gathered:
                # compact the active ids, ascending (nonzero preserves the
                # client order every sequential contract — selection
                # tie-breaks, scatter-add ordering — is stated in); padded
                # slots carry the sentinel n: they read a clipped duplicate
                # row, train dead weight, and write nothing back
                mb = self._scheduler.m_bound
                act_idx = jnp.nonzero(act, size=mb,
                                      fill_value=n)[0].astype(jnp.int32)
                slot_ok = act_idx < n
                iclip = jnp.minimum(act_idx, jnp.int32(n - 1))

                def gather_rows(t):
                    return jax.tree_util.tree_map(lambda a: a[iclip], t)

                def put_rows(old, new):
                    return jax.tree_util.tree_map(
                        lambda a, b: a.at[act_idx].set(b, mode="drop"),
                        old, new)

                bx, by, samp = self._store.draw_gathered(data, samp, hp.H,
                                                         act_idx)
                _, opt_c, state_c, g, cands_c, losses_c = self._local_phase(
                    gather_rows(params_s), gather_rows(opt_s),
                    gather_rows(state_s) if state_s else {}, (bx, by),
                    gather_rows(ef_mem) if ef_mem is not None else None)
                opt_s = put_rows(opt_s, opt_c)
                if state_s:
                    state_s = put_rows(state_s, state_c)
                # inactive clients never trained: their loss is undefined —
                # NaN, the same contract the masked path reports
                losses = jnp.full((n,), jnp.nan, jnp.float32).at[
                    act_idx].set(losses_c, mode="drop")
                cands = (jnp.zeros((n, hp.r), jnp.int32).at[act_idx].set(
                    cands_c, mode="drop") if cands_c is not None else None)
            else:
                act_idx = slot_ok = iclip = None
                bx, by, samp2 = self._store.draw(data, samp, hp.H)
                _, opt_s2, state_s2, g, cands, losses = self._local_phase(
                    params_s, opt_s, state_s if state_s else {}, (bx, by),
                    ef_mem)
                # non-participants sit the round out: their local state holds
                # and their data stream is not consumed
                opt_s = _where_clients(act, opt_s2, opt_s)
                samp = _where_clients(act, samp2, samp)
                if state_s:
                    state_s = _where_clients(act, state_s2, state_s)
                losses = jnp.where(act, losses, jnp.nan)

        # -- wire faults + validation gate (DESIGN.md §13) ------------------
        # ``act_ps`` is who the PS actually HEARS from this round: active
        # minus wire-dropped minus gate-quarantined. It drives everything
        # PS-side (selection, age resets, the aggregate, AoI resets, the
        # ef residual write) while ``act`` keeps driving the local plane
        # (the clients did train; their losses stay finite). With
        # faults=None, act_ps IS act — the same Python object, so every
        # downstream use traces the identical graph.
        act_ps = act
        n_quar = n_drop = jnp.int32(0)
        if flt is not None and flt.any_wire:
            gm = (lambda m: m[iclip]) if gathered else (lambda m: m)
            g = flt.corrupt(g, gm(f_nan), gm(f_inf), gm(f_byz))
            n_drop = (act & f_drop).sum().astype(jnp.int32)
            act_ps = act & ~f_drop
            if self._quarantine:
                # the gate inspects each arriving update row: finite
                # everywhere and within the magnitude band. NaN rows
                # fail isfinite; Byzantine-scaled rows fail the bound.
                row_ok = (jnp.isfinite(g).all(axis=1)
                          & (jnp.abs(g).max(axis=1)
                             <= jnp.float32(self._gate_bound)))
                ok = (jnp.zeros((n,), bool).at[act_idx].set(
                    row_ok, mode="drop") if gathered else row_ok)
                n_quar = (act_ps & ~ok).sum().astype(jnp.int32)
                act_ps = act_ps & ok
            if gathered:
                # fold the wire verdict into the slot mask every
                # gathered value/ef path below already consults
                slot_ok = slot_ok & act_ps[iclip]

        with jax.named_scope("selection"):
            key, sub = jax.random.split(key)
            method = hp.method
            seg = None
            if method == "rage_k":
                # both selection planes consume the FUSED report (g=None):
                # in gathered mode the compact (m, r) report was scattered
                # into full-N layout above (inactive rows are never read)
                if self._selection == "segmented":
                    idx, age, seg = rage_select_segmented(
                        None, age, r=hp.r, k=hp.k, num_segments=num_segments,
                        max_seg=max_seg, disjoint=hp.disjoint_in_cluster,
                        impl=self._sel_impl, return_seg=True,
                        candidates=hp.candidates, active=act_ps, cands=cands,
                        d=d)
                else:
                    idx, age = rage_select(None, age, r=hp.r, k=hp.k,
                                           disjoint=hp.disjoint_in_cluster,
                                           candidates=hp.candidates,
                                           active=act_ps, cands=cands, d=d)
            elif method == "cafe":
                # per-client cost-and-age selection via the batched protocol;
                # cluster_age doubles as the per-client age rows (clusters
                # stay singleton — no recluster on this method) and the
                # cumulative cost CAFe discounts by lives in ``freq``
                # (dense layout) or the dedicated ``cost`` rows
                # (hierarchical — already cluster-keyed, cafe clusters are
                # singletons). Inactive clients: eq. (2) with no reset, no
                # cost, no request
                cost_pl = age.freq if age.freq is not None else age.cost
                if gathered:
                    idx_c, _, (ca_c, fr_c) = self._strategy.select_batch(
                        g, (age.cluster_age[iclip], cost_pl[iclip]))
                    ca = (age.cluster_age + 1).at[act_idx].set(ca_c,
                                                               mode="drop")
                    fr = cost_pl.at[act_idx].set(fr_c, mode="drop")
                    if act_ps is not act:
                        # quarantined/dropped rows: eq. (2) no reset, no cost
                        ca = jnp.where(act_ps[:, None], ca,
                                       age.cluster_age + 1)
                        fr = jnp.where(act_ps[:, None], fr, cost_pl)
                    idx = jnp.full((n, hp.k), d, jnp.int32).at[act_idx].set(
                        idx_c.astype(jnp.int32), mode="drop")
                else:
                    idx, _, (ca, fr) = self._strategy.select_batch(
                        g, (age.cluster_age, cost_pl))
                    ca = jnp.where(act_ps[:, None], ca, age.cluster_age + 1)
                    fr = jnp.where(act_ps[:, None], fr, cost_pl)
                    idx = idx.astype(jnp.int32)
                if age.freq is not None:
                    age = age._replace(cluster_age=ca, freq=fr)
                else:
                    age = age._replace(cluster_age=ca, cost=fr)
            elif method == "dense":
                idx = None
            elif method in ("rtop_k", "random_k"):
                # the per-client key split stays full-N so a client's key
                # depends only on its id, not on who else took part
                keys = jax.random.split(sub, self.n)
                if gathered:
                    idx_c, _, _ = self._strategy.select_batch(g, keys[iclip])
                    idx = jnp.full((n, hp.k), d, jnp.int32).at[act_idx].set(
                        idx_c.astype(jnp.int32), mode="drop")
                else:
                    idx, _, _ = self._strategy.select_batch(g, keys)
            else:                                     # top_k — deterministic
                if gathered:
                    idx_c, _, _ = self._strategy.select_batch(g, ())
                    idx = jnp.full((n, hp.k), d, jnp.int32).at[act_idx].set(
                        idx_c.astype(jnp.int32), mode="drop")
                else:
                    idx, _, _ = self._strategy.select_batch(g, ())

            if idx is not None:
                # inactive clients request nothing — sentinel-d rows, in ONE
                # place so no strategy branch can forget the mask (a no-op
                # on the rage paths, which already masked internally).
                # act_ps: quarantined/dropped clients request nothing either
                idx = jnp.where(act_ps[:, None], idx, jnp.int32(d))

            if method == "rage_k" and age.log_ptr is not None:
                # hierarchical layout: append this round's requests to the
                # sparse update log ring (the every-M DBSCAN input — the
                # dense layout's on-device freq scatter moved host-side).
                # Rows are the compacted participants; padded slots carry
                # sentinel client id n and all-sentinel-d index rows
                if gathered:
                    mem, ok, mclip = act_idx, slot_ok, iclip
                else:
                    mem = jnp.nonzero(act, size=age.log_mem.shape[1],
                                      fill_value=n)[0].astype(jnp.int32)
                    ok = mem < n
                    mclip = jnp.minimum(mem, jnp.int32(n - 1))
                slot = jax.lax.rem(age.log_ptr,
                                   jnp.int32(age.log_idx.shape[0]))
                age = age._replace(
                    log_idx=age.log_idx.at[slot].set(
                        jnp.where(ok[:, None], idx[mclip], jnp.int32(d))),
                    log_mem=age.log_mem.at[slot].set(mem),
                    log_ptr=age.log_ptr + 1)
            if age.upload_cost is not None:
                # O(N) per-client cumulative upload-cost scalar (entries
                # actually uploaded this round — the CAFe-style cost signal
                # at scale, no dense matrix needed)
                per = jnp.int32(d if method == "dense" else hp.k)
                age = age._replace(upload_cost=age.upload_cost
                                   + act.astype(jnp.int32) * per)

        with jax.named_scope("aggregation"):
            # ``sent`` (what each client actually uploaded, for the ef
            # residual) stays COMPACT (m, d) in gathered mode; only the
            # O(N*k) vals layout is rebuilt full-size for aggregation, so
            # the sum's add order (client-ascending) matches the masked
            # path's bit for bit
            if idx is None:
                if gathered:
                    gw = g.astype(self._wire_dtype).astype(g.dtype)
                    gw = jnp.where(
                        stale[iclip][:, None],
                        gw * plan.weight[iclip][:, None].astype(g.dtype), gw)
                    if act_ps is not act:
                        # quarantined/dropped slots contribute nothing
                        gw = jnp.where(slot_ok[:, None], gw,
                                       jnp.zeros((), g.dtype))
                    sent = gw
                    g_sum = jnp.zeros((n, d), g.dtype).at[act_idx].set(
                        gw, mode="drop").sum(0)
                else:
                    gw = g.astype(self._wire_dtype).astype(g.dtype)
                    gw = jnp.where(
                        stale[:, None],
                        gw * plan.weight[:, None].astype(g.dtype), gw)
                    gw = jnp.where(act_ps[:, None], gw,
                                   jnp.zeros((), g.dtype))
                    g_sum = gw.sum(0)
                    sent = gw
            else:
                if gathered:
                    idx_rows = idx[iclip]
                    vals_c = jnp.take_along_axis(
                        g, jnp.minimum(idx_rows, jnp.int32(d - 1)), axis=1)
                    vals_c = vals_c.astype(self._wire_dtype).astype(g.dtype)
                    vals_c = jnp.where(
                        stale[iclip][:, None],
                        vals_c * plan.weight[iclip][:, None].astype(g.dtype),
                        vals_c)
                    vals_c = jnp.where(slot_ok[:, None], vals_c,
                                       jnp.zeros((), g.dtype))
                    vals = jnp.zeros((n, idx.shape[1]), g.dtype).at[
                        act_idx].set(vals_c, mode="drop")
                    sent = jax.vmap(
                        lambda i, v: jnp.zeros((self.d,), g.dtype).at[i].set(
                            v, mode="drop")
                    )(idx_rows, vals_c)
                else:
                    vals = jnp.take_along_axis(
                        g, jnp.minimum(idx, jnp.int32(d - 1)), axis=1)
                    vals = vals.astype(self._wire_dtype).astype(g.dtype)
                    # stale arrivals land staleness-discounted; the fresh
                    # path stays bitwise untouched (weight only where stale)
                    vals = jnp.where(
                        stale[:, None],
                        vals * plan.weight[:, None].astype(g.dtype), vals)
                    vals = jnp.where(act_ps[:, None], vals,
                                     jnp.zeros((), g.dtype))
                    sent = jax.vmap(
                        lambda i, v: jnp.zeros((self.d,), g.dtype).at[i].set(
                            v, mode="drop")
                    )(idx, vals)
                if seg is not None and self._agg_impl == "pallas":
                    # fused path: the SEGMENTED layout feeds the kernel
                    # directly — padded member slots (and, under a partial
                    # plan, unpacked inactive clients) carry the sentinel
                    # index d, which the scatter kernel drops
                    mclip = jnp.minimum(seg.members, self.n - 1)
                    seg_vals = jnp.where(seg.members[..., None] < self.n,
                                         vals[mclip], jnp.zeros((), g.dtype))
                    dense, _ = aggregate_sparse_fused(
                        seg.idx, seg_vals, jnp.zeros((self.d,), jnp.int32),
                        impl="pallas")
                    g_sum = dense
                else:
                    g_sum = self._aggregate(idx, vals)
            if ef_mem is not None:
                if gathered:
                    ef_rows = g - sent
                    if act_ps is not act:
                        # wire-faulted slots hold their ef memory: the
                        # corrupted row must not poison the residual
                        ef_rows = jnp.where(slot_ok[:, None], ef_rows,
                                            gather_rows(ef_mem))
                    ef_mem = ef_mem.at[act_idx].set(ef_rows, mode="drop")
                else:
                    ef_new = g - sent
                    if act_ps is not act:
                        ef_new = jnp.where(act_ps[:, None], ef_new, ef_mem)
                    ef_mem = jnp.where(act[:, None], ef_new, ef_mem)

        with jax.named_scope("global_update"):
            g_params, g_opt_state = apply_global(
                self._g_opt, self._unflatten, g_sum, g_params, g_opt_state)
            params_s = C.broadcast_global(g_params, self.n)

        # AoI bookkeeping + participation metrics (scalars; the per-chunk
        # pull stays O(N*k)). Client AoI: rounds since last heard from.
        # Coordinate AoI: the cluster_age field over LIVE cluster rows.
        aoi = jnp.where(act_ps, jnp.int32(0), sched.aoi + 1)
        sched = SchedState(key=sched.key, rnd=sched.rnd + 1, aoi=aoi)
        live = jnp.zeros((age.cluster_age.shape[0],),
                         bool).at[age.cluster_of].set(True)
        ca_live = jnp.where(live[:, None], age.cluster_age, 0)
        metrics = {
            "losses": losses,
            "idx": idx if idx is not None else jnp.zeros((), jnp.int32),
            "n_active": act.sum().astype(jnp.int32),
            "aoi_mean": aoi.astype(jnp.float32).mean(),
            "aoi_peak": aoi.max(),
            "age_mean": (ca_live.astype(jnp.float32).sum()
                         / (live.sum().astype(jnp.float32) * d)),
            "age_peak": ca_live.max(),
            # resilience counters (DESIGN.md §13) — constants 0 when
            # faults are off, so the metrics layout never changes
            "n_quarantined": n_quar,
            "n_crashed": n_crashed,
            "n_dropped": n_drop,
        }
        return (g_params, g_opt_state, params_s, opt_s, state_s, age,
                ef_mem, key, samp, sched), metrics

    def _eval_impl(self, params_s, state_s):
        accs = []
        with jax.named_scope("eval"):
            for i in range(self.n):
                p_i = jax.tree_util.tree_map(lambda x: x[i], params_s)
                s_i = (jax.tree_util.tree_map(lambda x: x[i], state_s)
                       if state_s else self._state0)
                xe, ye = self._eval_sets[i]
                logits = self._predict(p_i, s_i, xe)
                accs.append(jnp.mean(
                    (jnp.argmax(logits, -1) == ye).astype(jnp.float32)))
            return jnp.stack(accs)

    # ------------------------------------------------------------------
    # host control plane
    # ------------------------------------------------------------------
    def _seg_bounds(self):
        """Static packing bounds for the jitted round — (None, None) for
        every path that doesn't consume them, so e.g. selection='scan'
        never recompiles when a recluster changes the cluster shape.
        The member-scan bound is additionally clipped to the scheduler's
        static participation ceiling (at most m clients are active, so
        no cluster packs more than m active members) — recomputed from
        the PLAN's static bound, never from a device pull, so the
        jit/chunk caches stay warm across rounds."""
        self._recluster_join()
        if self.hp.method == "rage_k" and self._selection == "segmented":
            return self._num_seg, min(self._max_seg,
                                      self._scheduler.m_bound)
        return None, None

    def _pack(self):
        self._recluster_join()
        return (self.g_params, self.g_opt_state, self.params_s, self.opt_s,
                self.state_s, self.age, self.ef_mem, self._key, self.samp,
                self.sched)

    def _unpack(self, carry):
        (self.g_params, self.g_opt_state, self.params_s, self.opt_s,
         self.state_s, self.age, self.ef_mem, self._key, self.samp,
         self.sched) = carry

    # ------------------------------------------------------------------
    # checkpoint/resume (DESIGN.md §13)
    # ------------------------------------------------------------------
    def state_tree(self):
        """The COMPLETE round state as one pytree: the full scan carry
        (params, opt, per-client rows, ``DeviceAgeState`` in either
        layout incl. the sparse log ring, ef memory, PRNG key, sampler,
        ``SchedState``) plus the hierarchical layout's host freq
        accumulator. Joins any in-flight recluster first (via `_pack`)
        so labels/packing bounds are committed, and drains the request
        log so the host accumulator in the snapshot is current — the
        drain is a watermark move, so an early drain leaves the run's
        math untouched."""
        tree = {"carry": self._pack()}
        if self._freq_host is not None:
            self._drain_freq_log()
            tree["freq_host"] = np.array(self._freq_host)
        return tree

    def _extra_state(self) -> dict:
        return {"round_idx": self.round_idx, "cum_bytes": self.cum_bytes,
                "log_seen": self._log_seen, "num_seg": self._num_seg,
                "max_seg": self._max_seg}

    def save_state(self, checkpointer, result: FLResult | None = None):
        """Snapshot the complete round state into ``checkpointer`` (an
        AsyncCheckpointer). The host-side scalars (round counter, byte
        ledger, log watermark, DBSCAN packing bounds) and — when given —
        the FLResult-so-far ride in the JSON meta, so a resumed driver
        reproduces the uninterrupted run's output byte for byte."""
        tree = self.state_tree()     # BEFORE extras: the drain inside
        extra = self._extra_state()  # moves the log_seen watermark
        if result is not None:
            extra["result"] = _result_to_json(result)
        checkpointer.save(self.round_idx, tree, extra=extra)

    def load_state(self, source, step: int | None = None) -> FLResult:
        """Restore from the newest good checkpoint under ``source`` (an
        AsyncCheckpointer or a directory path), falling back past
        corrupt entries (checkpoint.io). The engine must be constructed
        with the same config/seed; the restored arrays adopt their SAVED
        shapes (the hierarchical cluster_age rows are (C, d)-compacted).
        Returns the FLResult recorded in the checkpoint (empty if none
        was saved) for the driver to keep appending to."""
        path = source.path if hasattr(source, "path") else source
        tree, meta = load_checkpoint(path, self.state_tree(), step=step)
        self._unpack(tuple(tree["carry"]))
        if "freq_host" in tree:
            # back to a HOST accumulator (drain folds into it in place)
            self._freq_host = np.array(tree["freq_host"])
        ex = meta["extra"]
        self.round_idx = int(ex["round_idx"])
        self.cum_bytes = int(ex["cum_bytes"])
        self._log_seen = int(ex["log_seen"])
        self._num_seg = int(ex["num_seg"])
        self._max_seg = int(ex["max_seg"])
        return _result_from_json(ex.get("result"))

    def _chunk(self, length: int):
        """Jitted `length`-round chunk: one lax.scan over `_round_impl`,
        metrics stacked (length, ...) on device. Cached per length (chunk
        boundaries produce only a handful of distinct lengths); the
        segmented-packing bounds ride along as STATIC jit arguments
        (chunk boundaries align to the recluster rounds where they
        change), pre-bound so the returned callable keeps the
        (data, carry) signature. A key not built before is counted in
        ``retraces``: its first call compiles."""
        ns, ms = self._seg_bounds()
        fn = self._chunks.get(length)
        if fn is None:
            def chunk(data, carry, num_segments, max_seg):
                return jax.lax.scan(
                    lambda c, _: self._round_impl(data, c, num_segments,
                                                  max_seg),
                    carry, None, length=length)
            fn = self._chunks[length] = jax.jit(
                chunk, static_argnames=("num_segments", "max_seg"))
            self.retraces["length"] += 1
        elif (length, ns, ms) not in self._chunk_keys:
            self.retraces["packing"] += 1
        self._chunk_keys.add((length, ns, ms))
        return partial(fn, num_segments=ns, max_seg=ms)

    def program_texts(self) -> list:
        """The compiled HLO text of every chunk program built so far
        (those at the current packing bounds first) and of the eval
        program. A TPU trace's op events name each op's HLO instruction
        but carry no ``op_name``; this text maps one to the other, and
        so each op to its named scope. Lowered again from the same
        functions and arguments, so a program that ran comes from JAX's
        in-process cache as it ran. (JAX's persistent cache keys leave
        named scopes out: an executable loaded from it may predate
        them.)"""
        carry = self._pack()
        now = self._seg_bounds()
        texts = [self._chunks[n].lower(self._data, carry, num_segments=ns,
                                       max_seg=ms).compile().as_text()
                 for n, ns, ms in sorted(self._chunk_keys,
                                         key=lambda k: k[1:] != now)]
        texts.append(self._eval.lower(self.params_s, self.state_s)
                     .compile().as_text())
        return texts

    def _bookkeep(self, n_active: int | None = None):
        """Per-round host accounting shared by both drivers. Uplink is
        charged per PARTICIPANT (n_active; the candidate report rides
        inside _per_client_bytes, so absent clients are not billed for
        it either); None bills the full population (pre-plane ledger)."""
        self.round_idx += 1
        self.cum_bytes += self._per_client_bytes * (
            self.n if n_active is None else int(n_active))
        if self.hp.method == "rage_k" and self.round_idx % self.hp.M == 0:
            self._recluster()

    @staticmethod
    def _round_row(metrics, j=None) -> dict:
        """Host floats of one round's participation metrics ((T,)-stacked
        under the scan driver; scalar under step)."""
        pick = (lambda v: v[j]) if j is not None else (lambda v: v)
        return {"n_active": int(pick(metrics["n_active"])),
                "aoi_mean": float(pick(metrics["aoi_mean"])),
                "aoi_peak": int(pick(metrics["aoi_peak"])),
                "age_mean": float(pick(metrics["age_mean"])),
                "age_peak": int(pick(metrics["age_peak"])),
                "n_quarantined": int(pick(metrics["n_quarantined"])),
                "n_crashed": int(pick(metrics["n_crashed"])),
                "n_dropped": int(pick(metrics["n_dropped"]))}

    def _track(self, res: FLResult, row: dict, requested) -> None:
        """Append one round's participation metrics + requested indices
        (the per-ROUND columns of FLResult, DESIGN.md §9)."""
        res.requested.append(requested)
        res.n_active.append(row["n_active"])
        res.aoi_mean.append(row["aoi_mean"])
        res.aoi_peak.append(row["aoi_peak"])
        res.age_mean.append(row["age_mean"])
        res.age_peak.append(row["age_peak"])
        res.n_quarantined.append(row["n_quarantined"])
        res.n_crashed.append(row["n_crashed"])
        res.n_dropped.append(row["n_dropped"])

    def step(self) -> dict:
        """Advance one global round. Returns {"losses": (N,), "idx":
        (N, k)|None, "n_active", "aoi_mean", "aoi_peak", "age_mean",
        "age_peak"} — the only per-round device->host traffic (O(N*k)
        plus five scalars). Inactive clients' idx rows hold the
        sentinel d ("no request")."""
        ns, ms = self._seg_bounds()
        carry, metrics = self._round(self._data, self._pack(),
                                     num_segments=ns, max_seg=ms)
        jax.block_until_ready(metrics)
        self._unpack(carry)
        out = self._round_row(metrics)
        self._bookkeep(out["n_active"])
        out["losses"] = np.asarray(metrics["losses"])
        out["idx"] = (np.asarray(metrics["idx"])
                      if self.hp.method != "dense" else None)
        return out

    def _drain_freq_log(self):
        """Pull the sparse update-log slots written since the last drain
        and fold them into the host-side cumulative (N, d) frequency
        matrix (hierarchical layout; no-op otherwise). O(m_bound·k·M)
        device->host bytes per recluster window instead of the dense
        layout's O(N·d) pull. Callers must hold no in-flight recluster
        (the worker reads ``_freq_host``) — both call sites join
        first."""
        if self._freq_host is None or self.age.log_ptr is None:
            return
        self._log_seen = drain_request_log(self.age, self._freq_host,
                                           self._log_seen, n=self.n,
                                           d=self.d)

    @property
    def freq_matrix(self) -> np.ndarray:
        """The cumulative (N, d) request-frequency matrix (eq.-3 inputs /
        the paper's heatmap source), layout-agnostic: the device matrix
        under 'dense', the host accumulator (sparse log drained first)
        under 'hierarchical' — bit-identical by construction. CAFe's
        cost rows stand in for freq exactly as the dense layout stores
        them there; methods that never request return zeros."""
        self._recluster_join()
        if self.age.freq is not None:
            return np.asarray(self.age.freq)
        if self._freq_host is not None:
            self._drain_freq_log()
            return self._freq_host
        if self.age.cost is not None:
            return np.asarray(self.age.cost)
        return np.zeros((self.n, self.d), np.int32)

    def _recluster_submit(self):
        """Kick the every-M host DBSCAN onto a worker thread at a chunk
        boundary (scan driver): the device->host freq pull, eq. (3)
        similarity, DBSCAN and the age merge all run while the main
        thread drains the chunk metrics and bookkeeps; :meth:`_recluster`
        joins BEFORE the labels are consumed. Bit-identical to the
        synchronous path — same freq snapshot, same numpy math. Under
        the hierarchical layout the sparse log is drained HERE, on the
        main thread, before the submit — the worker then reads a
        quiescent ``_freq_host`` (the next drain cannot start until
        this future is joined)."""
        if self._recluster_future is not None:
            return
        if self._recluster_pool is None:
            self._recluster_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="recluster")
        self._drain_freq_log()
        age, eps, mp = self.age, self.hp.eps, self.hp.min_pts
        freq, compact = self._freq_host, self._age_layout == "hierarchical"

        def work():
            t0 = time.perf_counter()
            with TraceAnnotation("fl.recluster.compute"):
                out = _recluster_host_packed(age, eps, mp, freq=freq,
                                             compact=compact)
            return out, time.perf_counter() - t0

        self._recluster_future = self._recluster_pool.submit(work)

    def _recluster(self):
        """The every-M recluster. Step driver (no in-flight submission):
        compute inline, fully blocking. Scan driver (a worker-thread
        future is pending): do NOTHING here — the join is deferred to
        the first consumer of the new labels (:meth:`_pack` before the
        next chunk dispatch, :meth:`_seg_bounds`, the ``cluster_of``
        property inside ``_record``), so the DBSCAN also overlaps the
        chunk-boundary EVAL, the dominant host-paced boundary work.
        ``recluster_s`` accumulates the host clustering wall;
        ``recluster_wait_s`` only the part the driver actually blocked
        on — their difference is the hidden host time reported by
        benchmarks/engine_bench.py."""
        if self._recluster_future is not None:
            return
        t0 = time.perf_counter()
        with TraceAnnotation("fl.recluster.compute"):
            self._drain_freq_log()
            new_ca, labels = _recluster_host_packed(
                self.age, self.hp.eps, self.hp.min_pts,
                freq=self._freq_host,
                compact=self._age_layout == "hierarchical")
        dt = time.perf_counter() - t0
        self.recluster_s += dt
        self.recluster_wait_s += dt
        self._apply_recluster(new_ca, labels)

    def _recluster_join(self):
        """Block on (and apply) the in-flight async recluster, if any.
        Every reader of post-recluster state funnels through here, so a
        deferred join can never be observed. The future is CLAIMED under
        a lock before it is joined, so concurrent callers (close()
        racing __del__, a driver unwinding from a mid-scan exception)
        join and apply it exactly once — the losers see None and
        return."""
        with self._recluster_lock:
            fut, self._recluster_future = self._recluster_future, None
        if fut is None:
            if self._recluster_exc is not None:
                # a PAST worker failure: keep raising at every consumer
                # — the cluster assignments are frozen at the last good
                # labels and silently running on would hide that
                raise RuntimeError(
                    "recluster worker failed; cluster assignments are "
                    "stale") from self._recluster_exc
            return
        t0 = time.perf_counter()
        try:
            with TraceAnnotation("fl.recluster.join"):
                (new_ca, labels), comp_s = fut.result()
        except BaseException as e:
            # capture BEFORE raising: the first raise may be swallowed
            # (__del__, a driver's bare except) but every later label
            # consumer — and close() — must see the failure too
            self._recluster_exc = e
            raise
        self.recluster_wait_s += time.perf_counter() - t0
        self.recluster_s += comp_s
        self._apply_recluster(new_ca, labels)

    def _apply_recluster(self, new_ca: np.ndarray, labels: np.ndarray):
        # remap rule (DESIGN.md §12): rows keyed by the canonical labels
        # apply_clusters just produced; hierarchical hands back exactly
        # the C_new live rows (the new static bound — shape change means
        # one retrace per distinct C_new, same as the packing bounds)
        self.age = self.age._replace(
            cluster_age=jnp.asarray(new_ca),
            cluster_of=jnp.asarray(labels, dtype=jnp.int32))
        # tighten the segmented packing to the live clustering — from the
        # labels DBSCAN just produced ON HOST, no new device->host pull
        self._num_seg = int(labels.max()) + 1
        self._max_seg = int(np.bincount(labels).max())

    def close(self):
        """Join any in-flight recluster and release its worker thread.
        Idempotent AND race-safe: the future claim in _recluster_join
        and the pool hand-off below are both atomic, so close() racing
        __del__ (or a second close(), or an unwind from a mid-scan
        exception) joins the worker exactly once and shuts the pool
        down exactly once. Engines are reusable after close — the pool
        is re-created lazily on the next scan-driver recluster. A
        captured worker failure re-raises here too — but only after the
        pool is released, so a failing close() never leaks the
        thread."""
        try:
            self._recluster_join()
        finally:
            with self._recluster_lock:
                pool, self._recluster_pool = self._recluster_pool, None
            if pool is not None:
                pool.shutdown(wait=True)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    @property
    def cluster_of(self) -> np.ndarray:
        self._recluster_join()
        return np.asarray(self.age.cluster_of).astype(np.int64)

    @property
    def client_aoi(self) -> np.ndarray:
        """(N,) rounds since the PS last heard from each client — the
        participation plane's client-level AoI (DESIGN.md §9)."""
        return np.asarray(self.sched.aoi).astype(np.int64)

    @property
    def scheduler(self):
        return self._scheduler

    def eval_acc(self) -> float:
        with TraceAnnotation("fl.eval"):
            return float(jnp.mean(self._eval(self.params_s, self.state_s)))

    def _record(self, res: FLResult, losses, *, end: int, eval_every: int,
                heatmap_at, verbose: bool) -> None:
        """Eval/record/heatmap at the current round — the shared tail of
        both drivers (run() after each step, run_scanned() at chunk
        boundaries, which land exactly on the same rounds). `losses` is
        the CURRENT round's (N,) loss vector; non-participants' entries
        are NaN (they never trained — DESIGN.md §11), so the recorded
        loss is the mean over THIS round's participants."""
        t = self.round_idx
        if t % eval_every == 0 or t == end:
            acc = self.eval_acc()
            loss = float(np.nanmean(losses))
            res.rounds.append(t)
            res.loss.append(loss)
            res.acc.append(acc)
            res.uplink_bytes.append(self.cum_bytes)
            res.cluster_labels.append(self.cluster_of)
            if verbose:
                aoi = (f" aoi={res.aoi_mean[-1]:.1f}/{res.aoi_peak[-1]}"
                       if res.aoi_peak else "")
                print(f"[{self.hp.method}] round {t:4d} "
                      f"loss={loss:.4f} "
                      f"acc={acc:.4f} "
                      f"upl={self.cum_bytes/2**20:.2f}MB{aoi}")
        if t in heatmap_at:
            res.heatmaps[t] = connectivity_matrix(self.freq_matrix)

    def run(self, rounds: int, *, eval_every: int = 5, heatmap_at=(),
            verbose: bool = False, checkpointer=None,
            ckpt_every: int = 0, result: FLResult | None = None
            ) -> FLResult:
        t0 = time.time()
        res = result if result is not None else FLResult()
        end = self.round_idx + rounds
        while self.round_idx < end:
            metrics = self.step()
            self._track(res, metrics, metrics["idx"])
            self._record(res, metrics["losses"], end=end,
                         eval_every=eval_every, heatmap_at=heatmap_at,
                         verbose=verbose)
            if (checkpointer is not None and ckpt_every
                    and self.round_idx % ckpt_every == 0):
                self.save_state(checkpointer, result=res)
        res.wall_s = time.time() - t0
        return res

    # ------------------------------------------------------------------
    # scanned driver: many rounds per dispatch
    # ------------------------------------------------------------------
    def _next_stop(self, end: int, eval_every: int, heatmap_at,
                   ckpt_every: int = 0) -> int:
        """First round after `round_idx` where the host must intervene:
        recluster (every M, rage_k), eval, heatmap, checkpoint, or the
        end."""
        t = self.round_idx
        stops = [end, t + eval_every - t % eval_every]
        if self.hp.method == "rage_k":
            stops.append(t + self.hp.M - t % self.hp.M)
        if ckpt_every:
            stops.append(t + ckpt_every - t % ckpt_every)
        stops.extend(h for h in heatmap_at if h > t)
        return min(stops)

    def _scan_chunk(self, res: FLResult, T: int, *, end: int,
                    eval_every: int, heatmap_at, verbose: bool,
                    checkpointer, ckpt_every: int) -> None:
        """One chunk of :meth:`run_scanned`: dispatch, the wait, and the
        host stop at its end, each under a profiler span (``fl.*``)."""
        # the bounds join an in-flight recluster; a key the chunk cache
        # has not built compiles inside the dispatch (``fl.retrace``)
        fresh = (T, *self._seg_bounds()) not in self._chunk_keys
        with TraceAnnotation("fl.retrace" if fresh else "fl.dispatch"):
            carry, metrics = self._chunk(T)(self._data, self._pack())
        with TraceAnnotation("fl.device_wait"):
            jax.block_until_ready(metrics)
        with TraceAnnotation("fl.host_stop"):
            self._unpack(carry)
            # chunk boundaries align to the every-M recluster, so only
            # the chunk's FINAL round can trigger one — kick the host
            # DBSCAN onto the worker thread now and let it overlap the
            # metrics drain + bookkeeping below; _recluster() joins it
            # before anything reads the new labels
            if (self.hp.method == "rage_k"
                    and (self.round_idx + T) % self.hp.M == 0):
                self._recluster_submit()
            # the ONE per-chunk host pull: (T, N) losses, (T, N, k)
            # indices, (T,)-stacked participation scalars
            with TraceAnnotation("fl.metrics_pull"):
                metrics = {k: np.asarray(v) for k, v in metrics.items()}
            losses = metrics["losses"]
            idx = metrics["idx"] if self.hp.method != "dense" else None
            with TraceAnnotation("fl.bookkeep"):
                for j in range(T):
                    row = self._round_row(metrics, j)
                    self._bookkeep(row["n_active"])
                    self._track(res, row,
                                idx[j] if idx is not None else None)
            self._record(res, losses[-1], end=end, eval_every=eval_every,
                         heatmap_at=heatmap_at, verbose=verbose)
            if (checkpointer is not None and ckpt_every
                    and self.round_idx % ckpt_every == 0):
                with TraceAnnotation("fl.checkpoint"):
                    self.save_state(checkpointer, result=res)

    def run_scanned(self, rounds: int, *, eval_every: int = 5,
                    heatmap_at=(), verbose: bool = False,
                    checkpointer=None, ckpt_every: int = 0,
                    result: FLResult | None = None) -> FLResult:
        """Drive `rounds` with lax.scan chunks — same math as :meth:`run`
        (bit-identical, tests/test_scan_driver.py) but the host touches
        the device once per CHUNK, not once per round: stacked metrics
        come down at chunk ends, which are aligned to the every-M
        recluster round-trip and the eval/heatmap cadence (and, with
        ``ckpt_every``, to the checkpoint cadence — a snapshot is only
        ever taken at a chunk boundary, where the carry is quiescent)."""
        t0 = time.time()
        res = result if result is not None else FLResult()
        end = self.round_idx + rounds
        while self.round_idx < end:
            T = (self._next_stop(end, eval_every, heatmap_at, ckpt_every)
                 - self.round_idx)
            with TraceAnnotation("fl.chunk", round=self.round_idx,
                                 rounds=T):
                self._scan_chunk(res, T, end=end, eval_every=eval_every,
                                 heatmap_at=heatmap_at, verbose=verbose,
                                 checkpointer=checkpointer,
                                 ckpt_every=ckpt_every)
        res.wall_s = time.time() - t0
        return res
