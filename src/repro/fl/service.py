"""Async PS service plane — event-driven buffered aggregation with
age-decayed staleness (DESIGN.md §10).

The engine's rounds are lockstep: even under partial participation the
PS waits for every solicited client, so rounds/sec is bounded by the
slowest client. This module is the production shape the Timely-FL line
points at (Buyukates & Ulukus, PAPERS.md): the PS as a continuously
running server whose throughput is set by AGGREGATION, not stragglers.

The whole service is device-resident and virtual-clocked: a
deterministic per-client latency model (``fl.latency.LatencyModel``,
the same lognormal compute+uplink draw ``fl.schedule.Deadline`` prices
synchronous rounds with, fold_in-keyed so any event is recomputable
from the constant carried key) drives an event loop run as ONE
``lax.scan`` over arrival events. Each scan step:

1. pops the in-flight client with the earliest completion time (ties
   resolve to the lowest client id) and advances the virtual clock;
2. replays that client's local phase (H steps) against the parameter
   snapshot of the version it was actually SENT, read from a bounded
   ring of the last V snapshots — staleness is clipped at V-1 because
   older versions no longer exist (memory bound V*d);
3. selects the client's k upload coordinates — ``solicit='report'``
   (default): the paper's plane, top-r |g| candidates filtered by
   cluster age with in-window disjointness (the shared
   ``engine.select_member_topk``); ``solicit='dispatch'``: the PS
   already solicited the r STALEST coordinates of the client's cluster
   at dispatch time (disjoint from the cluster's other in-flight
   solicitations) and the client uploads the k largest-|g| of them —
   downlink-billed, the rAge-k dual where age narrows to r and
   magnitude picks k;
4. lands the update in a FedBuff-style buffer, weighted by the
   age-decayed staleness discount 1/(1+s)^eta, and applies eq. (2) to
   the client's cluster row (+1, requested reset);
5. if K updates have landed, flushes: one global optimizer step on the
   buffered sum, version += 1, the new snapshot overwrites ring slot
   ``version % V``, buffer and disjointness window reset;
6. re-dispatches the client with the post-flush version; its next
   arrival time is ``clock + latency.dispatch_s(key, client, n)``.

Degenerate pin: at K=N, equal latencies (hetero=jitter=0) and V=1 the
event loop IS the synchronous ``Full`` engine — everyone lands once per
window in client-id order against the current params, the flush is the
round boundary — and tests/test_service.py pins it BIT-IDENTICAL to
``FederatedEngine`` under both drivers across a recluster boundary.

Only metrics leave the device (per chunk); the every-M-aggregations
DBSCAN recluster reuses the engine's host path unchanged.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import load_checkpoint
from repro.configs.base import RAgeKConfig
from repro.core.compression import (bytes_per_index, bytes_per_round,
                                    downlink_bytes_per_round)
from repro.core.strategies import CANDIDATE_IMPLS
from repro.data.pipeline import DeviceShardStore
from repro.fl import client as C
from repro.fl.engine import (DeviceAgeState, _build_model,
                             _recluster_host_packed, apply_global,
                             build_eval_sets, drain_request_log,
                             member_age_row, select_member_topk)
from repro.fl.latency import LatencyModel
from repro.optim.optimizers import adam, sgd

SOLICIT_MODES = ("report", "dispatch")


class ServiceState(NamedTuple):
    """The async PS's entire mutable state, threaded through the event
    scan — chunk boundaries round-trip it through the host untouched,
    so ``run_async(T)`` is invariant to chunking (tests/test_service).

    clock:        () f32   — virtual time (last processed arrival).
    next_done:    (N,) f32 — per-client in-flight completion times.
    sent_version: (N,) i32 — model version each client was dispatched.
    n_dispatch:   (N,) i32 — per-client dispatch counter (latency key).
    version:      () i32   — current global model version.
    ring:         pytree, leaves (V, ...) — last V parameter snapshots;
                  slot v%V holds version v. Memory bound: V*d.
    g_params / g_opt_state — current global model + optimizer.
    buf:          (d,) f32 — FedBuff accumulator (staleness-weighted).
    buf_count:    () i32   — updates landed since the last flush.
    taken:        (C_rows, d) bool — in-window cluster disjointness set,
                  keyed by cluster id (report mode; reset at every
                  flush). C_rows follows the age plane's row count: N
                  under ``age_layout='dense'``, the compacted C_max
                  bound under ``'hierarchical'``.
    solicited:    (N, r) i32  — dispatch mode: the coordinate list the
                  PS solicited from each client at its dispatch.
    inflight:     (N, d) bool — dispatch mode: coordinates currently
                  solicited from ANY in-flight member, per cluster row.
    age:          DeviceAgeState — cluster ages / freq / labels.
    opt_s / state_s / samp — per-client local optimizer, model state
                  (BatchNorm), sampler rows; only the landing client's
                  row advances per event.
    key:          (2,) u32 — constant latency PRNG key.
    n_retry:      (N,) i32 — consecutive failed dispatches per client
                  (fault plane, DESIGN.md §13): drives the bounded
                  virtual-clock backoff of re-solicitations; reset to 0
                  the moment a dispatch lands cleanly.
    """

    clock: jnp.ndarray
    next_done: jnp.ndarray
    sent_version: jnp.ndarray
    n_dispatch: jnp.ndarray
    version: jnp.ndarray
    ring: Any
    g_params: Any
    g_opt_state: Any
    buf: jnp.ndarray
    buf_count: jnp.ndarray
    taken: jnp.ndarray
    solicited: jnp.ndarray
    inflight: jnp.ndarray
    age: DeviceAgeState
    opt_s: Any
    state_s: Any
    samp: Any
    key: jnp.ndarray
    n_retry: jnp.ndarray


@dataclass
class ServiceResult:
    """Per-aggregation curves + per-event traces of one service run."""

    rounds: list = field(default_factory=list)       # aggregation index
    loss: list = field(default_factory=list)         # window mean loss
    acc: list = field(default_factory=list)
    uplink_bytes: list = field(default_factory=list)   # cumulative
    downlink_bytes: list = field(default_factory=list) # cumulative
    clock: list = field(default_factory=list)        # virtual s at eval
    cluster_labels: list = field(default_factory=list)
    # per-EVENT traces (one entry per landing, in event order)
    clients: list = field(default_factory=list)      # landing client id
    staleness: list = field(default_factory=list)    # versions late
    event_clock: list = field(default_factory=list)
    requested: list = field(default_factory=list)    # (k,) idx per event
    # resilience-plane per-event flags (DESIGN.md §13; all-False when
    # faults are off): quarantined by the gate, crashed dispatches,
    # wire-dropped updates, retries scheduled with backoff
    quarantined: list = field(default_factory=list)
    crashed: list = field(default_factory=list)
    dropped: list = field(default_factory=list)
    retried: list = field(default_factory=list)
    wall_s: float = 0.0

    def staleness_hist(self) -> dict:
        vals, counts = np.unique(np.asarray(self.staleness, np.int64),
                                 return_counts=True)
        return {int(v): int(c) for v, c in zip(vals, counts)}

    def summary(self) -> dict:
        virtual_s = float(self.event_clock[-1]) if self.event_clock else 0.0
        aggs = self.rounds[-1] if self.rounds else 0
        return {
            "aggregations": aggs,
            "events": len(self.clients),
            "virtual_s": virtual_s,
            "aggs_per_virtual_s": (aggs / virtual_s if virtual_s else 0.0),
            "final_acc": self.acc[-1] if self.acc else float("nan"),
            "final_loss": self.loss[-1] if self.loss else float("nan"),
            "total_uplink_mb": (self.uplink_bytes[-1] / 2**20
                                if self.uplink_bytes else 0.0),
            "total_downlink_mb": (self.downlink_bytes[-1] / 2**20
                                  if self.downlink_bytes else 0.0),
            "staleness_mean": (float(np.mean(self.staleness))
                               if self.staleness else 0.0),
            "staleness_max": (int(max(self.staleness))
                              if self.staleness else 0),
            "total_quarantined": int(sum(self.quarantined)),
            "total_crashed": int(sum(self.crashed)),
            "total_dropped": int(sum(self.dropped)),
            "total_retried": int(sum(self.retried)),
            "wall_s": self.wall_s,
        }


class AsyncService:
    """The engine as a continuously running server (virtual-clocked).

    Usage::

        svc = AsyncService("mlp", shards, test, hp, seed=0,
                           latency=LatencyModel(len(shards), hetero=1.0))
        res = svc.run_async(aggregations=40, eval_every=5)

    ``hp.buffer_k`` (K; 0 -> N), ``hp.staleness_eta`` (eta of the
    1/(1+s)^eta discount) and ``hp.version_window`` (V) come from
    :class:`RAgeKConfig`; ``latency=None`` means the equal-latency
    degenerate model (hetero=jitter=0 — every dispatch takes exactly
    1.0 virtual seconds), which together with K=N and V=1 is the
    configuration pinned bit-identical to the synchronous engine.
    """

    def __init__(self, kind: str, shards: list, test: tuple,
                 hp: RAgeKConfig, *, seed: int = 0,
                 latency: LatencyModel | None = None,
                 solicit: str = "report", global_opt: str = "adam",
                 faults=None, quarantine: bool = True,
                 gate_bound: float = 1e4, max_retries: int = 3,
                 backoff: float = 2.0):
        if hp.method != "rage_k":
            raise ValueError(
                f"AsyncService runs the rAge-k plane; method "
                f"{hp.method!r} has no age state to solicit from "
                f"(use FederatedEngine)")
        if solicit not in SOLICIT_MODES:
            raise ValueError(f"solicit must be one of {SOLICIT_MODES}, "
                             f"got {solicit!r}")
        if hp.candidates not in CANDIDATE_IMPLS:
            raise ValueError(f"candidates must be one of "
                             f"{CANDIDATE_IMPLS}, got {hp.candidates!r}")
        if hp.r < hp.k:
            raise ValueError(f"need r >= k (got r={hp.r}, k={hp.k})")
        if hp.version_window < 1:
            raise ValueError(f"version_window (V) must be >= 1, got "
                             f"{hp.version_window}")
        if hp.buffer_k < 0 or hp.buffer_k > len(shards):
            raise ValueError(
                f"buffer_k must be in [0, N={len(shards)}] (0 -> N), "
                f"got {hp.buffer_k}")
        if hp.staleness_eta < 0:
            raise ValueError(f"staleness_eta must be >= 0, got "
                             f"{hp.staleness_eta}")
        self.hp = hp
        self.kind = kind
        self.n = len(shards)
        self.seed = seed
        self.K = hp.buffer_k or self.n
        self.V = hp.version_window
        self.eta = float(hp.staleness_eta)
        self._solicit = solicit
        self._latency = latency if latency is not None else LatencyModel(
            self.n, hetero=0.0, jitter=0.0, seed=seed)
        if self._latency.n != self.n:
            raise ValueError(f"latency model is for n={self._latency.n} "
                             f"clients, engine has N={self.n}")
        # resilience plane (fl.faults, DESIGN.md §13): per-dispatch
        # fault fates, a PS-side validation gate, and bounded
        # re-solicitation with virtual-clock backoff on failures
        if faults is not None and faults.n != self.n:
            raise ValueError(f"FaultModel.n={faults.n} != {self.n} clients")
        if max_retries < 0 or backoff < 1.0:
            raise ValueError(f"need max_retries >= 0 and backoff >= 1 "
                             f"(got {max_retries}, {backoff})")
        self._faults = faults
        self._quarantine = bool(quarantine)
        self._gate_bound = float(gate_bound)
        self._max_retries = int(max_retries)
        self._backoff = float(backoff)
        self._fault_key = jax.random.PRNGKey(seed + 77)

        key = jax.random.PRNGKey(seed)
        g_params, state0, apply_loss, predict = _build_model(kind, key)
        self._predict = predict
        self._state0 = state0
        self.d = sum(int(x.size)
                     for x in jax.tree_util.tree_leaves(g_params))
        self._unflatten = C.unflattener(g_params)
        # report mode fuses the top-r candidate report into the client
        # phase's tail (DESIGN.md §11) — same client_candidates row the
        # landing selection previously recomputed from g_i, bitwise
        self._client_phase = C.make_client_phase(
            apply_loss, hp.lr,
            report_r=hp.r if solicit == "report" else None,
            report_impl=hp.candidates)
        self._g_opt = adam(hp.lr) if global_opt == "adam" else sgd(hp.lr)
        self._wire_dtype = jnp.dtype(hp.wire_dtype)

        # --- device state (mirrors the engine's layout) --------------------
        n, d, V = self.n, self.d, self.V
        params_s = C.broadcast_global(g_params, n)
        # age plane layout (DESIGN.md §12): the event loop writes one
        # log slot per LANDING, so the hierarchical ring spans a full
        # recluster window of M aggregations x K landings each
        if hp.age_layout == "hierarchical":
            age0 = DeviceAgeState.create_hierarchical(
                d, n, log_len=hp.M * self.K, m_bound=1, k=hp.k)
            self._freq_host = np.zeros((n, d), np.int32)
        else:
            age0 = DeviceAgeState.create(d, n)
            self._freq_host = None
        self._log_seen = 0
        self.state = ServiceState(
            clock=jnp.float32(0.0),
            next_done=jax.vmap(lambda i: self._latency.dispatch_s(
                key, i, jnp.int32(0)))(jnp.arange(n, dtype=jnp.int32)
                                       ).astype(jnp.float32),
            sent_version=jnp.zeros((n,), jnp.int32),
            n_dispatch=jnp.zeros((n,), jnp.int32),
            version=jnp.int32(0),
            ring=jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p[None], (V,) + p.shape), g_params),
            g_params=g_params,
            g_opt_state=self._g_opt.init(g_params),
            buf=jnp.zeros((d,), jnp.float32),
            buf_count=jnp.int32(0),
            taken=jnp.zeros((n, d), bool),
            solicited=jnp.zeros(
                (n, hp.r if solicit == "dispatch" else 1), jnp.int32),
            inflight=jnp.zeros((n if solicit == "dispatch" else 1, d), bool),
            age=age0,
            opt_s=jax.vmap(adam(hp.lr).init)(params_s),
            state_s=C.stack_clients([state0] * n) if state0 else {},
            samp=None,                       # filled below (needs store)
            key=key,
            n_retry=jnp.zeros((n,), jnp.int32),
        )

        self._store = DeviceShardStore(shards, hp.batch_size,
                                       seed=seed + 17)
        self._data = self._store.data
        self.state = self.state._replace(samp=self._store.init_state())
        if solicit == "dispatch":
            self.state = self.state._replace(
                **self._initial_solicitations(self.state))
        self._eval_sets = build_eval_sets(shards, test)
        self._eval = jax.jit(self._eval_impl)
        self._chunks: dict = {}

        # --- wire accounting (per landing / per dispatch) -------------------
        ib = bytes_per_index(d)
        if solicit == "report":
            # the paper's uplink (k entries + the r-candidate report) and
            # the previously-unbilled downlink: the PS's k-requested list
            self._uplink_per_landing = bytes_per_round(
                hp.k, d, wire_dtype=hp.wire_dtype) + hp.r * ib
            self._downlink_per_dispatch = downlink_bytes_per_round(hp.k, d)
        else:
            # flipped protocol: the solicitation (r stalest indices) goes
            # DOWN at dispatch; only k entries come up
            self._uplink_per_landing = bytes_per_round(
                hp.k, d, wire_dtype=hp.wire_dtype)
            self._downlink_per_dispatch = downlink_bytes_per_round(hp.r, d)
        self.cum_uplink = 0
        self.cum_downlink = self._downlink_per_dispatch * self.n  # t=0 fleet
        self.aggs_done = 0
        self.events_done = 0
        self.recluster_s = 0.0

    # ------------------------------------------------------------------
    # jitted bodies
    # ------------------------------------------------------------------
    def _initial_solicitations(self, st: ServiceState) -> dict:
        """Dispatch mode t=0: solicit the r stalest coordinates of every
        client's cluster row, sequentially in client id order with
        in-flight disjointness (the same discipline the event loop
        maintains afterwards)."""
        r = self.hp.r

        def body(inflight, i):
            cl = st.age.cluster_of[i]
            masked = jnp.where(inflight[cl], jnp.int32(-1),
                               st.age.cluster_age[cl])
            _, sol = jax.lax.top_k(masked, r)
            return inflight.at[cl, sol].set(True), sol.astype(jnp.int32)

        inflight, solicited = jax.lax.scan(
            body, jnp.zeros((self.n, self.d), bool),
            jnp.arange(self.n, dtype=jnp.int32))
        return {"inflight": inflight, "solicited": solicited}

    def _select_landing(self, st: ServiceState, i, cl, g_i, cand=None):
        """The landing client's k upload coordinates + the updated
        disjointness/solicitation state (mode-dependent). ``cand`` is
        the client's fused top-r report (report mode; computed in the
        client phase while the gradient was live, DESIGN.md §11)."""
        hp = self.hp
        if self._solicit == "report":
            idx = select_member_topk(st.age.cluster_age, st.taken, cand,
                                     cl, k=hp.k,
                                     disjoint=hp.disjoint_in_cluster)
            taken = (st.taken.at[cl, idx].set(True, mode="drop")
                     if hp.disjoint_in_cluster else st.taken)
            return idx, taken, st.solicited, st.inflight
        # dispatch mode: the PS solicited `solicited[i]` when it sent the
        # model; the client uploads the k largest-|g| of those r
        sub = st.solicited[i]
        _, sel = jax.lax.top_k(jnp.abs(g_i)[sub], hp.k)
        idx = sub[sel]
        # the completed solicitation frees its coordinates for the
        # cluster's next dispatches (solicitations are disjoint, so only
        # client i holds these marks)
        inflight = st.inflight.at[cl, sub].set(False)
        return idx, st.taken, st.solicited, inflight

    def _resolicit(self, st: ServiceState, inflight, cluster_age, i, cl):
        """Dispatch mode re-dispatch: solicit the r stalest coordinates
        of the client's (just-updated) cluster row, disjoint from the
        cluster's other in-flight solicitations."""
        masked = jnp.where(inflight[cl], jnp.int32(-1), cluster_age[cl])
        _, sol = jax.lax.top_k(masked, self.hp.r)
        sol = sol.astype(jnp.int32)
        return (st.solicited.at[i].set(sol),
                inflight.at[cl, sol].set(True))

    def _event_impl(self, data, st: ServiceState):
        """One arrival event: land, buffer, maybe flush, re-dispatch."""
        hp = self.hp
        n, d, V, K = self.n, self.d, self.V, self.K

        # 1. pop the earliest in-flight completion (ties -> lowest id)
        i = jnp.argmin(st.next_done).astype(jnp.int32)
        t = st.next_done[i]

        # 2. local phase against the snapshot of the version client i
        #    was SENT — clipped to the ring's memory: versions older
        #    than V-1 flushes ago were overwritten (staleness clip)
        eff_v = jnp.maximum(st.sent_version[i], st.version - (V - 1))
        s = st.version - eff_v
        params_i = jax.tree_util.tree_map(lambda rg: rg[eff_v % V], st.ring)
        bx, by, samp = self._store.draw_one(data, st.samp, hp.H, i)
        opt_i = jax.tree_util.tree_map(lambda x: x[i], st.opt_s)
        state_i = (jax.tree_util.tree_map(lambda x: x[i], st.state_s)
                   if st.state_s else {})
        if self._solicit == "report":
            _, opt_i, state_i, g_i, cand_i, loss = self._client_phase(
                params_i, opt_i, state_i, (bx, by))
        else:
            _, opt_i, state_i, g_i, loss = self._client_phase(
                params_i, opt_i, state_i, (bx, by))
            cand_i = None
        opt_s = jax.tree_util.tree_map(
            lambda full, one: full.at[i].set(one), st.opt_s, opt_i)
        state_s = (jax.tree_util.tree_map(
            lambda full, one: full.at[i].set(one), st.state_s, state_i)
            if st.state_s else {})

        # -- fault fate of THIS dispatch (fl.faults, DESIGN.md §13) ---------
        # keyed (client, dispatch count) like the latency draw, so the
        # fate is recomputable from the carried key alone. ``good`` is
        # whether the update actually lands: not crashed, not
        # wire-dropped, and past the validation gate. faults=None
        # (good=None below) traces none of this.
        flt = self._faults
        if flt is not None and flt.any:
            crashed, f_nan, f_inf, f_byz, f_drop = flt.dispatch_fate(
                self._fault_key, i, st.n_dispatch[i])
            g_i = flt.corrupt(g_i, f_nan, f_inf, f_byz)
            row_ok = (jnp.isfinite(g_i).all()
                      & (jnp.abs(g_i).max()
                         <= jnp.float32(self._gate_bound)))
            good = (~crashed) & (~f_drop)
            quar = (good & ~row_ok if self._quarantine
                    else jnp.asarray(False))
            if self._quarantine:
                good = good & row_ok
            # a crashed dispatch never ran: the client's optimizer/
            # BatchNorm/sampler rows hold, its data stream unconsumed
            def hold(new, old):
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(crashed, b, a), new, old)
            opt_s = hold(opt_s, st.opt_s)
            if st.state_s:
                state_s = hold(state_s, st.state_s)
            samp = hold(samp, st.samp)
            loss = jnp.where(crashed, jnp.nan, loss)
        else:
            good = quar = crashed = f_drop = None

        # 3. upload coordinates (mode-dependent selection)
        cl = st.age.cluster_of[i]
        idx, taken, solicited, inflight = self._select_landing(
            st, i, cl, g_i, cand_i)
        if good is not None:
            # failed landings leave the disjointness window untouched
            taken = jnp.where(good, taken, st.taken)

        # 4. land in the buffer, staleness-discounted; eq. (2) on the
        #    cluster row (+1, requested reset), freq counts the upload
        vals = g_i[idx].astype(self._wire_dtype).astype(g_i.dtype)
        w = jnp.power(1.0 + s.astype(jnp.float32), -self.eta)
        vals = jnp.where(s > 0, vals * w.astype(vals.dtype), vals)
        if good is not None:
            # failed dispatch: nothing lands (zeros into the buffer, no
            # count), the cluster row takes eq. (2) with NO reset, and
            # the request never shows in the freq plane
            vals = jnp.where(good, vals, jnp.zeros_like(vals))
        buf = st.buf.at[idx].add(vals.astype(jnp.float32), mode="drop")
        buf_count = st.buf_count + (1 if good is None
                                    else good.astype(jnp.int32))
        row = st.age.cluster_age[cl]
        new_row = member_age_row(row, idx)
        if good is not None:
            new_row = jnp.where(good, new_row, row + 1)
        ca = st.age.cluster_age.at[cl].set(new_row)
        if st.age.freq is not None:
            hit = 1 if good is None else good.astype(jnp.int32)
            age = st.age._replace(
                cluster_age=ca,
                freq=st.age.freq.at[i, idx].add(hit, mode="drop"))
        else:
            # hierarchical layout: the landing appends one slot to the
            # sparse update log (m_bound=1 — one client per event) and
            # bumps the O(N) cumulative upload-cost scalar
            slot = jax.lax.rem(st.age.log_ptr,
                               jnp.int32(st.age.log_idx.shape[0]))
            log_val = idx.astype(jnp.int32)
            cost = jnp.int32(hp.k)
            if good is not None:
                # column d is the drain-time sentinel for "no request"
                log_val = jnp.where(good, log_val, jnp.int32(self.d))
                cost = jnp.where(crashed, jnp.int32(0), cost)
            age = st.age._replace(
                cluster_age=ca,
                log_idx=st.age.log_idx.at[slot, 0].set(log_val),
                log_mem=st.age.log_mem.at[slot, 0].set(i),
                log_ptr=st.age.log_ptr + 1,
                upload_cost=st.age.upload_cost.at[i].add(cost))

        # 5. flush when K updates have landed: one global step on the
        #    buffered sum, new snapshot into ring slot (version+1) % V.
        #    lax.cond, NOT a where-select: cond branches compile as
        #    separate XLA subcomputations, so the adam chain keeps the
        #    exact fused arithmetic of the engine's in-round apply_global
        #    (a fused-in select perturbs its FMA contraction by 1 ulp —
        #    observed, and it breaks the degenerate bitwise pin). It
        #    also runs the global update once per K events, not per
        #    event.
        flush = buf_count >= K
        version = st.version + flush.astype(jnp.int32)

        def do_flush(op):
            buf, gp, go, ring, taken = op
            new_p, new_o = apply_global(self._g_opt, self._unflatten,
                                        buf, gp, go)
            ring = jax.tree_util.tree_map(
                lambda rg, p: rg.at[version % V].set(p), ring, new_p)
            return (jnp.zeros_like(buf), new_p, new_o, ring,
                    jnp.zeros_like(taken), jnp.int32(0))

        def no_flush(op):
            buf, gp, go, ring, taken = op
            return buf, gp, go, ring, taken, buf_count

        buf, g_params, g_opt_state, ring, taken, buf_count = jax.lax.cond(
            flush, do_flush, no_flush,
            (buf, st.g_params, st.g_opt_state, st.ring, taken))

        # 6. re-dispatch client i with the post-flush version. A failed
        #    dispatch is re-solicited with bounded exponential backoff
        #    in VIRTUAL time (latency x backoff^retries, exponent capped
        #    at max_retries) so a dark client cannot monopolise the
        #    event queue; a good landing resets its retry counter.
        nd = st.n_dispatch[i] + 1
        lat = self._latency.dispatch_s(st.key, i, nd).astype(jnp.float32)
        n_retry = st.n_retry
        if good is not None:
            retry = jnp.where(good, jnp.int32(0),
                              jnp.minimum(st.n_retry[i] + 1,
                                          jnp.int32(self._max_retries)))
            lat = lat * jnp.float32(self._backoff) ** retry.astype(
                jnp.float32)
            n_retry = st.n_retry.at[i].set(retry)
        if self._solicit == "dispatch":
            solicited, inflight = self._resolicit(
                st._replace(solicited=solicited), inflight, ca, i, cl)

        new_st = ServiceState(
            clock=t,
            next_done=st.next_done.at[i].set(t + lat),
            sent_version=st.sent_version.at[i].set(version),
            n_dispatch=st.n_dispatch.at[i].set(nd),
            version=version,
            ring=ring, g_params=g_params, g_opt_state=g_opt_state,
            buf=buf, buf_count=buf_count, taken=taken,
            solicited=solicited, inflight=inflight,
            age=age,
            opt_s=opt_s, state_s=state_s, samp=samp, key=st.key,
            n_retry=n_retry)
        off = jnp.asarray(False)
        metrics = {"loss": loss, "client": i, "staleness": s,
                   "version": version, "flushed": flush, "clock": t,
                   "idx": idx.astype(jnp.int32),
                   "quarantined": off if good is None else quar,
                   "crashed": off if good is None else crashed,
                   "dropped": off if good is None
                   else (~crashed) & f_drop,
                   "retried": off if good is None else ~good}
        return new_st, metrics

    def _eval_impl(self, g_params, state_s):
        accs = []
        for i in range(self.n):
            s_i = (jax.tree_util.tree_map(lambda x: x[i], state_s)
                   if state_s else self._state0)
            xe, ye = self._eval_sets[i]
            logits = self._predict(g_params, s_i, xe)
            accs.append(jnp.mean(
                (jnp.argmax(logits, -1) == ye).astype(jnp.float32)))
        return jnp.stack(accs)

    def _chunk(self, length: int):
        fn = self._chunks.get(length)
        if fn is None:
            def chunk(data, st):
                return jax.lax.scan(
                    lambda c, _: self._event_impl(data, c), st, None,
                    length=length)
            fn = self._chunks[length] = jax.jit(chunk)
        return fn

    # ------------------------------------------------------------------
    # host control plane
    # ------------------------------------------------------------------
    def _advance(self, n_events: int) -> dict:
        """Run ``n_events`` arrival events as one jitted scan chunk and
        return the stacked (n_events, ...) metrics as numpy. The carry
        round-trips through ``self.state``, so ANY chunking of the same
        total event count replays the identical event sequence."""
        st, metrics = self._chunk(n_events)(self._data, self.state)
        self.state = st
        self.events_done += n_events
        return {k: np.asarray(v) for k, v in metrics.items()}

    def _recluster(self):
        """The every-M-aggregations host DBSCAN — the engine's recluster
        path verbatim (eq. (3) similarity -> DBSCAN -> age merge). Runs
        at flush boundaries, where the disjointness window is empty; in
        dispatch mode the in-flight solicitation marks are re-keyed to
        the new cluster rows."""
        t0 = time.perf_counter()
        hier = self._freq_host is not None
        if hier:
            # hierarchical layout: fold the sparse log into the host
            # cumulative matrix (the O(m·k·M) pull), cluster on that
            self._log_seen = drain_request_log(
                self.state.age, self._freq_host, self._log_seen,
                n=self.n, d=self.d)
        new_ca, labels = _recluster_host_packed(
            self.state.age, self.hp.eps, self.hp.min_pts,
            freq=self._freq_host, compact=hier)
        age = self.state.age._replace(
            cluster_age=jnp.asarray(new_ca),
            cluster_of=jnp.asarray(labels, jnp.int32))
        self.state = self.state._replace(age=age)
        rows = int(age.cluster_age.shape[0])
        if hier and self.state.taken.shape[0] != rows:
            # cluster-row-keyed scratch follows the compacted C_max
            # bound; reclusters land at flush boundaries, where the
            # disjointness window was just reset — zeros are exact
            self.state = self.state._replace(
                taken=jnp.zeros((rows, self.d), bool))
        if self._solicit == "dispatch":
            cl = age.cluster_of
            inflight = jnp.zeros((rows if hier else self.n, self.d), bool)
            rr = jnp.repeat(cl[:, None], self.hp.r, axis=1)
            inflight = inflight.at[rr, self.state.solicited].set(True)
            self.state = self.state._replace(inflight=inflight)
        self.recluster_s += time.perf_counter() - t0

    def _next_stop(self, end: int, eval_every: int,
                   ckpt_every: int = 0) -> int:
        """Next aggregation count where the host must intervene:
        recluster (every M aggregations), eval, checkpoint, or the
        end."""
        a = self.aggs_done
        stops = [end, a + eval_every - a % eval_every,
                 a + self.hp.M - a % self.hp.M]
        if ckpt_every:
            stops.append(a + ckpt_every - a % ckpt_every)
        return min(stops)

    # ------------------------------------------------------------------
    # checkpoint plane (repro.checkpoint, DESIGN.md §13)
    # ------------------------------------------------------------------
    def state_tree(self) -> dict:
        """The service's complete device state as a checkpointable
        pytree. Under the hierarchical age layout the sparse update log
        is drained into the host freq accumulator first (math-neutral
        at any point), so the saved accumulator + watermark are
        self-consistent."""
        tree = {"state": self.state}
        if self._freq_host is not None:
            self._log_seen = drain_request_log(
                self.state.age, self._freq_host, self._log_seen,
                n=self.n, d=self.d)
            tree["freq_host"] = np.array(self._freq_host)
        return tree

    def _extra_state(self) -> dict:
        return {"aggs_done": int(self.aggs_done),
                "events_done": int(self.events_done),
                "cum_uplink": int(self.cum_uplink),
                "cum_downlink": int(self.cum_downlink),
                "log_seen": int(self._log_seen)}

    def save_state(self, checkpointer):
        """Snapshot the full service onto ``checkpointer`` (an
        AsyncCheckpointer), keyed by the aggregation count."""
        # the tree BEFORE the extras: state_tree's drain moves the
        # log_seen watermark that _extra_state records
        tree = self.state_tree()
        checkpointer.save(self.aggs_done, tree, extra=self._extra_state())

    def load_state(self, source, step: int | None = None):
        """Restore a :meth:`save_state` snapshot from ``source`` (an
        AsyncCheckpointer or a directory path); the continued event
        stream is bit-identical to the uninterrupted one."""
        path = source.path if hasattr(source, "path") else source
        tree, meta = load_checkpoint(path, self.state_tree(), step=step)
        self.state = tree["state"]
        if "freq_host" in tree:
            self._freq_host = np.array(tree["freq_host"])
        ex = meta["extra"]
        self.aggs_done = int(ex["aggs_done"])
        self.events_done = int(ex["events_done"])
        self.cum_uplink = int(ex["cum_uplink"])
        self.cum_downlink = int(ex["cum_downlink"])
        self._log_seen = int(ex["log_seen"])

    def eval_acc(self) -> float:
        accs = self._eval(self.state.g_params, self.state.state_s)
        return float(jnp.mean(accs))

    @property
    def cluster_of(self) -> np.ndarray:
        return np.asarray(self.state.age.cluster_of).astype(np.int64)

    @property
    def age(self) -> DeviceAgeState:
        return self.state.age

    @property
    def freq_matrix(self) -> np.ndarray:
        """Cumulative (N, d) request counts, layout-agnostic (mirrors
        ``FederatedEngine.freq_matrix``): the device matrix under
        'dense', the drained host accumulator under 'hierarchical'."""
        if self.state.age.freq is not None:
            return np.asarray(self.state.age.freq)
        self._log_seen = drain_request_log(
            self.state.age, self._freq_host, self._log_seen,
            n=self.n, d=self.d)
        return self._freq_host

    def run_async(self, aggregations: int, *, eval_every: int = 5,
                  verbose: bool = False, checkpointer=None,
                  ckpt_every: int = 0) -> ServiceResult:
        """Drive the service until ``aggregations`` more buffer flushes
        have happened (every flush consumes exactly K landings, so the
        event count is ``aggregations * K``). Chunk boundaries align to
        the every-M recluster and the eval cadence, both in aggregation
        units; the carry round-trips through ``self.state`` so chained
        calls continue the SAME event stream (chunk invariance is
        pinned by tests/test_service.py)."""
        t0 = time.time()
        res = ServiceResult()
        end = self.aggs_done + aggregations
        faulty = self._faults is not None and self._faults.any
        stall = 0
        while self.aggs_done < end:
            if faulty:
                # faulted dispatches don't land, so events no longer map
                # K:1 onto flushes — advance K events at a time and count
                # the flushes that actually happened. buf_count <= K-1
                # entering a chunk and a chunk lands at most K updates,
                # so at most ONE flush per chunk: the aggregation counter
                # can never overshoot a recluster/eval boundary.
                metrics = self._advance(self.K)
                flushed_now = int(metrics["flushed"].sum())
                assert flushed_now <= 1
                self.aggs_done += flushed_now
                stall = 0 if flushed_now else stall + 1
                if stall >= 1000:
                    raise RuntimeError(
                        f"async service stalled: no flush in the last "
                        f"{stall * self.K} events — the fault rate "
                        f"leaves fewer than K={self.K} live clients")
            else:
                stop = self._next_stop(end, eval_every, ckpt_every)
                n_aggs = stop - self.aggs_done
                metrics = self._advance(n_aggs * self.K)
                assert int(metrics["flushed"].sum()) == n_aggs
                flushed_now = n_aggs
                self.aggs_done = stop
            a = self.aggs_done
            # per-event traces + wire ledger
            res.clients.extend(int(c) for c in metrics["client"])
            res.staleness.extend(int(s) for s in metrics["staleness"])
            res.event_clock.extend(float(c) for c in metrics["clock"])
            res.requested.extend(np.asarray(metrics["idx"]))
            n_ev = len(metrics["client"])
            n_up = n_ev
            if faulty:
                res.quarantined.extend(
                    bool(q) for q in metrics["quarantined"])
                res.crashed.extend(bool(c) for c in metrics["crashed"])
                res.dropped.extend(bool(c) for c in metrics["dropped"])
                res.retried.extend(bool(c) for c in metrics["retried"])
                # crashed clients never put bytes on the wire; dropped/
                # quarantined uploads were sent and paid for
                n_up -= int(metrics["crashed"].sum())
            self.cum_uplink += self._uplink_per_landing * n_up
            # every landing triggers exactly one re-dispatch
            self.cum_downlink += self._downlink_per_dispatch * n_ev
            if (self.hp.method == "rage_k" and flushed_now
                    and a % self.hp.M == 0):
                self._recluster()
            if (checkpointer is not None and ckpt_every and flushed_now
                    and a % ckpt_every == 0):
                self.save_state(checkpointer)
            if flushed_now and (a % eval_every == 0 or a == end):
                acc = self.eval_acc()
                # window loss: mean over the LAST flush window's K
                # landings (the engine's per-round loss, degenerately);
                # crashed dispatches log NaN losses, so the faulted path
                # takes the mean over the landings that ran
                if faulty:
                    win = np.asarray(metrics["loss"][-self.K:])
                    loss_win = (float(np.nanmean(win))
                                if np.isfinite(win).any() else float("nan"))
                else:
                    loss_win = float(metrics["loss"][-self.K:].mean())
                res.rounds.append(a)
                res.loss.append(loss_win)
                res.acc.append(acc)
                res.uplink_bytes.append(self.cum_uplink)
                res.downlink_bytes.append(self.cum_downlink)
                res.clock.append(float(metrics["clock"][-1]))
                res.cluster_labels.append(self.cluster_of)
                if verbose:
                    print(f"[async k={self.K} eta={self.eta} V={self.V}] "
                          f"agg {a:4d} t={res.clock[-1]:8.2f}s "
                          f"loss={res.loss[-1]:.4f} acc={acc:.4f} "
                          f"stale_max={max(res.staleness):d}")
        res.wall_s = time.time() - t0
        return res
