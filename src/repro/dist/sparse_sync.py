"""Sparse (rAge-k) gradient synchronization — the paper's protocol as a
data-parallel collective (DESIGN.md §4).

Age state is a pytree of int32 arrays shaped like the params: one age per
coordinate, bucketed per leaf with the global (r, k) budget split
proportionally (``core.sparsify.bucket_budgets``). Selection per bucket
goes through the SAME ``core.strategies`` classes as the FL engine — the
sharded sync is just another backend of the Strategy API.

Two entry points:

``make_sync_train_step``  — single-program (GSPMD) step: grads are
    sparsified in place of a dense exchange; the partitioner moves the
    k-entry payloads. CPU-scale drivers (launch/train.py, examples/).

``make_manual_sync``      — explicit shard_map exchange for production
    meshes: each data shard selects its k entries per bucket LOCALLY,
    all-gathers (idx, vals) over the data axes, and scatter-adds. Params
    must be replicated over the data axes (lower_combo passes
    rules={"fsdp": None}); the model axes keep their shards untouched.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.sparsify import bucket_budgets
from repro.core.strategies import make_strategy
from repro.optim.optimizers import apply_updates

# Indices here are accounted at 4 B: the shard_map exchange physically
# all-gathers int32 index arrays, so that IS the wire payload of this
# implementation. The idealized ceil(log2(d)/8) sizing (what an
# entropy-aware encoding would need — see core.compression.bytes_per_index)
# applies to the FL protocol accounting, not to this collective.
_INDEX_BYTES = 4


def init_age_state(params, *, method: str = "rage_k"):
    """Age pytree: int32 zeros shaped like every param leaf. For
    ``method='cafe'`` each leaf gains a leading (2,) axis: row 0 the age
    vector, row 1 the cumulative upload-cost counter the CAFe score
    discounts by.

    Note the relation to the FL engine's hierarchical age plane
    (``fl.engine.DeviceAgeState``, DESIGN.md §12): the manual sync's
    union-age semantics treat the whole data axis as ONE cluster, so
    this pytree IS the cluster-keyed layout at C=1 — one (d,) row total
    (bucketed per leaf), independent of the number of data shards. The
    per-client (N, d) matrices only exist in the engine's dense layout;
    the distributed collective never had them to shrink."""
    lead = (2,) if method == "cafe" else ()
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(lead + tuple(p.shape), jnp.int32), params)


def age_state_bytes(ages) -> int:
    """Device bytes of a sync age pytree — the distributed analogue of
    ``DeviceAgeState.device_bytes``. Under union-age semantics this is
    O(d) (x2 for cafe's cost lane) no matter how many data shards
    participate: the C=1 cluster-keyed row of the hierarchical memory
    model, which is what benchmarks compare engine layouts against."""
    return sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(ages))


def init_age_state_sharded(shapes, *, method: str = "rage_k"):
    """Same as init_age_state but from ShapeDtypeStructs (abstract
    params); usable under jax.eval_shape for lowering-only paths."""
    return init_age_state(shapes, method=method)


def _wire_bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _leaf_sizes(shapes) -> list:
    return [int(jnp.prod(jnp.asarray(l.shape))) if l.shape else 1
            for l in jax.tree_util.tree_leaves(shapes)]


def _select_bucket(method: str, flat, age_flat, r_b: int, k_b: int,
                   lam: float = 0.1, candidates: str = "sort"):
    """One bucket's selection via the Strategy API. Returns
    (idx (k_b,), vals (k_b,), new_age_flat). For 'cafe' ``age_flat`` is
    the stacked (2, d_b) [age; cost] state (init_age_state layout)."""
    d_b = flat.shape[0]
    r_b, k_b = min(r_b, d_b), min(k_b, d_b)
    strat = make_strategy(method, r=r_b, k=k_b, lam=lam,
                          candidates=candidates)
    if method == "rage_k":
        return strat.select(flat, age_flat)
    if method == "cafe":
        idx, vals, (na, nc) = strat.select(flat, (age_flat[0], age_flat[1]))
        return idx, vals, jnp.stack([na, nc])
    if method in ("top_k",):
        idx, vals, _ = strat.select(flat, ())
        return idx, vals, age_flat
    raise ValueError(
        f"sparse_sync supports 'rage_k' | 'cafe' | 'top_k' | 'dense', got "
        f"{method!r} (stochastic baselines need per-step keys; use the FL "
        "engine)")


def _flat_age(a, method: str):
    """Bucket view of one age leaf: (d_b,) for rage_k, (2, d_b) for cafe."""
    return a.reshape(2, -1) if method == "cafe" else a.reshape(-1)


# ---------------------------------------------------------------------------
# single-program (GSPMD) sync
# ---------------------------------------------------------------------------

def make_sync_train_step(loss_fn, opt, mesh, *, method: str = "rage_k",
                         r: int = 0, k: int = 0,
                         wire_dtype=jnp.bfloat16, lam: float = 0.1,
                         candidates: str = "sort"):
    """Returns step(params, opt_state, ages, batch) ->
    (params, opt_state, ages, loss, stats).

    The gradient is replaced by its wire form before the optimizer:
    dense -> a wire_dtype cast round-trip; sparse -> the k_b selected
    entries per bucket (everything else zero), ages updated per eq. (2)
    ('cafe' additionally threads the per-leaf cost counters; ``lam`` is
    its cost weight). stats["wire_bytes_per_shard"] counts
    k_b * (4B index + wire value).
    """
    del mesh  # GSPMD path: partitioning is inferred; kept for API parity
    vb = _wire_bytes(wire_dtype)

    def step(params, opt_state, ages, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        age_leaves = jax.tree_util.tree_leaves(ages)
        sizes = [int(l.size) for l in leaves]
        if method == "dense":
            synced = [l.astype(wire_dtype).astype(l.dtype) for l in leaves]
            new_ages = age_leaves
            wire = sum(sizes) * vb
        else:
            budgets = bucket_budgets(sizes, r, k)
            synced, new_ages = [], []
            wire = 0
            for l, a, (r_b, k_b) in zip(leaves, age_leaves, budgets):
                flat = l.reshape(-1)
                idx, vals, new_a = _select_bucket(
                    method, flat, _flat_age(a, method), r_b, k_b, lam=lam,
                    candidates=candidates)
                vals = vals.astype(wire_dtype).astype(flat.dtype)
                synced.append(
                    jnp.zeros_like(flat).at[idx].set(vals).reshape(l.shape))
                new_ages.append(new_a.reshape(a.shape))
                wire += min(k_b, int(flat.shape[0])) * (_INDEX_BYTES + vb)
        synced = jax.tree_util.tree_unflatten(treedef, synced)
        new_ages = jax.tree_util.tree_unflatten(treedef, new_ages)
        updates, opt_state = opt.update(synced, opt_state, params)
        params = apply_updates(params, updates)
        stats = {"wire_bytes_per_shard": jnp.int32(wire)}
        return params, opt_state, new_ages, loss, stats

    return step


# ---------------------------------------------------------------------------
# explicit shard_map sync (production meshes)
# ---------------------------------------------------------------------------

def make_manual_sync(mesh, specs, shapes, *, method: str = "rage_k",
                     candidates: str = "sort",
                     r: int = 0, k: int = 0, wire_dtype=jnp.bfloat16,
                     lam: float = 0.1, validate: bool = False,
                     gate_bound: float = 1e4):
    """Explicit gradient exchange over the mesh's data axes.

    specs/shapes: pytrees of PartitionSpec / ShapeDtypeStruct for the
    grads (= params). Returns sync(grads, ages) -> (synced, new_ages,
    stats); the closure exposes ``.age_specs`` (ages sharded like grads;
    for 'cafe' the stacked (2, ...) [age; cost] leaves replicate their
    leading axis).

    Each data shard selects its k_b entries per bucket from its LOCAL
    gradient (its microbatch's view), all-gathers the (idx, vals)
    payloads over the data axes, and scatter-adds the union divided by
    the shard count (a sparse pmean). Ages are updated with the UNION of
    requested indices — the merged-vector semantics of the paper's
    cluster age (§II) applied to data shards ('cafe' additionally counts
    the union into the cost lane).

    Participation plane (DESIGN.md §9): ``sync(grads, ages,
    active=mask)`` takes an (n_data,) bool mask over the flattened data
    shards — inactive shards contribute NO payload to the gather
    (sentinel indices, dropped), the union divides by the ACTIVE shard
    count, and ages advance with the active union only (absent shards'
    unrequested coordinates keep aging, eq. (2) with no reset).
    ``active=None`` is the full synchronous exchange, bit-identical to
    the pre-plane collective. stats: ``wire_bytes_per_shard`` is what an
    UPLOADING shard sends (inactive shards send nothing);
    ``wire_bytes_total = wire_bytes_per_shard * senders`` is the
    round's true uplink — the number partial-participation accounting
    must total, since the per-shard figure alone would overbill absent
    shards.

    Validation gate (DESIGN.md §13): with ``validate=True`` a shard
    whose LOCAL gradient is non-finite or out-of-band
    (max |g| > ``gate_bound``) is quarantined — it contributes no
    payload to the union and no age hits (its requested coordinates
    keep aging, eq. (2) with no reset), exactly like an inactive shard;
    but it DID send, so ``wire_bytes_total`` still bills it.
    ``stats["quarantined_shards"]`` counts the gated shards; the gate
    is opt-in because the traced mask path changes the dense pmean to a
    psum/count (1-ulp-class difference the bitwise pins can't absorb).
    """
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    sizes = _leaf_sizes(shapes)
    spec_leaves_for_budget = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))

    def _shard_count(spec) -> int:
        """Model-axis shards of one leaf (its replica group is 'one
        client'; params are data-replicated under manual sync)."""
        n = 1
        for entry in spec:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                n *= mesh.shape.get(a, 1)
        return n

    if method != "dense":
        # split each leaf's GLOBAL (r_b, k_b) across its model shards,
        # so the whole replica group uploads k_b entries, not shards*k_b
        budgets = []
        for (r_b, k_b), spec in zip(bucket_budgets(sizes, r, k),
                                    spec_leaves_for_budget):
            ns = _shard_count(spec)
            r_l = max(1, r_b // ns)
            k_l = max(1, min(r_l, k_b // ns if k_b >= ns else 1))
            budgets.append((r_l, k_l))
    else:
        budgets = [(0, 0)] * len(sizes)
    vb = _wire_bytes(wire_dtype)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    treedef = jax.tree_util.tree_structure(shapes)

    def _make_exchange(masked: bool):
        def _exchange(*flat_args):
            if masked:
                # (n_data,) replicated participation mask; this shard's
                # flattened data index picks its own activity bit
                active, flat_args = flat_args[0], flat_args[1:]
                fidx = jnp.int32(0)
                for ax in data_axes:
                    fidx = fidx * mesh.shape[ax] + jax.lax.axis_index(ax)
                my = active[fidx]
                n_senders = active.sum().astype(jnp.int32)
            else:
                my = None
                n_senders = jnp.int32(n_data)
            n = len(flat_args) // 2
            g_leaves, age_leaves = flat_args[:n], flat_args[n:]
            n_quar = jnp.int32(0)
            if validate:
                # quarantine: a non-finite/out-of-band local payload is
                # excluded like an inactive shard's. ok is per-shard
                # (unreplicated), so the landed count is a psum
                ok = jnp.bool_(True)
                for g in g_leaves:
                    fg = g.reshape(-1).astype(jnp.float32)
                    ok = (ok & jnp.isfinite(fg).all()
                          & (jnp.abs(fg).max() <= jnp.float32(gate_bound)))
                my = ok if my is None else my & ok
                n_uploaders = (jax.lax.psum(my.astype(jnp.int32), data_axes)
                               if data_axes else my.astype(jnp.int32))
                n_quar = n_senders - n_uploaders
            else:
                n_uploaders = n_senders
            if my is not None:
                n_act = jnp.maximum(n_uploaders, 1).astype(jnp.float32)
            else:
                n_act = n_data
            synced, new_ages = [], []
            wire = 0
            for g, a, (r_b, k_b) in zip(g_leaves, age_leaves, budgets):
                flat = g.reshape(-1).astype(jnp.float32)
                if method == "dense":
                    w = flat.astype(wire_dtype).astype(jnp.float32)
                    if my is not None:
                        w = jnp.where(my, w, 0.0)
                        if data_axes:
                            w = jax.lax.psum(w, data_axes)
                        w = w / n_act
                    elif data_axes:
                        w = jax.lax.pmean(w, data_axes)
                    synced.append(w.reshape(g.shape).astype(g.dtype))
                    new_ages.append(a)
                    wire += flat.shape[0] * vb
                    continue
                af = _flat_age(a, method)
                idx, vals, _ = _select_bucket(
                    method, flat, af, r_b, k_b, lam=lam,
                    candidates=candidates)
                vals = vals.astype(wire_dtype)
                if my is not None:
                    # inactive shard: sentinel indices (dropped from the
                    # union scatter AND the age hits), zero payload
                    idx = jnp.where(my, idx, jnp.int32(flat.shape[0]))
                    vals = jnp.where(my, vals,
                                     jnp.zeros((), vals.dtype))
                if data_axes:
                    idx = jax.lax.all_gather(idx, data_axes, tiled=True)
                    vals = jax.lax.all_gather(vals, data_axes, tiled=True)
                dense = jnp.zeros_like(flat).at[idx].add(
                    vals.astype(jnp.float32) / n_act, mode="drop")
                hit = jnp.zeros(flat.shape, bool).at[idx].set(
                    True, mode="drop")
                if method == "cafe":
                    # union semantics on the age lane; the union also
                    # counts into the cost lane (one upload of every
                    # union index)
                    new_a = jnp.stack([
                        jnp.where(hit, 0, af[0] + 1),
                        af[1] + hit.astype(jnp.int32)]).astype(jnp.int32)
                else:
                    new_a = jnp.where(hit, 0, af + 1).astype(jnp.int32)
                synced.append(dense.reshape(g.shape).astype(g.dtype))
                new_ages.append(new_a.reshape(a.shape))
                wire += min(k_b, int(flat.shape[0])) * (_INDEX_BYTES + vb)
            # per-shard counts bytes an UPLOADING shard sends; the round
            # total multiplies by the shards that actually SENT — a
            # quarantined shard paid for its rejected upload.
            # wire is static, so the int32-overflow check is too: dense
            # LM-scale payloads x many shards exceed 2^31 — go float32
            # there instead of wrapping negative
            if wire * n_data < 2 ** 31:
                total = jnp.int32(wire) * n_senders
            else:
                total = jnp.float32(wire) * n_senders.astype(jnp.float32)
            stats = {"wire_bytes_per_shard": jnp.int32(wire),
                     "active_shards": n_uploaders,
                     "wire_bytes_total": total,
                     "quarantined_shards": n_quar}
            return tuple(synced) + tuple(new_ages) + (stats,)
        return _exchange

    if method == "cafe":
        # stacked (2, ...) [age; cost] leaves: the leading axis is
        # replicated, the param dims keep the grad sharding
        age_spec_leaves = [P(*((None,) + tuple(s))) for s in spec_leaves]
    else:
        age_spec_leaves = list(spec_leaves)
    in_specs = tuple(spec_leaves) + tuple(age_spec_leaves)
    out_specs = (tuple(spec_leaves) + tuple(age_spec_leaves)
                 + ({"wire_bytes_per_shard": P(), "active_shards": P(),
                     "wire_bytes_total": P(),
                     "quarantined_shards": P()},))
    # check_vma off: each shard's gradient is its own (local microbatch)
    # although its spec replicates it over the data axes
    mapped = jax.shard_map(_make_exchange(False), mesh=mesh,
                           in_specs=in_specs, out_specs=out_specs,
                           check_vma=False)
    # participation-masked variant: the (n_data,) active mask rides
    # replicated ahead of the leaves
    mapped_act = jax.shard_map(_make_exchange(True), mesh=mesh,
                               in_specs=(P(None),) + in_specs,
                               out_specs=out_specs, check_vma=False)

    def sync(grads, ages, active=None):
        g_leaves = jax.tree_util.tree_leaves(grads)
        age_leaves = jax.tree_util.tree_leaves(ages)
        if active is None:
            out = mapped(*g_leaves, *age_leaves)
        else:
            active = jnp.asarray(active, bool)
            if active.shape != (n_data,):
                raise ValueError(
                    f"active mask must have shape ({n_data},) — one bit "
                    f"per flattened data shard — got {active.shape}")
            out = mapped_act(active, *g_leaves, *age_leaves)
        n = len(g_leaves)
        synced = jax.tree_util.tree_unflatten(treedef, out[:n])
        new_ages = jax.tree_util.tree_unflatten(treedef, out[n:2 * n])
        return synced, new_ages, out[-1]

    sync.n_data = n_data

    # ages are sharded exactly like grads (cafe: leading lane replicated)
    sync.age_specs = (jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(
            specs, is_leaf=lambda x: isinstance(x, P)), age_spec_leaves)
        if method == "cafe" else specs)
    return sync


# ---------------------------------------------------------------------------
# buffered (FedBuff-style) union — the async service plane's collective
# ---------------------------------------------------------------------------

class _BufferState:
    """Carried accumulator of :func:`make_buffered_sync` — created by
    ``.init_buffer()``, threaded through every call."""

    __slots__ = ("sums", "count")

    def __init__(self, sums, count):
        self.sums = sums          # pytree of f32 running union sums
        self.count = count        # () int32: shard-updates buffered


def _buffer_flatten(b):
    return (b.sums, b.count), None


def _buffer_unflatten(_, children):
    return _BufferState(*children)


jax.tree_util.register_pytree_node(_BufferState, _buffer_flatten,
                                   _buffer_unflatten)
BufferState = _BufferState


def make_buffered_sync(mesh, specs, shapes, *, buffer_k: int,
                       method: str = "rage_k", candidates: str = "sort",
                       r: int = 0, k: int = 0, wire_dtype=jnp.bfloat16,
                       lam: float = 0.1, validate: bool = False,
                       gate_bound: float = 1e4):
    """FedBuff-style buffered wrapper over :func:`make_manual_sync` —
    the async service plane's semantics (DESIGN.md §10) expressed on the
    sharded collective: each call lands that round's ACTIVE-shard union
    into a running buffer instead of applying it, and the mean update is
    released only once ``buffer_k`` shard-updates have accumulated.

    Returns ``sync(grads, ages, buf, active=None) -> (synced, new_ages,
    new_buf, stats)``. ``synced`` is zero (a bitwise no-op update) on
    buffering calls and the buffered mean — sum of landed updates over
    the number of landed shard-updates — on flushing calls; ages advance
    with every call's union exactly as the unbuffered sync (age is a
    property of requests, not of application). stats adds ``flushed``
    (bool) and ``buffered_shards`` (post-call count, 0 after a flush).

    ``buffer_k=1`` (with full participation of a single data shard) is
    call-by-call equivalent to the base sync: every call flushes its own
    mean. More generally any call reaching ``count >= buffer_k`` flushes
    sums/count, which for one full-participation round equals the base
    sync's pmean — pinned by tests/test_dist.py. The closure re-exports
    ``.n_data`` / ``.age_specs`` and adds ``.init_buffer()``.
    """
    if buffer_k < 1:
        raise ValueError(f"buffer_k must be >= 1, got {buffer_k}")
    base = make_manual_sync(mesh, specs, shapes, method=method,
                            candidates=candidates, r=r, k=k,
                            wire_dtype=wire_dtype, lam=lam,
                            validate=validate, gate_bound=gate_bound)

    def init_buffer() -> _BufferState:
        sums = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, jnp.float32), shapes)
        return _BufferState(sums, jnp.int32(0))

    def sync(grads, ages, buf: _BufferState, active=None):
        synced, new_ages, stats = base(grads, ages, active=active)
        n_act = stats["active_shards"]
        # undo the base sync's active-shard mean: the buffer holds SUMS,
        # so flushes landing across rounds with different participation
        # weight every shard-update equally
        sums = jax.tree_util.tree_map(
            lambda b, s: b + s.astype(jnp.float32)
            * n_act.astype(jnp.float32), buf.sums, synced)
        count = buf.count + n_act
        flush = count >= jnp.int32(buffer_k)
        denom = jnp.maximum(count, 1).astype(jnp.float32)
        out = jax.tree_util.tree_map(
            lambda s, g: jnp.where(flush, (s / denom).astype(g.dtype),
                                   jnp.zeros_like(g)),
            sums, synced)
        new_sums = jax.tree_util.tree_map(
            lambda s: jnp.where(flush, jnp.zeros_like(s), s), sums)
        new_count = jnp.where(flush, jnp.int32(0), count)
        stats = dict(stats, flushed=flush, buffered_shards=new_count)
        return out, new_ages, _BufferState(new_sums, new_count), stats

    sync.n_data = base.n_data
    sync.age_specs = base.age_specs
    sync.init_buffer = init_buffer
    return sync
