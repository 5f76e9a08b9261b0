"""Plain reference of the federated rounds the benchmark times.

It follows the rAge-k protocol of the paper (arXiv 2410.22192, Algorithm 1)
from the seed, in straightforward JAX and NumPy, one client at a time:

0. the round's participants are drawn by the configuration's ``schedule``:
   ``full`` takes every client; ``uniform`` takes m of the N clients (m is
   ``participation_m``, or max(N // 4, 1) where that is 0): the first m of
   ``permutation(fold_in(PRNGKey(seed + SCHEDULE_KEY), round), N)``, the
   round counted from 0. Participants are taken in ascending client id;
1. each participant draws its next H batches from its own shuffled stream
   and takes H Adam steps from the global parameters (its Adam moments and
   batch-norm statistics persist across rounds); its gradient at the last
   step is its update. A client that sits a round out trains nothing: its
   moments, statistics and stream stay as they were, and its loss is NaN;
2. it reports the indices of its r largest gradient magnitudes;
3. the parameter server picks, in client order, the k candidates of
   highest age in the participant's cluster, skipping those already picked
   for the cluster this round, reading the ages of the round's start; then
   each cluster's age vector grows by one for every member that sat the
   round out (eq. 2 for a member not heard from: no reset), and, member by
   member over the participants, grows by one and the picked coordinates
   reset to 0 (eq. 2); the picks are counted (eq. 3 inputs). A client that
   sits out requests nothing: its row of picks holds the sentinel d;
4. the picked values are summed into a dense gradient and the global model
   takes one Adam step;
5. every M rounds, the clients are clustered by DBSCAN on the symmetrised
   eq. (3) similarity of their request counts: a new cluster that wholly
   contains old ones takes the elementwise minimum of their age vectors,
   any other starts from zero.

It imports nothing of the program. Matrix products run at ``highest``
precision; ``dtype`` sets the precision of every array, so the same code in
bfloat16 is the control that the comparison must fail. ``precision``
"default" (one bfloat16 pass, as the configuration states the program's
products) makes it a witness of how far two sound float32 runs part.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8

# the program's PRNG streams, as offsets from the run's seed
MODEL_KEY, SAMPLER_KEY, SCHEDULE_KEY = 0, 17, 23
# the participation plans the reference follows
SCHEDULES = ("full", "uniform")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def adam_init(params, dtype):
    zeros = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, dtype), params)
    return (jnp.zeros((), jnp.int32), zeros, zeros)


def adam_step(params, opt, grads, lr, dtype):
    step, mu, nu = opt
    step = step + 1
    # the bias corrections are scalars, kept in float32 whatever ``dtype``
    b1t = 1 - B1 ** step.astype(jnp.float32)
    b2t = 1 - B2 ** step.astype(jnp.float32)
    mu = jax.tree_util.tree_map(lambda m, g: (B1 * m + (1 - B1) * g)
                                .astype(dtype), mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: (B2 * v + (1 - B2) * g * g)
                                .astype(dtype), nu, grads)
    params = jax.tree_util.tree_map(
        lambda p, m, v: (p - lr * (m / b1t) / (jnp.sqrt(v / b2t) + EPS))
        .astype(dtype), params, mu, nu)
    return params, (step, mu, nu)


def flatten(tree):
    return jnp.concatenate([a.reshape(-1)
                            for a in jax.tree_util.tree_leaves(tree)])


def unflatten(flat, template):
    leaves, treedef = jax.tree_util.tree_flatten(template)
    out, o = [], 0
    for a in leaves:
        out.append(flat[o:o + a.size].reshape(a.shape))
        o += a.size
    return jax.tree_util.tree_unflatten(treedef, out)


def _perm(key, length, capacity):
    u = jax.random.uniform(key, (capacity,))
    u = jnp.where(jnp.arange(capacity) < length, u, 2.0)
    return jnp.argsort(u).astype(jnp.int32)


def stream_start(base, i, length, capacity):
    """Client i's batch stream before its first draw: (order, pos, key)."""
    key, sub = jax.random.split(jax.random.fold_in(base, i))
    return _perm(sub, length, capacity), jnp.int32(0), key


def stream_draw(stream, length, capacity, bs, H):
    """The next H batches of one client's stream: an epoch is a fresh
    permutation of its samples, a batch the next ``bs`` of it, and a new
    epoch starts when fewer than ``bs`` remain. Returns ((H, bs) sample
    indices, the advanced stream)."""

    def step(carry, _):
        order, pos, key = carry
        wrap = pos + bs > length
        key, sub = jax.random.split(key)
        order = jnp.where(wrap, _perm(sub, length, capacity), order)
        pos = jnp.where(wrap, 0, pos)
        sel = jax.lax.dynamic_slice(order, (pos,), (bs,))
        return (order, pos + bs, key), sel

    stream, sel = jax.lax.scan(step, stream, None, length=H)
    return sel, stream


def similarity(freq: np.ndarray) -> np.ndarray:
    """Eq. (3): s[i, j] = <f_i, f_j> / <f_i, f_i> (0 rows stay 0)."""
    f = freq.astype(np.float64)
    g = f @ f.T
    diag = np.diag(g).copy()
    diag[diag == 0] = 1.0
    return g / diag[:, None]


def dbscan(dist: np.ndarray, eps: float, min_pts: int) -> np.ndarray:
    """DBSCAN over a distance matrix; noise points get their own ids."""
    n = dist.shape[0]
    nbrs = [np.nonzero(dist[i] <= eps)[0] for i in range(n)]
    core = [len(nb) >= min_pts for nb in nbrs]
    labels = np.full(n, -1)
    cid = 0
    for i in range(n):
        if labels[i] >= 0 or not core[i]:
            continue
        labels[i] = cid
        frontier = list(nbrs[i])
        while frontier:
            j = frontier.pop()
            if labels[j] >= 0:
                continue
            labels[j] = cid
            if core[j]:
                frontier.extend(nbrs[j])
        cid += 1
    for i in range(n):
        if labels[i] < 0:
            labels[i] = cid
            cid += 1
    return labels


def cluster(freq: np.ndarray, eps: float, min_pts: int) -> np.ndarray:
    s = similarity(freq)
    dist = 1.0 - np.clip((s + s.T) / 2.0, 0.0, 1.0)
    np.fill_diagonal(dist, 0.0)
    return dbscan(dist, eps, min_pts)


def regroup(ages: dict, old: np.ndarray, new: np.ndarray, d: int) -> dict:
    """Age vectors of the new clusters: the elementwise minimum of the old
    clusters that the new one wholly contains, zeros where there is none."""
    out = {}
    for c in np.unique(new):
        members = set(np.nonzero(new == c)[0].tolist())
        vecs = [ages[p] for p in {int(old[m]) for m in members}
                if set(np.nonzero(old == p)[0].tolist()) <= members]
        out[int(c)] = (np.minimum.reduce(vecs) if vecs
                       else np.zeros(d, np.int32))
    return out


@dataclass
class Trajectory:
    """What the reference (or the program) did over its rounds."""
    losses: list = field(default_factory=list)     # per round, (N,), NaN
    #                                                for who sat it out
    picks: list = field(default_factory=list)      # per round, (N, k), d
    #                                                for who sat it out
    labels: list = field(default_factory=list)     # per recluster, (N,)
    params0: dict | None = None                    # global, before round 1
    params: dict | None = None                     # global, after the rounds
    global_mu: dict | None = None                  # global Adam first moment
    client_mu: list | None = None                  # per client, Adam mu
    client_ages: np.ndarray | None = None          # (N, d) per-client ages
    freq: np.ndarray | None = None                 # (N, d) request counts


def check_plan(proto: dict) -> None:
    """Refuse a participation plan the reference does not follow, so that
    no cell is checked against one."""
    schedule = proto.get("schedule", "full")
    if schedule not in SCHEDULES:
        raise NotImplementedError(
            f"the reference follows the schedules {SCHEDULES}, not "
            f"{schedule!r}")


def participants(proto: dict, n: int, seed: int, rnd: int) -> np.ndarray:
    """(N,) bool: who takes part in round ``rnd`` (counted from 0)."""
    check_plan(proto)
    if proto.get("schedule", "full") == "full":
        return np.ones(n, bool)
    m = proto.get("participation_m", 0) or max(n // 4, 1)
    key = jax.random.fold_in(jax.random.PRNGKey(seed + SCHEDULE_KEY), rnd)
    active = np.zeros(n, bool)
    active[np.asarray(jax.random.permutation(key, n))[:m]] = True
    return active


class Reference:
    """The federation of one configuration, followed from the seed."""

    def __init__(self, cfg: dict, proto: dict, model, shards: list,
                 seed: int, dtype=jnp.float32, precision: str = "highest"):
        check_plan(proto)
        self.cfg, self.p, self.model, self.dtype = cfg, proto, model, dtype
        self.seed, self.precision = seed, precision
        self.n = len(shards)
        lengths = [len(y) for _, y in shards]
        self.lengths = jnp.asarray(lengths, jnp.int32)
        self.cap = max(lengths)
        self.bs = min(proto["batch_size"], min(lengths))
        # the shards, zero-padded to one (N, capacity, ...) block; padding
        # is never drawn
        x = np.zeros((self.n, self.cap) + shards[0][0].shape[1:], np.float32)
        y = np.zeros((self.n, self.cap), np.int32)
        for i, (xi, yi) in enumerate(shards):
            x[i, :len(yi)], y[i, :len(yi)] = xi, yi
        self.x, self.y = jnp.asarray(x, dtype), jnp.asarray(y)
        start = jax.jit(stream_start, static_argnums=3)
        base = jax.random.PRNGKey(seed + SAMPLER_KEY)
        self.streams = [start(base, i, self.lengths[i], self.cap)
                        for i in range(self.n)]
        params, bn = jax.jit(model.init, static_argnums=(1, 2))(
            jax.random.PRNGKey(seed + MODEL_KEY), _Frozen(cfg), dtype)
        self.params = params
        self.d = int(sum(a.size for a in jax.tree_util.tree_leaves(params)))
        init = jax.jit(adam_init, static_argnums=1)
        self.opt = init(params, dtype)
        self.client_opt = [init(params, dtype) for _ in range(self.n)]
        self.client_bn = [bn for _ in range(self.n)]
        self.cluster_of = np.arange(self.n)
        self.ages = {i: np.zeros(self.d, np.int32) for i in range(self.n)}
        self.freq = np.zeros((self.n, self.d), np.int32)
        self.round = 0
        self._client = jax.jit(self._client_impl)
        self._global = jax.jit(self._global_impl)
        self.traj = Trajectory(params0=jax.device_get(params))

    def _loss(self, params, bn, x, y):
        logits, bn = self.model.apply(params, bn, x, self.cfg)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1)), bn

    def _client_impl(self, params, opt, bn, stream, i, x, y):
        """Steps 1 and 2 for client i: its next H batches, H Adam steps
        from the global parameters, the last step's gradient, and the
        report of its r largest magnitudes."""
        lr, dtype = self.p["lr"], self.dtype
        sel, stream = stream_draw(stream, self.lengths[i], self.cap, self.bs,
                                  self.p["H"])
        bx, by = x[i][sel], y[i][sel]

        def step(carry, batch):
            params, opt, bn, _ = carry
            with jax.default_matmul_precision(self.precision):
                (loss, bn), g = jax.value_and_grad(self._loss, has_aux=True)(
                    params, bn, *batch)
            params, opt = adam_step(params, opt, g, lr, dtype)
            return (params, opt, bn, g), loss

        g0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        (_, opt, bn, g), losses = jax.lax.scan(step, (params, opt, bn, g0),
                                               (bx, by))
        g = flatten(g)
        report = jax.lax.top_k(jnp.abs(g), self.p["r"])[1]
        return opt, bn, stream, g, report, losses.astype(jnp.float32).mean()

    def _global_impl(self, params, opt, grads, idx):
        """Step 4: the picked values summed, one global Adam step."""
        vals = jnp.take_along_axis(grads, idx, axis=1)
        dense = jnp.zeros((self.d,), self.dtype).at[idx.reshape(-1)].add(
            vals.reshape(-1))
        return adam_step(params, opt, unflatten(dense, params), self.p["lr"],
                         self.dtype)

    def select(self, cands: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Step 3 for the round's participants ``ids`` (ascending), whose
        reports are the rows of ``cands``: the (N, k) picks, sentinel d in
        the rows of those who sat out, then eq. (2) and the request
        counts."""
        k = self.p["k"]
        taken: dict = {}
        picks = np.full((self.n, k), self.d, np.int64)
        for i, cand in zip(ids, cands):
            c = int(self.cluster_of[i])
            ages = self.ages[c][cand].astype(np.int64)
            if self.p["disjoint_in_cluster"] and c in taken:
                ages[np.isin(cand, taken[c])] = -1
            picks[i] = cand[np.argsort(-ages, kind="stable")[:k]]
            taken[c] = np.concatenate([taken.get(c, picks[i][:0]), picks[i]])
        # every pick above read the ages of the round's start; a member
        # that sat out adds its one with no reset, before the participants
        idle = np.ones(self.n, bool)
        idle[ids] = False
        for i in np.nonzero(idle)[0]:
            self.ages[int(self.cluster_of[i])] += 1
        for i in ids:
            a = self.ages[int(self.cluster_of[i])]
            a += 1
            a[picks[i]] = 0
            self.freq[i, picks[i]] += 1
        return picks

    def plan(self) -> np.ndarray:
        """(N,) bool: this round's participants."""
        return participants(self.p, self.n, self.seed, self.round)

    def step(self):
        """One global round."""
        ids = np.nonzero(self.plan())[0]
        grads, reports, losses = [], [], []
        for i in ids:
            (self.client_opt[i], self.client_bn[i], self.streams[i], g, rep,
             loss) = self._client(self.params, self.client_opt[i],
                                  self.client_bn[i], self.streams[i],
                                  jnp.int32(i), self.x, self.y)
            grads.append(g)
            reports.append(rep)
            losses.append(loss)
        picks = self.select(np.asarray(jax.device_get(reports)), ids)
        self.params, self.opt = self._global(
            self.params, self.opt, jnp.stack(grads),
            jnp.asarray(picks[ids], jnp.int32))
        self.round += 1
        row = np.full(self.n, np.nan, np.float32)
        row[ids] = jax.device_get(losses)
        self.traj.losses.append(row)
        self.traj.picks.append(picks)
        if self.round % self.p["M"] == 0:
            new = cluster(self.freq, self.p["eps"], self.p["min_pts"])
            self.ages = regroup(self.ages, self.cluster_of, new, self.d)
            self.cluster_of = new
            self.traj.labels.append(new.copy())

    def run(self, rounds: int) -> Trajectory:
        for _ in range(rounds):
            self.step()
        t = self.traj
        t.params = jax.device_get(self.params)
        t.global_mu = jax.device_get(self.opt[1])
        t.client_mu = [jax.device_get(o[1]) for o in self.client_opt]
        t.client_ages = np.stack([self.ages[int(c)] for c in self.cluster_of])
        t.freq = self.freq.copy()
        return t


class _Frozen(dict):
    """A configuration dict that can be a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))
