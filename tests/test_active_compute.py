"""Compute-plane tests (DESIGN.md §11): active-only gather-train-scatter.

1. Matrix parity: an engine with ``compute='gathered'`` (train only the
   scheduler's m_bound compacted clients) is BIT-IDENTICAL to the
   ``compute='masked'`` full-N reference — per-round losses (NaN rows
   for non-participants), requested indices, participation metrics, and
   the FULL engine state (params, opt, BatchNorm, sampler streams, ages,
   ef memory) — for all strategies × all four schedulers, across a
   recluster boundary, under both the step and scan drivers. The
   Full/Deadline rows force gathered (auto picks masked at m_bound==N)
   so the sentinel-padding discipline is exercised: padded slots read a
   clipped duplicate row, train dead weight, and write nothing back.
2. Error feedback and the cnn kind (BatchNorm state rows) gather and
   scatter bit-identically too.
3. Property tests (seeded sweeps + hypothesis where installed):
   ``draw_gathered`` advances EXACTLY the
   listed clients' sampler rows by the batched ``draw`` math, and the
   fused per-client phase is row-independent (a gathered subset equals
   the corresponding rows of the full batch) — the two facts the whole
   gathered-==-masked story rests on.
4. The gathered round is transfer-free under
   ``jax.transfer_guard("disallow")`` and its jitted-HLO FLOPs scale
   with m_bound, not N (cost_analysis on the compiled round).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RAgeKConfig
from repro.data.federated import paper_cifar_split, paper_mnist_split
from repro.data.pipeline import DeviceShardStore
from repro.data.synthetic import cifar10_like, mnist_like
from repro.fl import FederatedEngine
from repro.fl import client as C
from repro.launch.hlo_cost import cost_dict
from repro.models import paper_nets as P

METHODS = ("rage_k", "rtop_k", "top_k", "random_k", "dense")
SCHEDULES = ("full", "uniform", "aoi", "deadline")

# M=3, 7 rounds -> recluster boundaries at rounds 3 and 6
HP = dict(r=30, k=6, H=2, M=3, lr=2e-3, batch_size=16)
ROUNDS = 7

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def mnist_setup():
    (xtr, ytr), test = mnist_like(n_train=1200, n_test=400, seed=0)
    return paper_mnist_split(xtr, ytr, seed=0), test


@pytest.fixture(scope="module")
def cifar_setup():
    (xtr, ytr), test = cifar10_like(n_train=600, n_test=240, seed=0)
    return paper_cifar_split(xtr, ytr, seed=0), test


def _hp(method, schedule, **over):
    kw = dict(HP, method=method, schedule=schedule)
    if schedule in ("uniform", "aoi"):
        kw["participation_m"] = 4 if schedule == "uniform" else 3
    if schedule == "deadline":
        kw["deadline_s"] = 1.0
    kw.update(over)
    return RAgeKConfig(**kw)


def _leaves_equal(ta, tb):
    la = jax.tree_util.tree_leaves(ta)
    lb = jax.tree_util.tree_leaves(tb)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_same_engine(ea, eb):
    """The FULL mutable engine state, bitwise: global params/opt, every
    client's local params/opt/BatchNorm rows, ages, ef memory, PRNG key,
    sampler streams (held clients' rows untouched, active rows advanced
    identically) and scheduler state."""
    _leaves_equal(ea.g_params, eb.g_params)
    _leaves_equal(ea.g_opt_state, eb.g_opt_state)
    _leaves_equal(ea.params_s, eb.params_s)
    _leaves_equal(ea.opt_s, eb.opt_s)
    _leaves_equal(ea.state_s, eb.state_s)
    _leaves_equal(ea.samp, eb.samp)
    _leaves_equal((ea.age.cluster_age, ea.age.freq),
                  (eb.age.cluster_age, eb.age.freq))
    np.testing.assert_array_equal(ea.cluster_of, eb.cluster_of)
    np.testing.assert_array_equal(np.asarray(ea.sched.aoi),
                                  np.asarray(eb.sched.aoi))
    if ea.ef_mem is not None or eb.ef_mem is not None:
        np.testing.assert_array_equal(np.asarray(ea.ef_mem),
                                      np.asarray(eb.ef_mem))


def _step_parity(em, eg, rounds, loss_ulp=0):
    """Drive both engines round-at-a-time, comparing every per-round
    metric (assert_array_equal treats the NaN loss rows of inactive
    clients as equal). ``loss_ulp`` > 0 bounds the participants' losses
    at that many ulp instead of bitwise (the cnn contract, DESIGN.md
    §11); everything else stays bitwise."""
    for _ in range(rounds):
        mm, mg = em.step(), eg.step()
        if loss_ulp:
            held = np.isnan(mm["losses"])
            np.testing.assert_array_equal(held, np.isnan(mg["losses"]))
            np.testing.assert_array_max_ulp(mm["losses"][~held],
                                            mg["losses"][~held],
                                            maxulp=loss_ulp)
        else:
            np.testing.assert_array_equal(mm["losses"], mg["losses"])
        assert np.isnan(mm["losses"]).sum() == em.n - mm["n_active"]
        if mm["idx"] is None:
            assert mg["idx"] is None
        else:
            np.testing.assert_array_equal(mm["idx"], mg["idx"])
        for key in ("n_active", "aoi_mean", "aoi_peak", "age_mean",
                    "age_peak"):
            assert mm[key] == mg[key], key
    _assert_same_engine(em, eg)


# ---------------------------------------------------------------------------
# matrix: strategies × schedulers × drivers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("method", METHODS + ("cafe",))
def test_gathered_equals_masked(mnist_setup, method, schedule):
    shards, test = mnist_setup
    hp = _hp(method, schedule)
    em = FederatedEngine("mlp", shards, test, hp, seed=3,
                         compute="masked")
    eg = FederatedEngine("mlp", shards, test, hp, seed=3,
                         compute="gathered")
    if schedule in ("uniform", "aoi"):
        # auto gathers exactly when the scheduler bounds m below N
        auto = FederatedEngine("mlp", shards, test, hp, seed=3)
        assert auto._compute == "gathered"
        assert eg._scheduler.m_bound < eg.n
    else:
        # Full/Deadline bound m at N: auto keeps the masked program and
        # this test FORCES gathered to exercise the padding discipline
        assert FederatedEngine("mlp", shards, test, hp,
                               seed=3)._compute == "masked"
    _step_parity(em, eg, ROUNDS)
    # scan driver over the same gathered program: bit-identical again
    es = FederatedEngine("mlp", shards, test, hp, seed=3,
                         compute="gathered")
    rs = es.run_scanned(ROUNDS, eval_every=ROUNDS)
    _assert_same_engine(eg, es)
    assert rs.rounds == [ROUNDS]


def test_gathered_short_round_pads(mnist_setup):
    """Deadline rounds can activate FEWER than m_bound clients: the
    compaction pads with the sentinel n. Cross-check that some round in
    the run actually exercised a padded slot (n_active < N) — otherwise
    the parity above proved nothing about padding."""
    shards, test = mnist_setup
    hp = _hp("rage_k", "deadline")
    eg = FederatedEngine("mlp", shards, test, hp, seed=3,
                         compute="gathered")
    res = eg.run(ROUNDS, eval_every=ROUNDS)
    assert min(res.n_active) < eg.n
    assert max(res.n_active) <= eg._scheduler.m_bound == eg.n


# ---------------------------------------------------------------------------
# error feedback + BatchNorm coverage
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ("rage_k", "dense"))
def test_gathered_equals_masked_ef(mnist_setup, method):
    """ef memory rows gather/scatter with the client: the sparse (rage)
    and dense residual branches both stay bitwise."""
    shards, test = mnist_setup
    hp = _hp(method, "uniform")
    em = FederatedEngine("mlp", shards, test, hp, seed=3, ef=True,
                         compute="masked")
    eg = FederatedEngine("mlp", shards, test, hp, seed=3, ef=True,
                         compute="gathered")
    assert eg.ef_mem is not None
    _step_parity(em, eg, ROUNDS)


def test_gathered_equals_masked_cnn(cifar_setup):
    """cnn kind: BatchNorm running stats are per-client state rows —
    gathered trains m of them and scatters back; held clients' stats
    must come out untouched. XLA's CPU GEMM picks its blocking from the
    row count, so the dense layers' K-sums of an (m*B)-row program and an
    (N*B)-row one may round differently: losses are bounded at 4 ulp
    (1 measured), state and indices stay bitwise (DESIGN.md §11)."""
    shards, test = cifar_setup
    hp = RAgeKConfig(r=200, k=20, H=1, M=2, lr=1e-3, batch_size=8,
                     method="rage_k", schedule="uniform",
                     participation_m=2)
    em = FederatedEngine("cnn", shards, test, hp, seed=1,
                         compute="masked")
    eg = FederatedEngine("cnn", shards, test, hp, seed=1,
                         compute="gathered")
    assert eg.state_s                       # BatchNorm state present
    _step_parity(em, eg, 5, loss_ulp=4)


# ---------------------------------------------------------------------------
# property tests: the two facts gathered==masked rests on
# ---------------------------------------------------------------------------

_N, _CAP, _BS, _H = 6, 40, 8, 2


def _store(lengths, seed=0):
    rng = np.random.default_rng(seed)
    shards = [(rng.normal(size=(l, 3)).astype(np.float32),
               rng.integers(0, 4, l).astype(np.int32)) for l in lengths]
    return DeviceShardStore(shards, _BS, seed=seed)


def _check_draw_gathered(lengths, active, steps):
    """draw_gathered(idx) returns exactly the rows draw() would have
    produced for the listed clients and advances ONLY their sampler
    state — inactive rows (and padded sentinel slots) bitwise hold."""
    store = _store(lengths)
    state = store.init_state()
    for _ in range(steps):                   # desync the cursors a bit
        _, _, state = store.draw(store.data, state, _H)
    act = np.asarray(active, bool)
    m = max(int(act.sum()), 1)               # static bound, >= 1 slot
    idx = jnp.asarray(np.concatenate(
        [np.nonzero(act)[0], np.full(m - act.sum(), _N)]).astype(
            np.int32))
    bxf, byf, stf = store.draw(store.data, state, _H)
    bxg, byg, stg = store.draw_gathered(store.data, state, _H, idx)
    ic = np.minimum(np.asarray(idx), _N - 1)
    np.testing.assert_array_equal(np.asarray(bxg),
                                  np.asarray(bxf)[ic])
    np.testing.assert_array_equal(np.asarray(byg),
                                  np.asarray(byf)[ic])
    for full, gath, before in zip(stf, stg, state):
        full, gath, before = map(np.asarray, (full, gath, before))
        np.testing.assert_array_equal(gath[act], full[act])
        np.testing.assert_array_equal(gath[~act], before[~act])


def test_draw_gathered_matches_draw_rows_seeded():
    rng = np.random.default_rng(5)
    for _ in range(12):
        lengths = rng.integers(_BS, _CAP + 1, _N).tolist()
        active = (rng.random(_N) < 0.5).tolist()
        _check_draw_gathered(lengths, active, int(rng.integers(0, 4)))


_PHASE_CACHE = []


def _phase_setup():
    """Lazy module cache (not a fixture, so the hypothesis variants can
    share it without a function-scoped-fixture health check)."""
    if not _PHASE_CACHE:
        params = P.mlp_init(jax.random.PRNGKey(7))

        def apply_loss(params, state, batch):
            x, y = batch
            return C.softmax_xent(P.mlp_apply(params, x), y), state

        phase = C.make_local_phase(apply_loss, 1e-3, report_r=9,
                                   report_impl="sort")
        rng = np.random.default_rng(11)
        bx = jnp.asarray(rng.normal(size=(4, _H, _BS, 28 * 28))
                         .astype(np.float32))
        by = jnp.asarray(rng.integers(0, 10, (4, _H, _BS))
                         .astype(np.int32))
        from repro.optim.optimizers import adam
        params_s = C.broadcast_global(params, 4)
        opt_s = jax.vmap(adam(1e-3).init)(params_s)
        _PHASE_CACHE.append((phase, params_s, opt_s, bx, by))
    return _PHASE_CACHE[0]


def _check_phase_rows(rows):
    """The fused local phase is row-independent: running it on a
    gathered subset (any 2 of 4 clients, duplicates allowed — exactly
    what clipped sentinel padding produces) equals gathering the rows of
    the full-batch output, for params, gradients, the fused top-r report
    AND the losses."""
    phase, params_s, opt_s, bx, by = _phase_setup()
    ic = jnp.asarray(rows, jnp.int32)
    tak = lambda t: jax.tree_util.tree_map(lambda a: a[ic], t)
    pf, of, _, gf, cf, lf = phase(params_s, opt_s, {}, (bx, by))
    pg, og, _, gg, cg, lg = phase(tak(params_s), tak(opt_s), {},
                                  (bx[ic], by[ic]))
    _leaves_equal(pg, tak(pf))
    _leaves_equal(og, tak(of))
    np.testing.assert_array_equal(np.asarray(gg), np.asarray(gf)[rows])
    np.testing.assert_array_equal(np.asarray(cg), np.asarray(cf)[rows])
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lf)[rows])


def test_local_phase_rows_independent_seeded():
    rng = np.random.default_rng(6)
    for _ in range(10):
        _check_phase_rows([int(i) for i in rng.integers(0, 4, 2)])


def test_fused_report_matches_unfused():
    """The report fused into the phase is the SAME client_candidates
    call selection would have made on the returned gradients."""
    from repro.core.strategies import client_candidates
    phase, params_s, opt_s, bx, by = _phase_setup()
    _, _, _, g, cands, _ = phase(params_s, opt_s, {}, (bx, by))
    np.testing.assert_array_equal(
        np.asarray(cands), np.asarray(client_candidates(g, 9, "sort")))


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(deadline=None, max_examples=15)
    @given(lengths=st.lists(st.integers(_BS, _CAP), min_size=_N,
                            max_size=_N),
           active=st.lists(st.booleans(), min_size=_N, max_size=_N),
           steps=st.integers(0, 3))
    def test_draw_gathered_matches_draw_rows(lengths, active, steps):
        _check_draw_gathered(lengths, active, steps)

    @settings(deadline=None, max_examples=15)
    @given(rows=st.lists(st.integers(0, 3), min_size=2, max_size=2))
    def test_local_phase_rows_independent(rows):
        _check_phase_rows(rows)
except ImportError:                           # pragma: no cover
    pass


# ---------------------------------------------------------------------------
# transfer guard + FLOP scaling
# ---------------------------------------------------------------------------

def test_gathered_chunk_is_transfer_free(mnist_setup):
    """The gathered scan chunk stays device-pure: compaction, gather,
    scatter and the fused report introduce no host transfer (mirrors
    tests/test_scan_driver.py for the masked plane)."""
    shards, test = mnist_setup
    hp = _hp("rage_k", "uniform")
    engine = FederatedEngine("mlp", shards, test, hp, seed=0)
    assert engine._compute == "gathered"
    chunk = engine._chunk(hp.M)
    carry, metrics = chunk(engine._data, engine._pack())
    jax.block_until_ready(metrics)
    with jax.transfer_guard("disallow"):
        carry, metrics = chunk(engine._data, carry)
        jax.block_until_ready((carry, metrics))
    assert metrics["losses"].shape == (hp.M, engine.n)
    assert metrics["idx"].shape == (hp.M, engine.n, hp.k)


def _round_flops(engine):
    ns, ms = engine._seg_bounds()
    compiled = engine._round.lower(engine._data, engine._pack(),
                                   num_segments=ns,
                                   max_seg=ms).compile()
    return float(cost_dict(compiled).get("flops", 0.0))


def test_gathered_flops_scale_with_m(mnist_setup):
    """The compiled round's FLOPs scale with the scheduler's m_bound
    under gathered compute, and are flat at N under masked: the
    tentpole's entire point, asserted on the jitted HLO itself."""
    shards, test = mnist_setup

    def eng(m, compute):
        hp = _hp("rage_k", "uniform", participation_m=m)
        return FederatedEngine("mlp", shards, test, hp, seed=0,
                               compute=compute)

    f_g2 = _round_flops(eng(2, "gathered"))
    f_g5 = _round_flops(eng(5, "gathered"))
    f_m2 = _round_flops(eng(2, "masked"))
    f_m5 = _round_flops(eng(5, "masked"))
    assert f_g2 < f_g5 < f_m5
    # masked cost is ~flat in m (trains all N regardless)
    assert abs(f_m2 - f_m5) / f_m5 < 0.05
    # the local phase dominates: m=2 of N=10 must cut well past half
    assert f_g2 < 0.5 * f_m2
