"""Federations whose clients take part in turns, on the CPU: the reference
follows the UniformM plan under either age layout and on skewed shards, a
sound run passes ``mnist_mlp.paper_fig3``'s limits, and a fault planted in
the timed path's participation fails them. Under ``schedule: "full"`` and
the even split, the reference's trajectory is pinned bit for bit."""
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testkit as kit
import compare
import reference
import run
from repro.fl import engine, schedule

SEED = 2**31 + 5


def run_cell(tmp_path, monkeypatch, layout):
    bench = kit.participation_bench(tmp_path, layout)
    monkeypatch.setattr(run, "CACHE_DIR", tmp_path / "jax_cache")
    return run.run(["--workload", kit.TINY, "--seed", str(SEED),
                    "--seconds", "0.3", "--trace", "0"],
                   require_chip=False, t0=time.perf_counter(), bench=bench)


@pytest.mark.parametrize("layout", ["dense", "hierarchical"])
def test_partial_participation_is_followed(layout, tmp_path, monkeypatch):
    out = run_cell(tmp_path, monkeypatch, layout)
    assert out["correct"], out["checks"]
    assert out["checks"]["pick_mismatch"]["value"] == 0.0
    assert out["checks"]["cluster_mismatch"]["value"] == 0.0


def test_the_reference_draws_the_programs_plan():
    proto = {"schedule": "uniform", "participation_m": 4}
    sched = schedule.make_scheduler("uniform", 16, participation_m=4)
    state = schedule.SchedState.create(16, SEED + reference.SCHEDULE_KEY)
    for rnd in range(8):
        got = np.asarray(sched.plan(state._replace(rnd=jnp.int32(rnd)))
                         .active)
        assert np.array_equal(reference.participants(proto, 16, SEED, rnd),
                              got)
        assert got.sum() == 4
    assert reference.participants({"schedule": "uniform"}, 16, SEED, 0) \
        .sum() == 4                             # m = N // 4 where unset
    assert reference.participants({}, 16, SEED, 0).all()


@pytest.mark.parametrize("plan", ["aoi", "deadline"])
def test_a_plan_the_reference_does_not_follow_is_refused(plan, tmp_path):
    bench = kit.tiny_bench(tmp_path)
    cell = run.harness.resolve(kit.load(tmp_path / "BENCHMARK.json"),
                               kit.TINY, bench)
    cell["mix"]["overrides"] = {"schedule": plan}
    with pytest.raises(NotImplementedError):
        run.harness.Run(cell, SEED, time.perf_counter())


def shifted_plan(plan):
    def wrapped(self, state, age_state=None):
        p = plan(self, state, age_state)
        return p._replace(active=jnp.roll(p.active, 1))
    return wrapped


def idle_ages_reset(select):
    """The cluster rows of clients that sit the round out reset at the
    round's picks."""
    def wrapped(*a, **kw):
        idx, age, *rest = select(*a, **kw)
        d, idle = kw["d"], (~kw["active"]).astype(jnp.int32)
        picked = jnp.zeros((d + 1,), bool).at[idx.reshape(-1)].set(True)[:d]
        rows = jnp.zeros((age.cluster_age.shape[0],), jnp.int32).at[
            age.cluster_of].max(idle) > 0
        ages = jnp.where(rows[:, None] & picked[None], 0, age.cluster_age)
        return (idx, age._replace(cluster_age=ages), *rest)
    return wrapped


def idle_adam_step(round_impl):
    """The Adam first moment of clients that sit the round out decays as
    by a step with no gradient."""
    def wrapped(self, data, carry, *a, **kw):
        active = self._scheduler.plan(carry[-1], carry[5]).active
        carry, metrics = round_impl(self, data, carry, *a, **kw)
        opt = carry[3]
        mu = jax.tree_util.tree_map(
            lambda m: jnp.where(active.reshape((-1,) + (1,) * (m.ndim - 1)),
                                m, m * reference.B1), opt.mu)
        return carry[:3] + (opt._replace(mu=mu),) + carry[4:], metrics
    return wrapped


FAULTS = {
    "plan_shifted": ("dense", schedule.UniformM, "plan", shifted_plan),
    "idle_ages_reset": ("hierarchical", engine, "rage_select_segmented",
                        idle_ages_reset),
    "idle_adam_step": ("dense", engine.FederatedEngine, "_round_impl",
                       idle_adam_step),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_participation_fault_in_the_timed_path_is_not_correct(
        fault, tmp_path, monkeypatch):
    layout, owner, name, make = FAULTS[fault]
    monkeypatch.setattr(owner, name, make(getattr(owner, name)))
    out = run_cell(tmp_path, monkeypatch, layout)
    assert not out["correct"], out["checks"]


def test_an_untrained_clients_moment_counts():
    trained = {"w": np.ones(4), "b": np.ones(2)}
    zero = {"w": np.zeros(4), "b": np.zeros(2)}
    moved = {"w": np.full(4, 0.5), "b": np.zeros(2)}
    gaps = compare.client_gaps([trained, zero, moved],
                               [trained, zero, zero])
    assert gaps[:2] == [0.0, 0.0]
    # the program's worst leaf norm, 1, over the trained client's median
    # leaf norm
    assert gaps[2] == pytest.approx(1.0 / np.median([np.sqrt(2.0), 2.0]))


def digest(t) -> str:
    h = hashlib.sha256()
    for a in (list(t.losses) + list(t.picks) + list(t.labels)
              + [leaf for tree in (t.params0, t.params, t.global_mu,
                                   t.client_mu)
                 for leaf in jax.tree_util.tree_leaves(tree)]
              + [t.client_ages, t.freq]):
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_full_participation_reference_is_pinned(tmp_path):
    """The tiny cell (full participation, even split) followed by the
    reference gives, bit for bit, the trajectory it gave before the
    reference followed participation plans."""
    bench = kit.tiny_bench(tmp_path)
    cell = run.harness.resolve(kit.load(tmp_path / "BENCHMARK.json"),
                               kit.TINY, bench)
    seed = 2**31 + 3
    shards, _ = run.harness.synth.federation_data(cell["config"], seed)
    assert digest(run.harness.follow(cell, shards, seed)) == \
        "478032be88f0af2262d3917f693612c2412e116a1e660c8c4da0d1eb25ad910a"
