"""Distributed runtime: sharding rules engine + sparse sync (1-device mesh
— multi-device behaviour is exercised by the dry-run; here we pin program
semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as SH
from repro.dist.sparse_sync import (init_age_state, make_sync_train_step)
from repro.launch.mesh import make_host_mesh
from repro.optim.optimizers import adam


def test_resolve_spec_divisibility_fallback():
    mesh = make_host_mesh(1, 1)
    with SH.use_mesh(mesh):
        # 10 is not divisible by anything > 1; with a 1-sized axis all
        # resolutions collapse to replication
        spec = SH.resolve_spec(("heads", "d_ff"), (10, 7))
        assert spec == P(None, None)


def test_host_mesh_refuses_more_devices_than_exist():
    with pytest.raises(ValueError, match="devices"):
        make_host_mesh(len(jax.devices()) + 1, 1)


def test_param_specs_structure_matches():
    mesh = make_host_mesh(1, 1)
    params = {"layers": {"attn": {"wq": jnp.zeros((8, 8))}},
              "embed": {"w": jnp.zeros((32, 8))}}
    with SH.use_mesh(mesh):
        specs = SH.param_specs(params)
    assert jax.tree_util.tree_structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree_util.tree_structure(params)


def test_constraint_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = SH.constraint(x, ("batch", None))
    assert y is x


def test_sparse_sync_converges_single_shard():
    mesh = make_host_mesh(1, 1)
    W = jnp.array([[1.0, -2.0], [3.0, 0.5]])

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    params = {"w": jnp.zeros((2, 2))}
    ages = init_age_state(params)
    opt = adam(5e-2)
    opt_state = opt.init(params)
    step = jax.jit(make_sync_train_step(loss_fn, opt, mesh,
                                        method="rage_k", r=4, k=2))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 2))
    batch = {"x": x, "y": x @ W}
    for _ in range(400):
        params, opt_state, ages, loss, stats = step(
            params, opt_state, ages, batch)
    assert float(loss) < 0.05
    # ages: every coordinate must have been visited (no starvation)
    assert int(ages["w"].max()) < 400


def test_sparse_sync_wire_accounting():
    mesh = make_host_mesh(1, 1)

    def loss_fn(params, batch):
        return jnp.sum(params["a"] ** 2) + jnp.sum(params["b"] ** 2)

    params = {"a": jnp.ones(100), "b": jnp.ones(300)}
    ages = init_age_state(params)
    opt = adam(1e-2)
    step = make_sync_train_step(loss_fn, opt, mesh, method="rage_k",
                                r=40, k=8)
    _, _, _, _, stats = jax.jit(step)(params, opt.init(params), ages,
                                      {"x": jnp.zeros(1)})
    # k split 100:300 -> (2, 6); bytes = sum k_b * (4 idx + 2 bf16)
    assert int(stats["wire_bytes_per_shard"]) == (2 + 6) * 6


def test_cafe_sync_threads_cost_lane():
    """method='cafe': age leaves carry the stacked (2, ...) [age; cost]
    state; selection runs, the cost lane accumulates exactly k_b per
    bucket per step, and lam=0 matches rage_k selection."""
    mesh = make_host_mesh(1, 1)

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)

    params = {"w": jnp.zeros((2, 2))}
    ages = init_age_state(params, method="cafe")
    assert ages["w"].shape == (2, 2, 2)
    opt = adam(5e-2)
    step = jax.jit(make_sync_train_step(loss_fn, opt, mesh, method="cafe",
                                        r=4, k=2, lam=0.3))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 2))
    batch = {"x": x, "y": x @ jnp.array([[1.0, -2.0], [3.0, 0.5]])}
    opt_state = opt.init(params)
    for t in range(1, 6):
        params, opt_state, ages, loss, stats = step(
            params, opt_state, ages, batch)
        assert int(ages["w"][1].sum()) == 2 * t         # cost lane
        assert int(ages["w"][0].max()) <= t             # age lane
    # lam=0 reproduces rage_k picks: run both one step from zeros
    ages_c = init_age_state(params, method="cafe")
    ages_r = init_age_state(params, method="rage_k")
    step_c = jax.jit(make_sync_train_step(loss_fn, opt, mesh,
                                          method="cafe", r=4, k=2, lam=0.0))
    step_r = jax.jit(make_sync_train_step(loss_fn, opt, mesh,
                                          method="rage_k", r=4, k=2))
    p0 = {"w": jnp.zeros((2, 2))}
    pc, _, ac, _, _ = step_c(p0, opt.init(p0), ages_c, batch)
    pr, _, ar, _, _ = step_r(p0, opt.init(p0), ages_r, batch)
    np.testing.assert_array_equal(np.asarray(ac["w"][0]),
                                  np.asarray(ar["w"]))
    np.testing.assert_allclose(np.asarray(pc["w"]), np.asarray(pr["w"]),
                               rtol=0, atol=0)


def test_dense_sync_matches_plain_grad():
    mesh = make_host_mesh(1, 1)

    def loss_fn(params, batch):
        return jnp.sum((params["w"] - 3.0) ** 2)

    params = {"w": jnp.ones(4)}
    opt = adam(1e-1)
    step = jax.jit(make_sync_train_step(loss_fn, opt, mesh, method="dense"))
    ages = init_age_state(params)
    p2, *_ = step(params, opt.init(params), ages, {"x": jnp.zeros(1)})
    # adam step of size lr towards 3.0
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               np.asarray(params["w"]) + 0.1, rtol=1e-3)


# ---------------------------------------------------------------------------
# buffered (FedBuff-style) sync — the async service plane's collective
# ---------------------------------------------------------------------------

def _buffered_setup():
    from repro.dist.sparse_sync import (init_age_state_sharded,
                                        make_buffered_sync,
                                        make_manual_sync)
    mesh = make_host_mesh(1, 1)
    grads = {"a": jnp.arange(-8.0, 8.0).reshape(4, 4),
             "b": jnp.ones((6,)) * 0.5}
    specs = jax.tree_util.tree_map(lambda _: P(), grads)
    shapes = jax.tree_util.tree_map(
        lambda g: jax.ShapeDtypeStruct(g.shape, g.dtype), grads)
    kw = dict(method="rage_k", r=8, k=4, wire_dtype=jnp.float32)
    return (grads, init_age_state_sharded,
            make_manual_sync(mesh, specs, shapes, **kw),
            lambda bk: make_buffered_sync(mesh, specs, shapes,
                                          buffer_k=bk, **kw))


def test_buffered_sync_k1_is_the_base_sync():
    """buffer_k=1 flushes every call: call-by-call identical to the
    unbuffered sync (values AND ages)."""
    grads, init_ages, base, make_buf = _buffered_setup()
    buf1 = make_buf(1)
    shapes = jax.tree_util.tree_map(
        lambda g: jax.ShapeDtypeStruct(g.shape, g.dtype), grads)
    ages_b, ages_o = init_ages(shapes), init_ages(shapes)
    b = buf1.init_buffer()
    for _ in range(3):
        sb, ages_b, _ = base(grads, ages_b)
        so, ages_o, b, stats = buf1(grads, ages_o, b)
        assert bool(stats["flushed"])
        assert int(stats["buffered_shards"]) == 0
        for k in sb:
            np.testing.assert_array_equal(np.asarray(so[k]),
                                          np.asarray(sb[k]))
            np.testing.assert_array_equal(np.asarray(ages_o[k]),
                                          np.asarray(ages_b[k]))


def test_buffered_sync_flush_cadence_mean_and_aging():
    """buffer_k=3: two buffering calls release a bitwise-zero update
    while ages keep advancing exactly like the base sync (age is a
    property of requests, not application); the third call flushes the
    f32 mean of the three landed unions and resets the buffer."""
    grads, init_ages, base, make_buf = _buffered_setup()
    buf3 = make_buf(3)
    shapes = jax.tree_util.tree_map(
        lambda g: jax.ShapeDtypeStruct(g.shape, g.dtype), grads)
    ages_b, ages_o = init_ages(shapes), init_ages(shapes)
    b = buf3.init_buffer()
    landed = {k: np.zeros(v.shape, np.float32) for k, v in grads.items()}
    for step in range(3):
        sb, ages_b, _ = base(grads, ages_b)
        for k in landed:
            landed[k] = landed[k] + np.asarray(sb[k], np.float32)
        so, ages_o, b, stats = buf3(grads, ages_o, b)
        for k in grads:
            np.testing.assert_array_equal(np.asarray(ages_o[k]),
                                          np.asarray(ages_b[k]))
        if step < 2:
            assert not bool(stats["flushed"])
            assert int(stats["buffered_shards"]) == step + 1
            assert all(not np.asarray(v).any() for v in
                       jax.tree_util.tree_leaves(so))
        else:
            assert bool(stats["flushed"])
            assert int(stats["buffered_shards"]) == 0
            for k in grads:
                np.testing.assert_array_equal(
                    np.asarray(so[k]),
                    (landed[k] / np.float32(3.0)).astype(np.float32))
    # the buffer really reset: next call buffers from scratch
    _, _, b, stats = buf3(grads, ages_o, b)
    assert not bool(stats["flushed"])
    assert int(stats["buffered_shards"]) == 1


def test_buffered_sync_validates_k():
    from repro.dist.sparse_sync import make_buffered_sync
    mesh = make_host_mesh(1, 1)
    g = {"a": jnp.zeros((4,))}
    specs = jax.tree_util.tree_map(lambda _: P(), g)
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), g)
    with pytest.raises(ValueError, match="buffer_k"):
        make_buffered_sync(mesh, specs, shapes, buffer_k=0, r=2, k=1)
