"""The production sync modes (--sync dense|rage_k) must lower+compile and
the manual rAge-k exchange must be numerically consistent with the plain
gradient on a 1-device mesh (all_gather of one shard == identity)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import InputShape
from repro.launch.mesh import make_host_mesh
from repro.launch.hlo_cost import cost_dict
from repro.launch.steps import lower_combo

TRAIN = InputShape("t", 64, 2, "train")


@pytest.mark.parametrize("sync", ["dense", "rage_k"])
def test_sync_modes_lower(sync):
    cfg = get_smoke_config("internlm2-1.8b")
    mesh = make_host_mesh(1, 1)
    lowered, kind = lower_combo(cfg, TRAIN, mesh, sync=sync)
    compiled = lowered.compile()
    assert kind == "train"
    assert cost_dict(compiled).get("flops", 0) > 0


def test_manual_sync_semantics_single_shard():
    """On one shard, dense sync == identity (cast round-trip) and rage_k
    keeps exactly the bucket budgets' worth of entries."""
    from jax.sharding import PartitionSpec as P
    from repro.dist.sparse_sync import make_manual_sync, init_age_state_sharded

    mesh = make_host_mesh(1, 1)
    grads = {"a": jnp.arange(-8.0, 8.0).reshape(4, 4),
             "b": jnp.ones((6,)) * 0.5}
    specs = jax.tree_util.tree_map(lambda _: P(), grads)
    shapes = jax.tree_util.tree_map(
        lambda g: jax.ShapeDtypeStruct(g.shape, g.dtype), grads)
    ages = init_age_state_sharded(shapes)

    dense = make_manual_sync(mesh, specs, shapes, method="dense", r=8, k=4,
                             wire_dtype=jnp.float32)
    synced, ages2, stats = jax.jit(dense)(grads, ages)
    np.testing.assert_allclose(np.asarray(synced["a"]),
                               np.asarray(grads["a"]), rtol=1e-6)

    sparse = make_manual_sync(mesh, specs, shapes, method="rage_k", r=8, k=4,
                              wire_dtype=jnp.float32)
    synced, ages2, stats = jax.jit(sparse)(grads, ages)
    nz = sum(int(jnp.count_nonzero(v)) for v in
             jax.tree_util.tree_leaves(synced))
    # budgets: sizes (16, 6), r=8 -> (6?, ...) k=4 -> (3, 1)
    from repro.core.sparsify import bucket_budgets
    budgets = bucket_budgets([16, 6], 8, 4)
    assert nz == sum(k for _, k in budgets)
    # ages: selected reset, others aged
    assert int(ages2["a"].min()) == 0 and int(ages2["a"].max()) == 1
    assert int(stats["wire_bytes_per_shard"]) > 0
