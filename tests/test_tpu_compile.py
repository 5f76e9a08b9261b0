"""The main-path Pallas kernels compile for a TPU v5e at the CIFAR-CNN
federation's shapes (paper Table I Network 2, d = 2,515,338; r = 2500,
k = 100; the paper's CIFAR split has N = 6 clients, and N = 10 covers a
ten-client population), without a chip: the TPU compiler compiles for a
described topology. Interpret-mode tests cannot see what this refuses
(block shapes off the (8, 128) tiling, layouts Mosaic cannot lower, VMEM
over the scoped limit).

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D = 2_515_338          # CIFAR CNN parameter count
R, K = 2500, 100


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(one_chip, monkeypatch):
    """Compile ``fn`` at the given (shape, dtype) arguments for the
    described chip and return the compiled module's HLO text. The
    backend here is the CPU, so the kernels are steered off their
    interpret-mode branch; the persistent compile cache is off, since a
    TPU entry written here could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *args):
        specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                 for s, dt in args]
        return jax.jit(fn).lower(*specs).compile().as_text()

    try:
        yield compile_
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("N", [6, 10])
def test_sparse_aggregate_compiles_for_v5e(tpu_compile, N):
    hlo = tpu_compile(lambda i, v, a: ops.sparse_aggregate(i, v, a),
                      ((N * K,), jnp.int32), ((N * K,), jnp.float32),
                      ((D,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("C,S", [(6, 1), (3, 2), (5, 2)])
def test_segmented_age_topk_compiles_for_v5e(tpu_compile, C, S):
    """Singleton clusters (the t=0 packing) and multi-member segments
    (the disjoint member recursion) after a recluster."""
    hlo = tpu_compile(lambda c, a, v: ops.segmented_age_topk(c, a, v, K),
                      ((C, S, R), jnp.int32), ((C, S, R), jnp.int32),
                      ((C, S), jnp.bool_))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("N", [6, 10])
def test_maghist_batch_compiles_for_v5e(tpu_compile, N):
    hlo = tpu_compile(ops.maghist_batch, ((N, D), jnp.float32))
    assert "tpu_custom_call" in hlo
