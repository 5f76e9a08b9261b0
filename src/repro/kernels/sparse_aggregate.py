"""Pallas TPU kernel: fused sparse scatter-add aggregation + age update.

The PS hot loop touches all d parameters every round: scatter-add N x k
sparse client updates into the dense gradient AND apply eq. (2) to the age
vector. Random-index scatter is slow on TPU vector units, so each VMEM
block turns the scatter into a ONE-HOT MATMUL on the MXU:

    [sum; hits][2, B] = [vals; ones][2, NK] @ onehot(idx_local)[NK, B]

which is exactly how TPUs like to scatter (dense systolic work, no
data-dependent addressing). The second LHS row counts the hits in the
same matmul, and the age update reads them:
age' = 0 where hit, age + 1 elsewhere.

Layouts follow the TPU's (8, 128) tiling: the indices arrive as an
(NK, 1) column (so the one-hot is a sublane-by-lane compare, no
in-kernel transpose), the [vals; ones] LHS and every d-sized operand as
lane-dense (rows, d) arrays. NK is tiled by ``nk_tile`` (a second,
accumulating grid dim), which bounds the (nk_tile, block_d) f32 one-hot
in VMEM: 2048 x 512 x 4 B = 4 MiB at the defaults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_D = 512
NK_TILE = 2048


def _kernel(idx_ref, lhs_ref, age_ref, out_ref, age_out_ref, hit_ref, *,
            block_d: int, nk_tile: int):
    j = pl.program_id(0)        # d-block index
    t = pl.program_id(1)        # NK tile index
    nt = pl.num_programs(1)

    local = idx_ref[...] - j * block_d                      # (nk_tile, 1)
    onehot = (local == jax.lax.broadcasted_iota(
        jnp.int32, (nk_tile, block_d), 1)).astype(jnp.float32)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        hit_ref[...] = jnp.zeros_like(hit_ref)

    # HIGHEST: the one-hot is exact in any precision, the values are not —
    # a single bf16 pass would round every aggregated f32 payload
    acc = jnp.dot(lhs_ref[...], onehot, preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)      # (2, block_d)
    out_ref[...] += acc[0:1]
    hit_ref[...] += acc[1:2]

    @pl.when(t == nt - 1)
    def _fini():
        age_out_ref[...] = jnp.where(hit_ref[...] > 0, 0, age_ref[...] + 1)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block_d", "nk_tile"))
def sparse_aggregate(idx: jnp.ndarray, vals: jnp.ndarray, age: jnp.ndarray,
                     *, interpret: bool = False, block_d: int = BLOCK_D,
                     nk_tile: int = NK_TILE):
    """idx/vals: (NK,) flattened client payloads (int32 / float); duplicate
    indices accumulate. age: (d,) int32. Returns (dense (d,) f32, new_age).

    d must be a multiple of block_d and NK a multiple of nk_tile (ops.py
    pads). Out-of-range idx (used as padding: idx = d) contribute nothing.
    block_d/nk_tile default to the module constants; the bench sweeps them.
    """
    d = age.shape[0]
    nk = idx.shape[0]
    assert d % block_d == 0 and nk % nk_tile == 0
    grid = (d // block_d, nk // nk_tile)
    lhs = jnp.stack([vals.astype(jnp.float32), jnp.ones((nk,), jnp.float32)])
    row = pl.BlockSpec((1, block_d), lambda j, t: (0, j))
    out, new_age = pl.pallas_call(
        functools.partial(_kernel, block_d=block_d, nk_tile=nk_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nk_tile, 1), lambda j, t: (t, 0)),
            pl.BlockSpec((2, nk_tile), lambda j, t: (0, t)),
            row,
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],  # hits
        interpret=interpret,
        name="sparse_aggregate",
    )(idx.reshape(nk, 1), lhs, age.reshape(1, d))
    return out[0], new_age[0]
