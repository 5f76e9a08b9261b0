"""Pallas TPU kernel: segmented age-top-k — the rAge-k selection phase.

The PS picks, for every client, the k highest-AGE indices among that
client's top-r magnitude candidates, with DISJOINT picks within a cluster
(paper §II): an index requested by an earlier member of the cluster is
masked (age -> -1) for the later members. Clusters are independent, so
the grid is one program per cluster (segment); inside a program the
member recursion is a short ``fori_loop`` over the padded segment
positions (max cluster size, not N).

Instead of a (d,) taken-mask, the kernel carries an (S, R) TAKEN
matrix over the segment's own candidate tile: when a valid member picks
index v, every lane of every member whose candidate equals v is marked,
so a later member masks its taken lanes (age -> -1) with one row read.
Tiny VMEM, no data-dependent (d,)-sized state. The masked top-k is k
max/first-index passes (first-occurrence argmax == ``lax.top_k``'s
stable ordering, so the |g|-descending candidate order keeps breaking
age ties toward larger magnitude, exactly like the sequential scan).

Every per-member value is a (1, R)/(1, k) row picked or placed by an
iota compare, never a dynamic slice, and every scalar stays a (1, 1)
vector, so the body lowers to plain TPU vector ops.

Interpret-mode on CPU (like ``sparse_aggregate``); the jnp oracle lives
in ``core.strategies.segmented_age_topk`` (re-exported by
``kernels.ref``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128                        # candidate-axis padding (int32 lanes)
NEG = -(2 ** 31) + 1              # never-selected sentinel age


def _kernel(cand_ref, age_ref, valid_ref, out_ref, *, k: int,
            disjoint: bool):
    cand = cand_ref[0]            # (S, R) int32
    ages = age_ref[0]             # (S, R) int32, >= 0 on real lanes
    valid = valid_ref[0]          # (1, S) int32 0/1
    S, R = cand.shape
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    k_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    s_lanes = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)

    def row(x, s):                # (S, R) -> its (1, R) row s
        return jnp.sum(jnp.where(rows == s, x, 0), axis=0, keepdims=True)

    def member(s, carry):
        taken, out = carry        # (S, R) 0/1, (S, k)
        c = row(cand, s)
        a = row(ages, s)
        if disjoint:
            a = jnp.where(row(taken, s) > 0, jnp.int32(-1), a)
        v = jnp.sum(jnp.where(s_lanes == s, valid, 0), axis=1,
                    keepdims=True) > 0                        # (1, 1)

        def pick(j, st):
            a_j, sel, taken = st
            top = jnp.max(a_j, axis=1, keepdims=True)
            p = jnp.min(jnp.where(a_j == top, lanes, R), axis=1,
                        keepdims=True)                        # first argmax
            hit = lanes == p
            idx = jnp.sum(jnp.where(hit, c, 0), axis=1, keepdims=True)
            sel = jnp.where(k_lanes == j, idx, sel)
            if disjoint:
                taken = jnp.where(v & (cand == idx), 1, taken)
            return jnp.where(hit, jnp.int32(NEG), a_j), sel, taken

        _, sel, taken = jax.lax.fori_loop(
            0, k, pick, (a, jnp.zeros((1, k), jnp.int32), taken))
        return taken, jnp.where(rows == s, sel, out)

    _, out = jax.lax.fori_loop(0, S, member,
                               (jnp.zeros((S, R), jnp.int32),
                                jnp.zeros((S, k), jnp.int32)))
    out_ref[0] = out


@functools.partial(jax.jit, static_argnames=("k", "disjoint", "interpret"))
def segmented_age_topk(cand: jnp.ndarray, age: jnp.ndarray,
                       valid: jnp.ndarray, k: int, *,
                       disjoint: bool = True, interpret: bool = False):
    """cand/age: (C, S, R) int32 candidate indices / non-negative ages
    (padded lanes: cand = -2, age = NEG — never selected while k <= real
    candidates; ops.py pads). valid: (C, S) int32 live-member mask.
    Returns (C, S, k) int32 selected indices (padded member slots produce
    don't-care values that never enter the taken matrix)."""
    C, S, R = cand.shape
    return pl.pallas_call(
        functools.partial(_kernel, k=k, disjoint=disjoint),
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, S, R), lambda c: (c, 0, 0)),
            pl.BlockSpec((1, S, R), lambda c: (c, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda c: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, k), lambda c: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, S, k), jnp.int32),
        interpret=interpret,
        name="segmented_age_topk",
    )(cand, age, valid.reshape(C, 1, S))
