"""Pallas TPU kernel: single-query flash attention over a blocked KV cache
(the decode-shape hot spot: one new token attending to seq_len cached KVs,
pure HBM-bandwidth work).

Grid = (G kv-groups, S/BLOCK_S cache blocks); the TPU grid is sequential,
so the online-softmax running state (m, l, acc) lives in VMEM scratch and
carries across cache blocks; output is written on the last block. Each
program computes (rep = H/G query heads) x BLOCK_S scores on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_S = 512


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    s_idx = pl.program_id(1)
    n_s = pl.num_programs(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)              # (rep, D)
    k = k_ref[0].astype(jnp.float32)              # (BLOCK_S, D)
    v = v_ref[0].astype(jnp.float32)              # (BLOCK_S, D)
    scale = q.shape[-1] ** -0.5
    s = jnp.dot(q * scale, k.T,
                preferred_element_type=jnp.float32)  # (rep, BLOCK_S)
    pos = s_idx * BLOCK_S + jax.lax.broadcasted_iota(
        jnp.int32, (1, BLOCK_S), 1)
    valid = pos < len_ref[0]
    s = jnp.where(valid, s, -jnp.inf)

    m_prev = m_ref[...]                            # (rep, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
    p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(jnp.isinf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _fini():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     cache_len: jnp.ndarray, *, interpret: bool = False):
    """q: (H, D); k/v: (S, G, D) with H % G == 0, S % BLOCK_S == 0;
    cache_len: (1,) int32 number of valid cache entries. -> (H, D)."""
    H, D = q.shape
    S, G, _ = k.shape
    rep = H // G
    assert S % BLOCK_S == 0
    qg = q.reshape(G, rep, D)
    grid = (G, S // BLOCK_S)
    # the cache goes in group-major (G, S, D): a (1, BLOCK_S, D) block
    # keeps the TPU-tiled last two dims whole, a (BLOCK_S, 1, D) one not
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # cache_len (1,)
            pl.BlockSpec((1, rep, D), lambda g, s: (g, 0, 0)),
            pl.BlockSpec((1, BLOCK_S, D), lambda g, s: (g, s, 0)),
            pl.BlockSpec((1, BLOCK_S, D), lambda g, s: (g, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep, D), lambda g, s: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, rep, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, D), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(cache_len, qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2))
    return out.reshape(H, D)
