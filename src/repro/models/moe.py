"""Mixture-of-Experts FFN with BLOCK-LOCAL capacity dispatch.

Tokens are routed within per-data-shard blocks against a LOCAL capacity
(C_local = cf * T_block * K / E), so dispatch/combine never move tokens
across shards — the only cross-device traffic is expert-weight gathers and
the usual gradient sync. This is the MaxText-style "dropping" scheme taken
one step further for meshes where n_experts doesn't divide any axis (e.g.
granite's 40 experts on a 16x16 mesh): see EXPERIMENTS.md §Perf granite
iterations 1-4 for the napkin math and measured deltas of the alternatives
(global capacity sharded over model: combine-backward all-reduces of
(T*K, d) f32; global capacity over data: scatter-combine all-reduces of the
full (E, C, d) buffer).

Blocks follow the active mesh (repro.dist.sharding.use_mesh); without a
mesh (CPU tests) there is a single block and the math reduces to the
textbook capacity dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import active_mesh, constraint
from repro.models.layers import act_fn, dense_init, mlp_params, apply_mlp


def _acc_dtype():
    """f32 MXU accumulation on TPU; the CPU runtime's DotThunk can't
    execute batched BF16xBF16=F32 dots, so the CPU runs the expert math
    in bf16. Asked when the layer is traced, not when the module is
    imported, so importing never initializes a backend."""
    return jnp.float32 if jax.default_backend() != "cpu" else None


def moe_params(key, cfg) -> dict:
    d, f, E = cfg.d_model, cfg.moe_hidden, cfg.n_experts
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "experts_w1": _expert_init(ks[1], E, d, f, dtype),
        "experts_w3": _expert_init(ks[2], E, d, f, dtype),
        "experts_w2": _expert_init(ks[3], E, f, d, dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_params(ks[4], cfg, cfg.n_shared_experts * cfg.moe_hidden)
    return p


def _expert_init(key, E, d_in, d_out, dtype):
    scale = 1.0 / jnp.sqrt(d_in)
    return (jax.random.normal(key, (E, d_in, d_out), jnp.float32) * scale).astype(dtype)


def capacity(cfg, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * n_tokens * cfg.experts_per_token / cfg.n_experts)
    return max(8, -(-c // 8) * 8)   # >=8, multiple of 8


def _n_token_blocks(T: int) -> int:
    """Token blocks aligned with the batch axes of the active mesh."""
    mesh = active_mesh()
    if mesh is None:
        return 1
    nb = 1
    for a in ("pod", "data"):
        nb *= mesh.shape.get(a, 1)
    # tiny workloads (decode) must not block: the per-block capacity floor
    # times n_experts times n_blocks over-allocates the dispatch buffers
    if nb <= 1 or T % nb or T // nb < 256:
        return 1
    return nb


def _position_in_expert(flat_ids: jnp.ndarray, E: int,
                        n_chunks: int = 1024) -> jnp.ndarray:
    """Exclusive rank of each assignment within its expert (one block).

    Hierarchical prefix sum — a flat cumsum over a sharded token axis makes
    GSPMD gather + replicate the whole layer (§Perf granite iteration 2).
    """
    TK = flat_ids.shape[0]
    n_chunks = min(n_chunks, TK)
    while TK % n_chunks:
        n_chunks //= 2
    chunk = TK // n_chunks
    oh = jax.nn.one_hot(flat_ids.reshape(n_chunks, chunk), E,
                        dtype=jnp.int32)                      # (nc, c, E)
    local = jnp.cumsum(oh, axis=1) - oh                       # exclusive
    totals = jnp.sum(oh, axis=1)                              # (nc, E)
    offsets = jnp.cumsum(totals, axis=0) - totals             # (nc, E)
    pos = local + offsets[:, None, :]
    return jnp.sum(pos * oh, axis=-1).reshape(TK)


def apply_moe(p: dict, cfg, x: jnp.ndarray):
    """x: (B, S, d) -> (y, aux) with aux = load-balance metrics."""
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.experts_per_token
    nb = _n_token_blocks(T)
    Tb = T // nb
    C = capacity(cfg, Tb)

    xt = x.reshape(nb, Tb, d)
    xt = constraint(xt, ("batch", None, None))

    logits = (xt.astype(jnp.float32) @ p["router"])           # (nb, Tb, E)
    gate_k, ids_k = jax.lax.top_k(logits, K)                  # (nb, Tb, K)
    gates = jax.nn.softmax(gate_k, axis=-1)

    flat_ids = ids_k.reshape(nb, Tb * K)
    pos = jax.vmap(lambda f: _position_in_expert(f, E))(flat_ids)
    keep = pos < C
    pos_c = jnp.where(keep, pos, 0)

    # block-local dispatch: scatter token copies into (nb, E, C, d)
    xe = jnp.repeat(xt, K, axis=1)                            # (nb, Tb*K, d)
    xe = jnp.where(keep[..., None], xe, 0).astype(x.dtype)

    def scatter_block(ids, pp, src):
        return jnp.zeros((E, C, d), x.dtype).at[ids, pp].add(src, mode="drop")
    buf = jax.vmap(scatter_block)(flat_ids, pos_c, xe)        # (nb, E, C, d)
    buf = constraint(buf, ("batch", "expert", None, None))

    a = act_fn(cfg.act)
    acc = _acc_dtype()
    h = a(jnp.einsum("becd,edf->becf", buf, p["experts_w1"],
                     preferred_element_type=acc).astype(x.dtype))
    h = h * jnp.einsum("becd,edf->becf", buf, p["experts_w3"],
                       preferred_element_type=acc).astype(x.dtype)
    h = constraint(h, ("batch", "expert", None, "d_ff"))
    out_buf = jnp.einsum("becf,efd->becd", h, p["experts_w2"],
                         preferred_element_type=acc).astype(x.dtype)
    out_buf = constraint(out_buf, ("batch", "expert", None, None))

    # block-local combine
    def gather_block(ob, ids, pp):
        return ob[ids, pp]                                    # (Tb*K, d)
    y = jax.vmap(gather_block)(out_buf, flat_ids, pos_c)
    y = jnp.where(keep[..., None], y, 0)
    y = y.reshape(nb, Tb, K, d) * gates[..., None].astype(x.dtype)
    y = y.sum(axis=2).reshape(T, d)

    if cfg.n_shared_experts:
        y = y + apply_mlp(p["shared"], cfg, x.reshape(T, d))

    # aux: load-balance loss (Switch-style) + drop fraction
    lf = logits.reshape(T, E)
    me = jnp.mean(jax.nn.softmax(lf, axis=-1), axis=0)
    ce = jnp.mean(jax.nn.one_hot(ids_k.reshape(T, K)[:, 0], E,
                                 dtype=jnp.float32), axis=0)
    aux = {
        "lb_loss": E * jnp.sum(me * ce),
        "drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return y.reshape(B, S, d), aux
