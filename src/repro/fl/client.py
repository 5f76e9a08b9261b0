"""Client-side machinery: vmapped local training phases (Algorithm 1,
lines 3-5). All N clients advance H local Adam steps inside one jitted
scan; the LAST local gradient is returned flat for sparsification (line 7
applies rAge-k to the gradient at the global-iteration step). Only that
gradient is kept: it rides in the scan's carry, each step replacing it,
so the phase never stacks H gradient trees (an (H, N, d) buffer).

Both phases can FUSE the protocol's client-side tail into the same
program (DESIGN.md §11): error-feedback add (``g + ef``) and the top-r
magnitude candidate report (``core.strategies.client_candidates``) run
while the flat gradient is still live, so the (N, d) grad matrix is
never re-materialized and re-read by the selection plane. The report is
computed by the IDENTICAL batched function the parameter server would
otherwise call on the same post-ef gradients — fusing it is a bitwise
no-op on every value.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.strategies import client_candidates
from repro.optim.optimizers import adam, apply_updates


def softmax_xent(logits, labels):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def flatten_tree(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate([l.reshape(-1) for l in leaves])


def unflattener(template):
    leaves, treedef = jax.tree_util.tree_flatten(template)
    shapes = [l.shape for l in leaves]
    sizes = [int(l.size) for l in leaves]

    def unflatten(flat):
        out, o = [], 0
        for s, sz in zip(shapes, sizes):
            out.append(flat[o:o + sz].reshape(s))
            o += sz
        return jax.tree_util.tree_unflatten(treedef, out)
    return unflatten


def make_client_phase(apply_loss: Callable, lr: float, *,
                      report_r: int | None = None,
                      report_impl: str = "sort") -> Callable:
    """ONE client's H-step local phase, pure and un-jitted (traceable
    inside any program — the async service's event loop runs it per
    arrival). phase(params, opt_state, state, batches[, ef]) -> (params,
    opt_state, state, flat_last_grad (d,), mean_loss ()); batches is an
    (H, ...) pytree. :func:`make_local_phase` is exactly its vmap, so a
    single-client call is bitwise the corresponding row of the batched
    phase (pinned by tests/test_service.py). The scan carries the newest
    gradient tree and emits only each step's loss, so memory does not
    grow with H.

    ``ef`` (optional) is the client's (d,) error-feedback residual,
    added to the flat gradient in-phase. ``report_r`` fuses the top-r
    candidate report into the phase tail: the return grows a sixth
    element, ``(params, opt_state, state, g, cand (r,), mean_loss)``,
    with ``cand`` the row of :func:`client_candidates` on the post-ef
    gradient (``report_impl``: 'sort' | 'threshold', bit-identical)."""
    opt = adam(lr)

    def one_step(carry, batch):
        params, opt_state, state, _ = carry
        (loss, new_state), grads = jax.value_and_grad(
            apply_loss, has_aux=True)(params, state, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return (params, opt_state, new_state, grads), loss

    def phase_one_client(params, opt_state, state, batches, ef=None):
        grads0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        (params, opt_state, state, last_grad), losses = jax.lax.scan(
            one_step, (params, opt_state, state, grads0), batches)
        g = flatten_tree(last_grad)
        if ef is not None:
            g = g + ef
        if report_r is None:
            return params, opt_state, state, g, losses.mean()
        cand = client_candidates(g[None], report_r, report_impl)[0]
        return params, opt_state, state, g, cand, losses.mean()

    return phase_one_client


def make_local_phase(apply_loss: Callable, lr: float, *,
                     report_r: int | None = None,
                     report_impl: str = "sort") -> Callable:
    """apply_loss(params, state, batch) -> (loss, new_state).

    Returns jitted phase(params_s, opt_s, state_s, batches[, ef]) with
    leading client axis on every arg; batches: (N, H, ...) pytree.
    Output is ``(params_s, opt_s, state_s, G (N, d), report, losses
    (N,))`` — the per-client final-step flat gradients, the fused top-r
    candidate report (``client_candidates(G, report_r, report_impl)``,
    or None when ``report_r`` is None) and the mean loss per client.
    ``ef`` (optional (N, d)) is the error-feedback residual, added
    before the report so selection sees the same post-ef gradients the
    unfused engine path computed. The train loop is the vmap of
    :func:`make_client_phase`, exactly — the batch's leading axis may
    be ANY m <= N (the compute plane's gathered round trains only the
    active m rows; per-client math is row-independent, DESIGN.md §11).
    """
    base = make_client_phase(apply_loss, lr)
    vphase = jax.vmap(lambda p, o, s, b: base(p, o, s, b))

    def phase(params_s, opt_s, state_s, batches, ef=None):
        params_s, opt_s, state_s, G, losses = vphase(
            params_s, opt_s, state_s, batches)
        if ef is not None:
            G = G + ef
        report = None
        if report_r is not None:
            with jax.named_scope("candidate_report"):
                report = client_candidates(G, report_r, report_impl)
        return params_s, opt_s, state_s, G, report, losses

    return jax.jit(phase)


def stack_clients(trees: list):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def broadcast_global(global_params, n: int):
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), global_params)
