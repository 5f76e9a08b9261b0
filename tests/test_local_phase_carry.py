"""The local phase keeps only the last step's gradient, as a scan carry.

1. Identical results: ``make_local_phase`` and ``make_client_phase`` give
   bitwise the outputs of the stacked formulation kept below as the
   reference (the scan emits every step's gradient tree and the phase
   reads the last slice): params, Adam state, BN state, the flat
   gradient, the fused candidate report and the losses. MLP and CNN
   (with batch-norm state), with and without error feedback, with no
   report and with the 'sort' and 'threshold' reports.
2. Memory: the compiled phase's temporaries do not grow with H. The
   stacked formulation grows by (H2 - H1) x N x d x 4 bytes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.strategies import client_candidates
from repro.fl import client as C
from repro.fl.engine import _build_model
from repro.optim.optimizers import adam, apply_updates

N, H, BS, R = 2, 3, 4, 9
LR = 1e-3
IN_SHAPE = {"mlp": (28 * 28,), "cnn": (32, 32, 3)}


def stacked_client_phase(apply_loss, lr, *, report_r=None,
                         report_impl="sort"):
    """The stacked formulation: the scan outputs (loss, grads) per step,
    so it holds H gradient trees, and the phase reads ``g[-1]``."""
    opt = adam(lr)

    def one_step(carry, batch):
        params, opt_state, state = carry
        (loss, new_state), grads = jax.value_and_grad(
            apply_loss, has_aux=True)(params, state, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return (params, opt_state, new_state), (loss, grads)

    def phase_one_client(params, opt_state, state, batches, ef=None):
        (params, opt_state, state), (losses, grads_seq) = jax.lax.scan(
            one_step, (params, opt_state, state), batches)
        last_grad = jax.tree_util.tree_map(lambda g: g[-1], grads_seq)
        g = C.flatten_tree(last_grad)
        if ef is not None:
            g = g + ef
        if report_r is None:
            return params, opt_state, state, g, losses.mean()
        cand = client_candidates(g[None], report_r, report_impl)[0]
        return params, opt_state, state, g, cand, losses.mean()

    return phase_one_client


def stacked_local_phase(apply_loss, lr, *, report_r=None,
                        report_impl="sort"):
    vphase = jax.vmap(stacked_client_phase(apply_loss, lr))

    def phase(params_s, opt_s, state_s, batches, ef=None):
        params_s, opt_s, state_s, G, losses = vphase(
            params_s, opt_s, state_s, batches)
        if ef is not None:
            G = G + ef
        report = None
        if report_r is not None:
            report = client_candidates(G, report_r, report_impl)
        return params_s, opt_s, state_s, G, report, losses

    return jax.jit(phase)


_SETUPS = {}


def _setup(kind):
    """(apply_loss, params_s, opt_s, state_s, batches, ef) for N clients,
    each client's params and batches different, Adam state after one
    update so every moment is non-zero."""
    if kind not in _SETUPS:
        params, state, apply_loss, _ = _build_model(
            kind, jax.random.PRNGKey(3))
        d = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        rng = np.random.default_rng(5)
        params_s = jax.tree_util.tree_map(
            lambda p: p[None] + jnp.asarray(
                rng.normal(0, 1e-2, (N,) + p.shape).astype(np.float32)),
            params)
        state_s = C.broadcast_global(state, N)
        x = rng.normal(size=(N, H, BS) + IN_SHAPE[kind]).astype(np.float32)
        y = rng.integers(0, 10, (N, H, BS)).astype(np.int32)
        batches = (jnp.asarray(x), jnp.asarray(y))
        opt = adam(LR)
        opt_s = jax.vmap(opt.init)(params_s)
        warm = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape)
                                  .astype(np.float32)), params_s)
        _, opt_s = jax.vmap(opt.update)(warm, opt_s, params_s)
        ef = jnp.asarray(rng.normal(0, 1e-3, (N, d)).astype(np.float32))
        _SETUPS[kind] = (apply_loss, params_s, opt_s, state_s, batches, ef)
    return _SETUPS[kind]


def _assert_trees_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


CASES = [(kind, use_ef, report)
         for kind in ("mlp", "cnn")
         for use_ef in (False, True)
         for report in (None, "sort", "threshold")]


def _case_id(case):
    kind, use_ef, report = case
    return f"{kind}-{'ef' if use_ef else 'noef'}-{report or 'noreport'}"


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_local_phase_equals_stacked(case):
    kind, use_ef, report = case
    apply_loss, params_s, opt_s, state_s, batches, ef = _setup(kind)
    kw = {} if report is None else dict(report_r=R, report_impl=report)
    args = (params_s, opt_s, state_s, batches) + ((ef,) if use_ef else ())
    got = C.make_local_phase(apply_loss, LR, **kw)(*args)
    want = stacked_local_phase(apply_loss, LR, **kw)(*args)
    assert (got[4] is None) == (report is None)
    _assert_trees_equal(got, want)
    if kind == "cnn":  # the BN running statistics moved
        assert not all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(jax.tree_util.tree_leaves(got[2]),
                                       jax.tree_util.tree_leaves(state_s)))


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_client_phase_equals_stacked(case):
    kind, use_ef, report = case
    apply_loss, params_s, opt_s, state_s, batches, ef = _setup(kind)
    row = lambda t: jax.tree_util.tree_map(lambda a: a[1], t)
    kw = {} if report is None else dict(report_r=R, report_impl=report)
    args = (row(params_s), row(opt_s), row(state_s), row(batches))
    args += (ef[1],) if use_ef else ()
    got = jax.jit(C.make_client_phase(apply_loss, LR, **kw))(*args)
    want = jax.jit(stacked_client_phase(apply_loss, LR, **kw))(*args)
    assert len(got) == (5 if report is None else 6)
    _assert_trees_equal(got, want)


def _temp_bytes(make_phase, h, n=4, bs=16):
    """(temp bytes of the compiled phase, one N x d f32 gradient matrix,
    the batches' bytes)."""
    params, state, apply_loss, _ = _build_model("mlp", jax.random.PRNGKey(0))
    spec = lambda a, lead: jax.ShapeDtypeStruct(lead + a.shape, a.dtype)
    params_s = jax.tree_util.tree_map(lambda p: spec(p, (n,)), params)
    opt_s = jax.eval_shape(jax.vmap(adam(LR).init), params_s)
    batches = (jax.ShapeDtypeStruct((n, h, bs, 28 * 28), jnp.float32),
               jax.ShapeDtypeStruct((n, h, bs), jnp.int32))
    d = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    ef = jax.ShapeDtypeStruct((n, d), jnp.float32)
    phase = make_phase(apply_loss, LR, report_r=R, report_impl="sort")
    compiled = phase.lower(params_s, opt_s, {}, batches, ef).compile()
    batch_bytes = sum(b.size * b.dtype.itemsize for b in batches)
    return (compiled.memory_analysis().temp_size_in_bytes, n * d * 4,
            batch_bytes)


@pytest.mark.parametrize("make_phase,grows", [
    (C.make_local_phase, False), (stacked_local_phase, True)],
    ids=["carried", "stacked"])
def test_phase_temporaries_flat_in_h(make_phase, grows):
    """From H = 4 to H = 32 the carried phase's temporaries grow by less
    than one N x d gradient matrix, beside the batches' own step-major
    copy (the vmapped scan reads them with H leading, so XLA transposes
    them once; that copy is sized by the batches, not by d). The stacked
    reference grows by at least the 28 extra gradient matrices it holds,
    which shows the measurement sees the stack."""
    t4, grad_bytes, b4 = _temp_bytes(make_phase, 4)
    t32, _, b32 = _temp_bytes(make_phase, 32)
    growth = (t32 - t4) - (b32 - b4)
    if grows:
        assert growth >= 28 * grad_bytes
    else:
        assert growth < grad_bytes
