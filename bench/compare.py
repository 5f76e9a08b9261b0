"""The numbers that decide ``correct``: the program's trajectory over the
checked rounds against the reference's.

Each is a gap that is 0 where the two agree exactly:

- ``loss_gap``: relative gap of the participants' mean training loss at
  each checked evaluation round, the largest (both sides report NaN for a
  client that sat the round out);
- ``local_state_gap``: the clients' Adam first moments after the checked
  rounds, by the worst leaf of the worst client: the gap between the
  program's norm and the reference's, over the larger of the reference's
  norm of that leaf and of its median leaf. Every client counts, so a
  client whose state should have been held and moved fails; one that has
  not yet taken part, whose reference moments are all zero, reads the
  program's worst leaf norm over the median of the trained clients' median
  leaf norms (0 where the program's are zero too);
- ``global_grad_gap``: the same for the global Adam first moment, the
  aggregated gradients as the server's optimizer holds them;
- ``global_change_gap``: the same for the change of the global
  parameters over the checked rounds, and ``global_change_median_gap``
  the median over the leaves of that leaf gap (steadier where the picks
  of the two sides part, so that which coordinates a leaf moved differs);
- ``pick_mismatch``: share of the requested (round, client, index)
  entries that the reference did not request (the sentinel d of a client
  that sat out is no request);
- ``age_mismatch``: share of the per-client age entries that differ, among
  the coordinates that either side ever requested;
- ``cluster_mismatch``: client pairs whose co-membership differs after the
  last checked recluster (only where the checked rounds hold one).

Leaves whose reference moment is nought to rounding (under a thousandth of
the median leaf's, as a convolution bias before batch normalisation) are
left out of the leaf gaps: Adam moves them by round-off alone.
"""
from __future__ import annotations

import jax
import numpy as np

NEGLIGIBLE = 1e-3


def _norms(tree) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(a, np.float64))
                     for a in jax.tree_util.tree_leaves(tree)])


def leaf_gaps(prog, ref, basis) -> tuple:
    """Per-leaf gaps of norms, and which leaves count: those whose
    reference moment (``basis``) is at least ``NEGLIGIBLE`` of the median
    over the leaves it moves at all (a sparse update leaves most of the
    network's leaves untouched in a round)."""
    p, r, b = _norms(prog), _norms(ref), _norms(basis)
    moved = b > 0
    keep = moved & (b >= NEGLIGIBLE * np.median(b[moved])) if moved.any() \
        else moved
    med = np.median(r[keep]) if keep.any() else 0.0
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(p - r) / np.maximum(r, med)
    return gap, keep, p, r


def leaf_gap(prog, ref, basis, over=np.max) -> float:
    """Worst-leaf (or, with ``over=np.median``, median-leaf) gap of norms
    over the leaves that count."""
    gap, keep, _, _ = leaf_gaps(prog, ref, basis)
    return float(over(gap[keep])) if keep.any() else 0.0


def worst_leaf(prog, ref, basis) -> dict:
    """Where a leaf gap comes from: the worst counted leaf, its norms, and
    the median gap over the counted leaves."""
    gap, keep, p, r = leaf_gaps(prog, ref, basis)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(ref)[0]]
    i = int(np.argmax(np.where(keep, gap, -1.0)))
    return {"leaf": names[i], "prog_norm": float(p[i]),
            "ref_norm": float(r[i]), "gap": float(gap[i]),
            "median_gap": float(np.median(gap[keep])),
            "left_out": [n for n, k in zip(names, keep) if not k]}


def co_membership(labels: np.ndarray) -> np.ndarray:
    labels = np.asarray(labels)
    return labels[:, None] == labels[None, :]


def participant_mean(losses) -> float:
    """Mean loss of a round's participants (the finite entries)."""
    losses = np.asarray(losses)
    return float(np.mean(losses[np.isfinite(losses)]))


def client_gaps(prog_mu: list, ref_mu: list) -> list:
    """Each client's leaf gap of its Adam first moment. A client whose
    reference moments are all zero has not yet trained: its gap is the
    program's worst leaf norm over the scale of a trained client's moment
    (the median over those clients of their median counted leaf norm)."""
    rows = [leaf_gaps(p, r, r) for p, r in zip(prog_mu, ref_mu)]
    scale = np.median([np.median(r[keep]) for _, keep, _, r in rows
                       if keep.any()] or [0.0])
    out = []
    for gap, keep, p, r in rows:
        if r.any():
            out.append(float(np.max(gap[keep])) if keep.any() else 0.0)
        else:
            worst = p.max(initial=0.0)
            out.append(float(worst / scale) if worst else 0.0)
    return out


def explain(prog, ref) -> dict:
    """The worst leaf behind each leaf gap (for calibration)."""
    per_client = client_gaps(prog.client_mu, ref.client_mu)
    worst = int(np.argmax(per_client))
    if _norms(ref.client_mu[worst]).any():
        local = worst_leaf(prog.client_mu[worst], ref.client_mu[worst],
                           ref.client_mu[worst])
    else:
        local = {"untrained": True, "gap": per_client[worst]}
    local["client"] = worst
    return {"local_state_gap": local,
            "global_grad_gap": worst_leaf(prog.global_mu, ref.global_mu,
                                          ref.global_mu),
            "global_change_gap": worst_leaf(_change(prog), _change(ref),
                                            ref.global_mu)}


def _change(t):
    return jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        t.params, t.params0)


def gaps(prog, ref, eval_rounds: list, prog_losses: list) -> dict:
    """prog/ref: ``reference.Trajectory``s over the same rounds;
    ``prog_losses[j]`` is the program's mean loss at round
    ``eval_rounds[j]``."""
    out = {}
    ref_loss = [participant_mean(ref.losses[t - 1]) for t in eval_rounds]
    out["loss_gap"] = max(abs(p - r) / abs(r)
                          for p, r in zip(prog_losses, ref_loss))
    out["local_state_gap"] = max(client_gaps(prog.client_mu, ref.client_mu))
    out["global_grad_gap"] = leaf_gap(prog.global_mu, ref.global_mu,
                                      ref.global_mu)
    out["global_change_gap"] = leaf_gap(_change(prog), _change(ref),
                                        ref.global_mu)
    out["global_change_median_gap"] = leaf_gap(
        _change(prog), _change(ref), ref.global_mu, over=np.median)
    d = ref.freq.shape[1]
    missed = sum(np.setdiff1d(p_i[p_i < d], r_i).size
                 for p, r in zip(prog.picks, ref.picks)
                 for p_i, r_i in zip(p, r))
    out["pick_mismatch"] = missed / sum(int(np.sum(r < d))
                                        for r in ref.picks)
    seen = (prog.freq > 0) | (ref.freq > 0)
    out["age_mismatch"] = (float(np.sum((prog.client_ages != ref.client_ages)
                                        & seen)) / max(int(seen.sum()), 1))
    if ref.labels:
        out["cluster_mismatch"] = float(np.sum(
            co_membership(prog.labels[-1]) != co_membership(ref.labels[-1]))
            // 2)
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): each number the cell's limits name, beside its
    limit. A number that is missing or not finite fails. The cell's other
    numbers are readings only: they separate the program from neither the
    control nor the planted faults (see PERF.md)."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        if value is None or not np.isfinite(value):
            value = None                     # JSON has no NaN
        checks[name] = {"value": value, "limit": limit}
        ok &= value is not None and value <= limit
    return ok, checks
