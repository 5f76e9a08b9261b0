"""Jit'd public wrappers around the Pallas kernels: padding to block
multiples, the platform rule for interpret mode (compiled on a TPU,
interpreted only on the CPU, where tests and rehearsals run), the
hybrid threshold-top-k built from the maghist kernel, and the autotune
registry consultation (kernels.autotune) — every tiling argument left
unspecified by the caller resolves through the persistent
``experiments/bench/AUTOTUNE.json`` sweep results before falling back to
the module constants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import maghist as MH
from repro.kernels import segmented_topk as ST
from repro.kernels import sparse_aggregate as SA
from repro.kernels import decode_attention as DA

def _interpret() -> bool:
    """Interpret mode is decided by the platform: the kernels run
    compiled on a TPU and are emulated only on the CPU. A kernel the TPU
    compiler refuses is a bug to fix, never a reason to emulate it on
    the chip."""
    return jax.default_backend() == "cpu"


def backend_tag() -> str:
    """Autotune backend key: the platform, plus '+interp' where the
    kernels run in interpret mode (emulation timings must never be
    confused with real-TPU entries)."""
    return jax.default_backend() + ("+interp" if _interpret() else "")


def _tuned(kernel: str, shape, dtype, defaults: dict) -> dict:
    """Resolve a kernel's tiling: registry entry for (kernel, raw shape,
    dtype, backend) if one exists, module-constant defaults otherwise.
    Unknown keys in a stale registry entry are ignored."""
    cfg = autotune.lookup(kernel, shape, str(jnp.dtype(dtype)),
                          backend_tag())
    out = dict(defaults)
    if cfg:
        out.update({k: v for k, v in cfg.items() if k in defaults})
    return out


def _pad_to(x, m, fill=0):
    pad = (-x.shape[0]) % m
    if pad:
        x = jnp.pad(x, (0, pad), constant_values=fill)
    return x


def sparse_aggregate(idx: jnp.ndarray, vals: jnp.ndarray, age: jnp.ndarray,
                     *, block_d: int | None = None,
                     nk_tile: int | None = None):
    """Public entry: arbitrary NK and d; pads idx with d (dropped) and the
    age vector with zeros (sliced back off). block_d/nk_tile expose the
    kernel tiling for sweeps; left as None they resolve through the
    autotune registry (key: raw (NK, d) shape) before the module
    constants."""
    d = age.shape[0]
    if block_d is None or nk_tile is None:
        cfg = _tuned("sparse_aggregate", (idx.shape[0], d), vals.dtype,
                     {"block_d": SA.BLOCK_D, "nk_tile": SA.NK_TILE})
        block_d = block_d or cfg["block_d"]
        nk_tile = nk_tile or cfg["nk_tile"]
    dp = d + ((-d) % block_d)
    idx_p = _pad_to(idx.astype(jnp.int32), nk_tile, fill=dp)
    vals_p = _pad_to(vals.astype(jnp.float32), nk_tile, fill=0)
    age_p = _pad_to(age.astype(jnp.int32), block_d, fill=0)
    dense, new_age = SA.sparse_aggregate(idx_p, vals_p, age_p,
                                         interpret=_interpret(),
                                         block_d=block_d, nk_tile=nk_tile)
    return dense[:d], new_age[:d]


def segmented_age_topk(cand: jnp.ndarray, cand_age: jnp.ndarray,
                       valid: jnp.ndarray, k: int, *,
                       disjoint: bool = True, lane: int | None = None):
    """Public entry for the segmented selection kernel: cand/cand_age
    (C, S, r) candidate indices / non-negative ages, valid (C, S) member
    mask -> (C, S, k) int32 picks. Pads the candidate axis to ``lane``
    (autotuned; default the int32 lane width) with never-selected
    sentinels (cand = -2 so it can't match the taken buffer, age = NEG);
    requires k <= r so padding can never be picked."""
    C, S, r = cand.shape
    if k > r:
        raise ValueError(f"need k <= r candidates (got k={k}, r={r})")
    lane = lane or _tuned("segmented_age_topk", (C, S, r), jnp.int32,
                          {"lane": ST.LANE})["lane"]
    pad = (-r) % lane
    cand = cand.astype(jnp.int32)
    cand_age = cand_age.astype(jnp.int32)
    if pad:
        cand = jnp.pad(cand, ((0, 0), (0, 0), (0, pad)),
                       constant_values=-2)
        cand_age = jnp.pad(cand_age, ((0, 0), (0, 0), (0, pad)),
                           constant_values=ST.NEG)
    return ST.segmented_age_topk(cand, cand_age,
                                 valid.astype(jnp.int32), k,
                                 disjoint=disjoint, interpret=_interpret())


def maghist(g: jnp.ndarray):
    """Per-block histograms of one vector: (d,) -> (ceil(d / BLOCK_D),
    NBINS) int32 — the batched kernel's rows over the (nb, BLOCK_D)
    view of the zero-padded vector."""
    gp = _pad_to(g, MH.BLOCK_D, fill=0)
    return maghist_batch(gp.reshape(-1, MH.BLOCK_D), block_d=MH.BLOCK_D)


def maghist_batch(G: jnp.ndarray, *, block_d: int | None = None):
    """Batched magnitude histograms via the (N, d)-grid Pallas kernel:
    (N, d) -> (N, NBINS) int32. Pads d with zeros (bottom bin — they can
    only inflate the bin-0 count, which the tau = 0 epilogue rule makes
    harmless; zero pad rows are sliced back off). block_d resolves
    through the autotune registry."""
    n, d = G.shape
    block_d = block_d or _tuned("maghist_batch", (n, d), G.dtype,
                                {"block_d": MH.BLOCK_D})["block_d"]
    G = jnp.pad(G, ((0, (-n) % MH.ROWS), (0, (-d) % block_d)))
    return MH.maghist_batch(G, interpret=_interpret(), block_d=block_d)[:n]


def _masked_topr(mag: jnp.ndarray, tau: jnp.ndarray, r: int):
    """Shared epilogue: mask non-candidates to -1, exact stable top-r of
    the survivors. Returns (vals, idx) with idx BIT-IDENTICAL to
    ``lax.top_k(|G|, r)`` row-wise for NaN-free input (see
    ops.threshold_topk for the argument)."""
    masked = jnp.where(mag >= tau[:, None], mag, -1.0)
    return jax.lax.top_k(masked, r)


def threshold_topk_batch(G: jnp.ndarray, r: int, *,
                         hist_impl: str | None = None) -> jnp.ndarray:
    """Batched two-pass top-r candidate report — the production candidate
    plane (``core.strategies.client_candidates`` impl='threshold').

    G: (N, d) -> (N, r) int32 indices, BIT-IDENTICAL to
    ``vmap(lambda g: lax.top_k(|g|, r)[1])(G)`` for NaN-free G: the exact
    |g| top-r set is always contained in the candidate set
    {|g| >= tau} (tau from the exact-exponent histogram; tau = 0 when the
    threshold bin is the bottom bin, so zeros/denormals stay candidates),
    surviving values keep their magnitudes while non-candidates drop to
    -1 < tau <= every candidate, and ``lax.top_k`` is stable — same
    values in the same index order give the same report. With NaNs the
    result is ``top_k(where(isnan, -1, |g|), r)``: NaN is never a
    candidate (pinned by tests). The d-sized prologue is ONE streaming
    pass; hist_impl picks it ('pallas' = the (N, d)-grid
    ``maghist_batch`` kernel + the vectorized histogram epilogue,
    'jnp' = the scatter-free binary-search tau, identical bit-for-bit;
    None routes pallas on a TPU and jnp on the CPU, where emulating the
    kernel would be Python-speed).
    """
    if hist_impl is None:
        hist_impl = "jnp" if _interpret() else "pallas"
    mag = jnp.abs(G.astype(jnp.float32))
    tau = (MH.threshold_from_hist_batch(maghist_batch(G), r)
           if hist_impl == "pallas" else MH.threshold_search(mag, r))
    return _masked_topr(mag, tau, r)[1]


def threshold_topk(g: jnp.ndarray, r: int):
    """Two-pass accelerator top-r: histogram -> threshold -> exact rank of
    the surviving candidates. Returns (vals, idx) like lax.top_k(|g|, r)
    (vals are the masked magnitudes: non-candidates read -1).

    Guarantee (tested): the exact |g| top-r set is always contained in the
    candidate set {|g| >= tau}, so the final exact top_k over candidates
    equals the true top-r (ties broken by index like lax.top_k) — for any
    finite/inf input; NaN entries are never candidates, i.e. the result
    is exactly ``lax.top_k(where(isnan, -1, |g|), r)``.
    """
    mag = jnp.abs(g.astype(jnp.float32))[None, :]
    tau = (MH.threshold_search(mag, r) if _interpret()
           else MH.threshold_from_hist(maghist(g), r)[None])
    vals, idx = _masked_topr(mag, tau, r)
    return vals[0], idx[0]


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     cache_len) -> jnp.ndarray:
    """q: (B, H, D); k/v: (B, S, G, D); cache_len: scalar int.
    Batched via vmap over B; pads S to BLOCK_S."""
    B, H, D = q.shape
    S = k.shape[1]
    pad = (-S) % DA.BLOCK_S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    clen = jnp.full((1,), cache_len, jnp.int32)
    fn = functools.partial(DA.decode_attention, interpret=_interpret())
    return jax.vmap(lambda qq, kk, vv: fn(qq, kk, vv, clen))(q, k, v)
