"""Pallas TPU kernel: per-block magnitude histogram (exponent buckets).

First pass of accelerator-native top-k: bucket |g| by binary exponent into
NBINS counters; a tiny jnp epilogue (:func:`threshold_from_hist`) picks
the threshold bin so that >= r entries survive, and only candidates are
ranked exactly. All-d work (the expensive part) is one streaming pass,
VMEM-tiled. One kernel, :func:`maghist_batch` ((N, d)-grid, one
program per (row-block, d-block) tile, per-row histograms accumulated
across blocks), serves both the production candidate plane
(``ops.threshold_topk_batch``) and the single-vector per-block
histograms of ``ops.maghist`` (a (d,) vector viewed as
(d // BLOCK_D, BLOCK_D) rows).

Bins come from the EXACT float32 exponent field (bitcast, not
``floor(log2)``): ``bin = clip(exponent(|g|) + OFFSET, 0, NBINS-1)``.
Exactness matters — the threshold containment proof needs "mag in bin b
implies mag >= 2^(b - OFFSET)", which float ``log2`` can violate by one
ulp at bin edges. Pathological values are routed explicitly: NaN -> bin 0
(never a candidate), +/-inf -> top bin (always a candidate), zeros and
denormals -> bin 0 (exponent field 0 clips there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_D = 4096
NBINS = 64
OFFSET = 40          # exponent -40 .. +23 covered


def exponent_bins(mag: jnp.ndarray) -> jnp.ndarray:
    """|g| (f32, non-negative) -> int32 bin ids via the exact exponent
    field. NaN -> 0, inf -> NBINS-1 (exponent 0xFF clips to the top bin),
    zeros/denormals -> 0 (exponent field 0 clips to the bottom bin)."""
    bits = jax.lax.bitcast_convert_type(mag.astype(jnp.float32), jnp.int32)
    e = jnp.right_shift(bits, 23) & 0xFF                 # biased exponent
    b = jnp.clip(e - 127 + OFFSET, 0, NBINS - 1).astype(jnp.int32)
    return jnp.where(mag != mag, 0, b)                   # NaN -> bin 0


ROWS = 8             # row block of the batched kernel (TPU sublane tile)


def _kernel(g_ref, hist_ref):
    """One (ROWS, block_d) tile: per-row bin counts, accumulated across
    the inner d-block grid axis. One compare-and-count pass per bin keeps
    every intermediate a lane-dense (ROWS, block_d) vector — no one-hot
    with a bin axis, no lane->sublane reshape."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    b = exponent_bins(jnp.abs(g_ref[...].astype(jnp.float32)))
    lanes = jax.lax.broadcasted_iota(jnp.int32, hist_ref.shape, 1)

    def count(i, h):
        c = jnp.sum((b == i).astype(jnp.int32), axis=1, keepdims=True)
        return jnp.where(lanes == i, c, h)

    hist_ref[...] += jax.lax.fori_loop(0, NBINS, count,
                                       jnp.zeros(hist_ref.shape, jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret", "block_d"))
def maghist_batch(G: jnp.ndarray, *, interpret: bool = False,
                  block_d: int = BLOCK_D) -> jnp.ndarray:
    """G: (N, d) with N % ROWS == 0 and d % block_d == 0 -> (N, NBINS)
    int32 row histograms (ops.py pads).

    Grid (N // ROWS, d // block_d): one program per (row-block, d-block)
    tile; the per-row histograms accumulate across the inner
    (fastest-moving) block dimension, exactly the revisiting pattern
    ``sparse_aggregate`` uses. ``block_d`` is the autotune surface
    (kernels.autotune).
    """
    n, d = G.shape
    assert n % ROWS == 0 and d % block_d == 0
    return pl.pallas_call(
        _kernel,
        grid=(n // ROWS, d // block_d),
        in_specs=[pl.BlockSpec((ROWS, block_d), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((ROWS, NBINS), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, NBINS), jnp.int32),
        interpret=interpret,
        name="maghist_batch",
    )(G)


def hist_rows(G: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp row histograms, (N, d) -> (N, NBINS) int32 — the oracle
    for :func:`maghist_batch` and the CPU (non-interpret) production path
    of ``ops.threshold_topk_batch``. One scatter-add pass over d."""
    n = G.shape[0]
    b = exponent_bins(jnp.abs(G.astype(jnp.float32)))
    return jnp.zeros((n, NBINS), jnp.int32).at[
        jnp.arange(n)[:, None], b].add(1)


def threshold_from_hist_batch(hist: jnp.ndarray, r: int) -> jnp.ndarray:
    """Per-row magnitude threshold: smallest tau with exceed-count >= r.

    hist: (N, NBINS) int32 row histograms -> (N,) f32. Candidates are
    {i : |g_i| >= tau}; their count is in [r, r + threshold-bin
    population). tau = 2^(bin - OFFSET), EXCEPT bin 0 where tau = 0: the
    bottom bin also holds zeros and denormals (all < 2^-OFFSET), so its
    lower bin edge would wrongly exclude them — tau = 0 keeps every
    non-NaN entry a candidate, preserving exact containment.
    """
    from_top = jnp.cumsum(hist[..., ::-1], axis=-1)[..., ::-1]
    # from_top is non-increasing in the bin index, so {b : from_top >= r}
    # is a prefix (non-empty: from_top[0] counts everything); the LARGEST
    # qualifying bin is its length - 1
    bin_sel = jnp.sum((from_top >= r).astype(jnp.int32), axis=-1) - 1
    return jnp.where(bin_sel == 0, jnp.float32(0),
                     jnp.exp2((bin_sel - OFFSET).astype(jnp.float32)))


def threshold_search(mag: jnp.ndarray, r: int) -> jnp.ndarray:
    """Scatter-free tau: per-row binary search of the bin edges over
    exceed-counts, ceil(log2(NBINS)) = 6 vectorized passes over d.

    mag: (N, d) non-negative f32 -> (N,) f32 tau, IDENTICAL to
    ``threshold_from_hist_batch(hist_rows(G), r)`` (pinned by tests):
    ``count(mag >= 2^(b - OFFSET)) == count(bin >= b)`` for b >= 1
    exactly (bin edges are exact powers of two; NaN sits in bin 0 and
    fails every ``>=``), and the b = 0 edge is never probed — the search
    keeps the invariant count(lo) >= r with lo = 0 trivially true, so
    all-small rows converge to lo = 0 and the tau = 0 rule applies.
    The CPU production path of ``ops.threshold_topk_batch`` uses this
    instead of materializing histograms (XLA CPU scatter is serial);
    the Pallas plane gets the histogram for free from ``maghist_batch``.
    """
    n = mag.shape[0]

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        edge = jnp.exp2((mid - OFFSET).astype(jnp.float32))
        cnt = jnp.sum((mag >= edge[:, None]).astype(jnp.int32), axis=1)
        ok = cnt >= r
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(
        0, 6, body, (jnp.zeros((n,), jnp.int32),
                     jnp.full((n,), NBINS, jnp.int32)))
    return jnp.where(lo == 0, jnp.float32(0),
                     jnp.exp2((lo - OFFSET).astype(jnp.float32)))


def threshold_from_hist(hist: jnp.ndarray, r: int) -> jnp.ndarray:
    """Single-vector epilogue over per-block histograms: (nb, NBINS) ->
    scalar tau (f32). See :func:`threshold_from_hist_batch`."""
    return threshold_from_hist_batch(hist.sum(0)[None, :], r)[0]
