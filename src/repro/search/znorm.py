"""Streaming z-normalization of subsequence windows via prefix sums.

The UCR suite z-normalizes every candidate window of the long reference
series. Doing that one window at a time is O(N·l); with prefix sums every
window's mean/std comes from two table lookups, and the normalized window is
materialized lazily only for the candidates that survive the LB cascade.

Two forms of the stats table:

  * ``window_stats`` — offline: one pass of prefix sums, taken within blocks
    of ``length`` samples, over the whole reference.
  * ``append_window_stats`` — appendable: given the ``length - 1`` tail of
    samples already seen and a new chunk, produce the stats of exactly the
    windows that *become valid* with that chunk (including the windows
    straddling the tail/chunk boundary) in O(tail + chunk) work. A stream of
    appends therefore builds the table of one offline pass over the
    concatenated series, to float rounding (the blocks start at the
    context's first sample, not the series') — without ever touching more
    than the boundary context. ``search/streaming.py`` drives this per
    ingest.

Precision: a window's sums never come from the difference of two sums over
more than ``length`` samples, so their float32 error does not grow with the
series. A running prefix over the whole series does: on the ECG analogue at
1,000,000 samples (l=128) it gave sigma a relative error of 1.1e-2 at
worst and 1.1e-4 at the median (XLA:CPU), where the blocked sums give
4e-6 and 6e-8.

Sigma handling (flat-segment audit): ``window_stats`` returns the *raw*
standard deviation — zero for a constant window — because pruning statistics
want the true value. Every normalization site must clamp with
``clamp_sigma`` (``max(sigma, EPS)``) before dividing; a constant window then
normalizes to exactly zero (``win - mu == 0``), so the LB cascade and DTW
stay finite on flat reference segments instead of producing inf/NaN.

Non-finite quarantine (DESIGN.md §2.6): the prefix sums above are the reason
a single NaN sample is harmful beyond its own windows without a prepass —
``cumsum`` carries it into the stats of every window that shares its block.
The quarantine contract is implemented right here at the stats layer:
``window_finite_mask`` marks the windows overlapping any non-finite sample
(one more prefix-sum pass, same O(N) shape as the stats themselves), and
``sanitize_series`` zero-fills the bad samples so the stats/cascade
arithmetic of the *surviving* windows is untouched by them. Drivers kill
the masked windows through the dead-lane sentinel (``+inf`` lower bound)
and report the count; everything outside a quarantined window stays exact.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# The sigma floor and the two normalization entry points live in core.common
# (the fused gather path normalizes inside core.batch and the kernels, and
# core must not import search); re-exported here so the search layer keeps
# one import site for all z-normalization.
from repro.core.common import EPS, clamp_sigma, norm_window_slice

__all__ = [
    "EPS",
    "append_window_stats",
    "clamp_sigma",
    "gather_norm_windows",
    "norm_window_slice",
    "sanitize_series",
    "window_finite_mask",
    "window_stats",
    "znorm",
]


@partial(jax.jit, static_argnames=("length",))
def window_stats(ref: jax.Array, length: int) -> tuple[jax.Array, jax.Array]:
    """Mean and std of every window ``ref[s : s+length]``.

    Returns ``(mu, sigma)`` of shape ``(N - length + 1,)`` each. ``sigma`` is
    raw (unclamped): exactly zero on a constant window. Divide only through
    ``clamp_sigma``.

    The sums of ``x`` and ``x * x`` come from prefix sums taken within
    blocks of ``length`` samples, never over the whole series: window
    ``s = b * length + o`` is the tail of block ``b`` from ``o`` plus the
    head of block ``b + 1`` up to ``o``, so no difference is taken between
    sums of more than ``length`` samples, and the float32 error does not
    grow with the series (module docstring, "Precision").
    """
    n = ref.shape[0]
    n_win = n - length + 1
    n_blocks = n // length + 1  # one more block than windows need
    pad = jnp.zeros((n_blocks * length - n,), ref.dtype)

    def window_sums(x):
        blocks = jnp.concatenate([x, pad]).reshape(n_blocks, length)
        incl = jnp.cumsum(blocks, axis=1)
        # head[b * length + o]: the block's sum before offset o;
        # total[b * length + o]: the whole block's sum.
        head = jnp.concatenate(
            [jnp.zeros((n_blocks, 1), x.dtype), incl[:, :-1]], axis=1
        ).reshape(-1)
        total = jnp.repeat(incl[:, -1], length)
        return (total[:n_win] - head[:n_win]) + head[length : length + n_win]

    mu = window_sums(ref) / length
    var = jnp.maximum(window_sums(ref * ref) / length - mu * mu, 0.0)
    return mu, jnp.sqrt(var)


def append_window_stats(
    tail: jax.Array, chunk: jax.Array, length: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stats of the windows that become valid when ``chunk`` is appended.

    ``tail`` holds the last ``min(seen, length - 1)`` samples of the stream
    so far (empty at stream start). Returns ``(new_tail, mu_new, sigma_new)``
    where the stats cover window starts ``seen - len(tail)`` …
    ``seen + len(chunk) - length`` in stream coordinates — i.e. every window
    ending inside the new chunk, including the ``length - 1`` windows
    straddling the tail/chunk boundary — and ``new_tail`` is the context to
    carry into the next append. Cost is O(tail + chunk) regardless of how
    long the stream already is.

    Zero windows may be valid yet (stream shorter than ``length``): then the
    stats arrays are empty and ``new_tail`` is the whole stream so far.
    """
    ctx = jnp.concatenate([jnp.asarray(tail), jnp.asarray(chunk)])
    keep = min(ctx.shape[0], length - 1)
    new_tail = ctx[ctx.shape[0] - keep :]
    if ctx.shape[0] < length:
        empty = jnp.zeros((0,), ctx.dtype)
        return new_tail, empty, empty
    mu, sigma = window_stats(ctx, length)
    return new_tail, mu, sigma


@partial(jax.jit, static_argnames=("length",))
def window_finite_mask(ref: jax.Array, length: int) -> jax.Array:
    """``(N - length + 1,)`` bool mask: True where the window is NaN/Inf-free.

    The quarantine prepass: a window overlapping *any* non-finite sample is
    excluded from search (mask False); every other window stays exact. One
    prefix-sum pass over a non-finite indicator — the same O(N) shape as
    ``window_stats``, so the clean-data overhead is one extra cumsum.
    """
    bad = (~jnp.isfinite(ref)).astype(jnp.int32)
    p = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(bad)])
    starts = jnp.arange(ref.shape[0] - length + 1)
    return (p[starts + length] - p[starts]) == 0


@jax.jit
def sanitize_series(ref: jax.Array) -> jax.Array:
    """Zero-fill non-finite samples so prefix sums stay finite.

    Only windows already condemned by ``window_finite_mask`` contain the
    zero-filled samples; the fill exists so the shared ``cumsum`` does not
    carry a NaN into the table entries of the *surviving* windows. On a
    fully finite series this is the identity.
    """
    return jnp.where(jnp.isfinite(ref), ref, jnp.zeros_like(ref))


@jax.jit
def znorm(x: jax.Array) -> jax.Array:
    """Z-normalize along the last axis (whole-series, for queries)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    sd = jnp.std(x, axis=-1, keepdims=True)
    return (x - mu) / clamp_sigma(sd)


@partial(jax.jit, static_argnames=("length",))
def gather_norm_windows(
    ref: jax.Array,
    starts: jax.Array,
    length: int,
    mu: jax.Array,
    sigma: jax.Array,
) -> jax.Array:
    """Materialize z-normalized windows ``(K, length)`` for given starts.

    ``mu``/``sigma`` are the precomputed per-window stats indexed by start.

    This is the O(K·l) **slab** baseline (``gather="slab"``): an arbitrary
    index gather that re-copies every overlapping window. The default search
    paths use the fused normalize-on-slice form instead
    (``core.common.norm_window_slice`` on the jax backend, in-kernel
    slicing on Pallas) with an O(N + K) working set; sanctioned callers of
    this function are the full/pruned baseline cores in
    ``search.pipeline._baseline_search_impl`` and the explicit
    ``gather="slab"`` comparison arms — ``scripts/lint_layers.py`` enforces
    the import surface.
    """
    idx = starts[:, None] + jnp.arange(length)[None, :]
    win = ref[idx]
    m = mu[starts][:, None]
    s = clamp_sigma(sigma[starts])[:, None]
    return (win - m) / s
