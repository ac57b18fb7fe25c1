"""Lower-bound cascade over all candidate windows (UCR-suite stage 1).

One fused, batched pass computes LB_Kim and LB_Keogh for *every* window —
the TPU-native replacement for the UCR suite's per-candidate cascade. The
output is a best-first candidate ordering plus per-window lower bounds, which
stage 2 (batched EAPrunedDTW, search/subsequence.py) consumes.

The pass runs offset-major: for a query offset ``j`` the term of every
window is the one unit-stride slice ``ref[j : j + n_win]``, normalized by
the per-window ``(mu, sigma)`` tables and clamped against the scalars
``U[j]``/``L[j]``. ``length`` such steps accumulate into an ``(n_win,)``
vector, so no ``(windows, length)`` block is ever built and the work is
``length`` wide vector ops rather than one slice per window.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.common import clamp_sigma
from repro.core.lower_bounds import _lb_keogh_terms, envelope

# Offsets per loop step: enough for XLA to fuse a few slices into one pass
# over the tables, few enough that l=1024 compiles quickly.
_UNROLL = 8


class CascadeOut(NamedTuple):
    order: jax.Array    # (N,) window starts sorted by ascending lower bound
    lb_sorted: jax.Array  # (N,) the lower bound per sorted window
    n_windows: int


@partial(jax.jit, static_argnames=("length", "window", "use_kim", "use_keogh"))
def cascade_lower_bounds(
    ref: jax.Array,
    query_n: jax.Array,
    mu: jax.Array,
    sigma: jax.Array,
    length: int,
    window: int,
    use_kim: bool = True,
    use_keogh: bool = True,
) -> jax.Array:
    """Lower bound for every candidate window start. Returns ``(N,)``.

    ``query_n`` must already be z-normalized. When both bounds are enabled the
    result is their max (both are valid DTW lower bounds). Each normalized
    value equals ``norm_window_slice``'s; only the order of the sum differs.
    """
    n_win = ref.shape[0] - length + 1
    sg = clamp_sigma(sigma)

    def norm(x):
        return (x - mu) / sg

    lb = jnp.zeros((n_win,), ref.dtype)
    if use_kim:
        kim = ((norm(ref[:n_win]) - query_n[0]) ** 2
               + (norm(ref[length - 1:]) - query_n[length - 1]) ** 2)
        lb = jnp.maximum(lb, kim)
    if use_keogh:
        u, low = envelope(query_n, window)

        def offset(j, acc):
            v = norm(jax.lax.dynamic_slice(ref, (j,), (n_win,)))
            return acc + _lb_keogh_terms(v, u[j], low[j])

        keogh = jax.lax.fori_loop(
            0, length, offset,
            jnp.zeros((n_win,), ref.dtype),
            unroll=min(_UNROLL, length),
        )
        lb = jnp.maximum(lb, keogh)
    return lb


@partial(jax.jit, static_argnames=("length", "window", "use_kim", "use_keogh"))
def cascade(
    ref: jax.Array,
    query_n: jax.Array,
    mu: jax.Array,
    sigma: jax.Array,
    length: int,
    window: int,
    use_kim: bool = True,
    use_keogh: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Best-first ordering of window starts by lower bound.

    Returns ``(order, lb_sorted)``; both ``(N,)`` with N = #windows.
    """
    lbs = cascade_lower_bounds(
        ref, query_n, mu, sigma, length, window, use_kim, use_keogh
    )
    order = jnp.argsort(lbs)
    return order, lbs[order]
