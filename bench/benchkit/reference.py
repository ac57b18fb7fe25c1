"""The plain reference: exact z-normalized, banded DTW nearest-window search.

Independent of the program: nothing here imports ``repro`` or takes what the
program made. Two parts.

* ``dtw_naive`` — the float64 full-matrix DTW of the paper's Figure 1, a
  copy of the program's NumPy oracle. It gives the reference distance of a
  window.
* ``search`` — every window of the reference against each query, by the
  textbook recurrence ``M[i,j] = c + min(M[i-1,j], M[i,j-1], M[i-1,j-1])``
  evaluated cell by cell along each row (no lower bounds, no early
  abandoning, no prefix scans), vectorized over blocks of windows on the
  cell's devices. Run in float32 it gives each query's nearest window; the
  float32 rounding of a sequential sum of at most ``l * (2w + 1)`` positive
  terms is far below the distance gap to any other window of the planted
  traffic.
  Run with every value rounded to bfloat16 it is the precision control that
  the comparison has to reject.

Window statistics and query normalization are computed here in float64 from
the float32 inputs the program is given.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

INF = math.inf
EPS = 1e-8
# Bytes of the fori carry, ``queries x (2w + 2) x windows`` float32, that
# one block of windows may hold on a device, whatever the reference's
# length: 81,442 windows at l=1024 and 8 queries, 645,277 at l=128.
CARRY_BYTES = 2**29


def dtw_naive(s: np.ndarray, t: np.ndarray, window: int | None = None) -> float:
    """O(n*m) full-matrix DTW (Figure 1 equations). Reference of references."""
    s = np.asarray(s, dtype=np.float64)
    t = np.asarray(t, dtype=np.float64)
    n, m = len(s), len(t)
    if window is not None and n != m:
        raise ValueError("windowed DTW requires equal lengths here")
    M = np.full((n + 1, m + 1), INF)
    M[0, 0] = 0.0
    for i in range(1, n + 1):
        lo, hi = 1, m
        if window is not None:
            lo, hi = max(1, i - window), min(m, i + window)
        for j in range(lo, hi + 1):
            c = (s[i - 1] - t[j - 1]) ** 2
            M[i, j] = c + min(M[i - 1, j], M[i, j - 1], M[i - 1, j - 1])
    return float(M[n, m])


def znorm64(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return (x - x.mean()) / max(x.std(), EPS)


def window_stats64(ref: np.ndarray, length: int) -> tuple[np.ndarray, np.ndarray]:
    """Float64 mean and standard deviation of every window of ``ref``."""
    x = np.asarray(ref, np.float64)
    p = np.concatenate([[0.0], np.cumsum(x)])
    q = np.concatenate([[0.0], np.cumsum(x * x)])
    s1 = p[length:] - p[:-length]
    s2 = q[length:] - q[:-length]
    mu = s1 / length
    return mu, np.sqrt(np.maximum(s2 / length - mu * mu, 0.0))


def window_dtw64(ref: np.ndarray, query: np.ndarray, start: int,
                 length: int, window: int) -> float:
    """Float64 DTW of the z-normalized ``query`` and window at ``start``."""
    win = np.asarray(ref[start : start + length], np.float64)
    return dtw_naive(znorm64(query), znorm64(win), window=window)


def _rounding(dtype: str):
    """Rounds a float32 value to ``dtype`` and keeps it in float32.

    ``lax.reduce_precision`` is never removed by the compiler, where a
    chain of bfloat16 ops may be computed in float32 with the intermediate
    roundings left out; so every value of a lower-precision run is rounded
    where it is produced, the same on every backend.
    """
    if jnp.dtype(dtype) == jnp.float32:
        return lambda v: v
    bits = jnp.finfo(dtype)
    return partial(jax.lax.reduce_precision, exponent_bits=bits.nexp,
                   mantissa_bits=bits.nmant)


@partial(jax.jit, static_argnames=("length", "window", "dtype"))
def _all_windows(x, mu, inv_sigma, qn, *, length, window, dtype):
    """``(Qb, n_win)`` DTW distances of every window to each query row,
    every value rounded to ``dtype``."""
    rnd = _rounding(dtype)
    n_win = x.shape[0] - length + 1
    band = 2 * window + 1
    nq = qn.shape[0]
    inf = jnp.float32(jnp.inf)
    qn = rnd(qn)
    # Band coordinates: row i, slot k holds column j = i - window + k. Slot
    # ``band`` stays +inf (the "up" neighbour past the band's right edge).
    # The virtual row -1 holds M[-1, -1] = 0 at slot ``window``.
    prev0 = jnp.full((nq, band + 1, n_win), inf)
    prev0 = prev0.at[:, window, :].set(0)

    def row(i, prev):
        qi = jax.lax.dynamic_index_in_dim(qn, i, axis=1, keepdims=False)
        left = jnp.full((nq, n_win), inf)
        cols = []
        for k in range(band):  # left to right: each cell needs its left
            j = i - window + k
            xs = jax.lax.dynamic_slice(x, (jnp.clip(j, 0, length - 1),), (n_win,))
            xn = rnd((xs - mu) * inv_sigma)
            c = rnd((qi[:, None] - xn[None, :]) ** 2)
            v = rnd(c + jnp.minimum(jnp.minimum(prev[:, k + 1], prev[:, k]),
                                    left))
            left = jnp.where(jnp.logical_and(j >= 0, j < length), v, inf)
            cols.append(left)
        cols.append(jnp.full((nq, n_win), inf))
        return jnp.stack(cols, axis=1)

    return jax.lax.fori_loop(0, length, row, prev0)[:, window, :]


def search(ref: np.ndarray, queries: np.ndarray, length: int, window: int,
           dtype: str = "float32", block: int = 8, devices=None):
    """Nearest window of every query, computed on ``devices``.

    The windows are evaluated in blocks of equal size, at most as many as
    ``CARRY_BYTES`` hold, dealt in turn to ``devices`` (JAX's default
    device where None), so that a device's memory does not grow with the
    reference. No value of one
    window's recurrence depends on another window, so the distances are
    those of one block over all windows, bit for bit.

    Returns ``(starts, dists, runner_up)`` as NumPy arrays: each query's
    argmin window (the lowest start among equal distances), its distance as
    computed in ``dtype``, and the smallest distance of any other window.
    """
    ref = np.asarray(ref, np.float32)
    mu, sd = window_stats64(ref, length)
    qn = np.stack([znorm64(q[:length]) for q in np.asarray(queries)])
    devices = list(devices or [None])
    n_win = mu.shape[0]
    window_block = CARRY_BYTES // (block * (2 * window + 2) * 4)
    n_blocks = -(-n_win // window_block)
    n_blocks = -(-n_blocks // len(devices)) * len(devices)
    size = -(-n_win // n_blocks)
    pad = np.zeros(n_blocks * size - n_win, np.float32)
    x = np.concatenate([ref, pad])
    mu = np.concatenate([mu.astype(np.float32), pad])
    inv = np.concatenate([(1.0 / np.maximum(sd, EPS)).astype(np.float32), pad])
    blocks = []  # (device, x, mu, 1/sigma) of each block of windows
    for b in range(n_blocks):
        dev, lo = devices[b % len(devices)], b * size
        blocks.append((dev, *jax.device_put(
            (x[lo : lo + size + length - 1], mu[lo : lo + size],
             inv[lo : lo + size]), dev)))
    pending = []
    for b in range(0, len(qn), block):
        qb = qn[b : b + block]
        pad_q = block - len(qb)
        qb = np.concatenate([qb, np.repeat(qb[-1:], pad_q, axis=0)]
                            ).astype(np.float32)
        # Every block is dispatched before any is read back.
        pending.append((len(qb) - pad_q, [
            _all_windows(x_d, mu_d, inv_d, jax.device_put(qb, dev),
                         length=length, window=window, dtype=dtype)
            for dev, x_d, mu_d, inv_d in blocks]))
    starts, dists, runner = [], [], []
    for n_q, parts in pending:
        d = np.concatenate(jax.device_get(parts), axis=1)[:n_q, :n_win]
        for row in d:
            k = int(np.argmin(row))
            starts.append(k)
            dists.append(float(row[k]))
            rest = np.delete(row, k)
            runner.append(float(rest.min()) if rest.size else INF)
    return np.asarray(starts), np.asarray(dists), np.asarray(runner)
