"""The offset-major LB cascade against the per-window formulation.

``search.cascade.cascade_lower_bounds`` sums LB_Keogh offset by offset over
contiguous reference slices; ``kernels.ref.lb_all_windows_ref`` builds every
z-normalized window and bounds it on its own. Both normalize each value the
same way, so they differ only in the order of the sum.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.common import EPS, norm_window_slice
from repro.core.ea_pruned_dtw_np import dtw_naive
from repro.kernels.ref import lb_all_windows_ref
from repro.search import pipeline, window_stats, znorm
from repro.search.cascade import cascade_lower_bounds

RTOL = 1e-5

# name: (reference length, query length, window, queries)
CASES = {
    "l32": (700, 32, 3, 1),
    "l128": (1500, 128, 12, 1),
    "l1024": (3000, 1024, 102, 1),
    "ragged": (1037, 64, 6, 1),     # 974 windows: no multiple of 8 or 128
    "flat": (800, 32, 3, 1),
    "nan": (900, 48, 4, 1),
    "q3": (1100, 96, 9, 3),
}


def _series(n, seed):
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.normal(size=n)).astype(np.float32)


def _queries(ref, length, nq, seed):
    rng = np.random.default_rng(seed + 1)
    starts = rng.integers(0, ref.shape[0] - length, size=nq)
    noise = 0.1 * rng.normal(size=(nq, length))
    return np.stack([ref[s:s + length] for s in starts]) + noise


def _direct(case, n, length, window):
    """``cascade_lower_bounds`` called as ``pipeline.cascade`` calls it."""
    raw = _series(n, 14)
    if case == "flat":
        raw[200:300] = raw[200]
    ref = jnp.asarray(raw)
    mu, sigma = window_stats(ref, length)
    if case == "flat":
        # Windows inside the flat stretch: force the sigma clamp.
        sigma = sigma.at[200:300 - length + 1].set(0.0)
        assert float(jnp.min(sigma)) < EPS
    qn = znorm(jnp.asarray(_queries(raw, length, 1, 14), jnp.float32))
    got = jax.vmap(
        lambda q: cascade_lower_bounds(ref, q, mu, sigma, length, window)
    )(qn)
    want = jnp.stack([lb_all_windows_ref(ref, q, mu, sigma, length, window)
                      for q in qn])
    return np.asarray(got), np.asarray(want)


def _through_pipeline(case, n, length, window, nq):
    """``pipeline.cascade``'s bounds, put back into window order."""
    raw = _series(n, 15)
    if case == "nan":
        raw[300:303] = np.nan
    plan = pipeline.make_plan(length=length, window=window, backend="jax")
    prep = pipeline.prepare_ref(plan, jnp.asarray(raw))
    pq = pipeline.prepare_queries(
        plan, jnp.asarray(_queries(np.nan_to_num(raw), length, nq, 15),
                          jnp.float32))
    order, lb_sorted = map(np.asarray, pipeline.cascade(plan, prep, pq.qn))
    got = np.empty_like(lb_sorted)
    np.put_along_axis(got, order, lb_sorted, axis=1)
    want = np.stack([
        np.where(np.asarray(prep.valid), np.asarray(lb_all_windows_ref(
            prep.ref, q, prep.mu, prep.sigma, length, window)), np.inf)
        for q in pq.qn])
    if case == "nan":
        # Every window that holds one of the three NaNs is quarantined.
        assert np.isinf(got).sum() == length + 2
    return got, want


@pytest.mark.parametrize("case", list(CASES))
def test_bounds_match_the_per_window_reference(case):
    n, length, window, nq = CASES[case]
    if case in ("nan", "q3"):
        got, want = _through_pipeline(case, n, length, window, nq)
    else:
        got, want = _direct(case, n, length, window)
    assert got.shape == (nq, n - length + 1)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=RTOL)


def test_bounds_lie_below_dtw():
    n, length, window = 300, 32, 3
    ref = jnp.asarray(_series(n, 16))
    mu, sigma = window_stats(ref, length)
    qn = znorm(jnp.asarray(_queries(np.asarray(ref), length, 1, 16),
                           jnp.float32))[0]
    lbs = np.asarray(cascade_lower_bounds(ref, qn, mu, sigma, length, window))
    wins = np.asarray(norm_window_slice(
        ref, jnp.arange(n - length + 1), length, mu, sigma))
    dtw = np.array([dtw_naive(np.asarray(qn), c, window) for c in wins])
    assert np.all(lbs <= dtw * (1 + RTOL))
    assert np.mean(lbs > 0) > 0.9  # the bounds are not vacuous
