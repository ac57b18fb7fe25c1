"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.lower_bounds import _lb_keogh_terms, envelope
from repro.kernels.ops import dtw_ea, lb_keogh_all_windows
from repro.kernels.ref import dtw_ea_ref, dtw_exact_ref, lb_all_windows_ref
from repro.search.znorm import window_stats, znorm


def _mk(n, k, seed):
    rng = np.random.default_rng(seed)
    q = znorm(jnp.asarray(rng.normal(size=n), jnp.float32))
    c = znorm(jnp.asarray(rng.normal(size=(k, n)), jnp.float32))
    return q, c


@pytest.mark.parametrize(
    "n,k,w,block_k,row_block",
    [
        (64, 8, 8, 8, 32),
        (96, 20, 10, 8, 32),   # k not divisible by block_k -> padding
        (128, 16, 16, 4, 128),
        (50, 5, 6, 8, 16),     # n not divisible by row_block
        (32, 8, 40, 8, 32),    # window wider than series -> full DTW
    ],
)
def test_dtw_ea_kernel_sweep(n, k, w, block_k, row_block):
    q, c = _mk(n, k, seed=n + k)
    exact = np.asarray(dtw_exact_ref(q, c, w))
    for ub in (np.median(exact), exact.max() * 1.01, exact.min() * 0.9):
        got = np.asarray(
            dtw_ea(
                q, c, float(ub), window=w, block_k=block_k,
                row_block=row_block, interpret=True,
            )
        )
        ref = np.asarray(dtw_ea_ref(q, c, float(ub), window=w))
        assert np.array_equal(np.isfinite(got), np.isfinite(ref)), (got, ref)
        fin = np.isfinite(ref)
        np.testing.assert_allclose(got[fin], ref[fin], rtol=1e-5)


def test_dtw_ea_kernel_cb():
    n, k, w = 96, 16, 10
    q, c = _mk(n, k, seed=7)
    u, low = envelope(q, w)
    terms = _lb_keogh_terms(c, u, low)
    cb = jnp.flip(jnp.cumsum(jnp.flip(terms, -1), -1), -1)
    exact = np.asarray(dtw_exact_ref(q, c, w))
    ub = float(np.median(exact))
    got = np.asarray(
        dtw_ea(q, c, ub, window=w, cb=cb, block_k=8, row_block=32, interpret=True)
    )
    ref = np.asarray(dtw_ea_ref(q, c, ub, window=w, cb=cb))
    assert np.array_equal(np.isfinite(got), np.isfinite(ref))
    fin = np.isfinite(ref)
    np.testing.assert_allclose(got[fin], ref[fin], rtol=1e-5)


def test_dtw_ea_kernel_value_vs_exact():
    """Survivors must equal exact DTW, not merely match the ref impl."""
    n, k, w = 64, 12, 8
    q, c = _mk(n, k, seed=11)
    exact = np.asarray(dtw_exact_ref(q, c, w))
    got = np.asarray(
        dtw_ea(q, c, float(exact.max() * 1.01), window=w, interpret=True)
    )
    np.testing.assert_allclose(got, exact, rtol=1e-5)


@pytest.mark.parametrize("n_ref,length,w,chunk", [
    (1500, 64, 8, 256),
    (777, 32, 4, 128),    # ragged: windows not divisible by chunk
    (2048, 128, 12, 512),
])
def test_lb_kernel_sweep(n_ref, length, w, chunk):
    rng = np.random.default_rng(n_ref)
    ref = jnp.asarray(np.cumsum(rng.normal(size=n_ref)), jnp.float32)
    q = znorm(jnp.asarray(np.cumsum(rng.normal(size=length)), jnp.float32))
    mu, sg = window_stats(ref, length)
    u, low = envelope(q, w)
    qe = jnp.asarray([q[0], q[-1]], jnp.float32)
    got = np.asarray(
        lb_keogh_all_windows(
            ref, mu, sg, u, low, qe, length=length, chunk=chunk,
            interpret=True,
        )
    )
    want = np.asarray(lb_all_windows_ref(ref, q, mu, sg, length, w))
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=1e-4)


def test_lb_kernel_is_lower_bound():
    from repro.core.ea_pruned_dtw_np import dtw_naive

    rng = np.random.default_rng(9)
    n_ref, length, w = 600, 48, 6
    ref = jnp.asarray(np.cumsum(rng.normal(size=n_ref)), jnp.float32)
    q = znorm(jnp.asarray(np.cumsum(rng.normal(size=length)), jnp.float32))
    mu, sg = window_stats(ref, length)
    u, low = envelope(q, w)
    qe = jnp.asarray([q[0], q[-1]], jnp.float32)
    lbs = np.asarray(
        lb_keogh_all_windows(
            ref, mu, sg, u, low, qe, length=length, interpret=True
        )
    )
    qn = np.asarray(q)
    for s in range(0, n_ref - length + 1, 37):
        wnd = np.asarray(ref[s : s + length])
        c = (wnd - wnd.mean()) / max(wnd.std(), 1e-8)
        d = dtw_naive(qn, c, window=w)
        assert lbs[s] <= d + 1e-3, (s, lbs[s], d)
