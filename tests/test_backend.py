"""Backend dispatch + Pallas interpret-mode parity with the banded JAX path.

The contract under test: for any (window, cb, ub) setting, the Pallas kernel
(`dtw_ea`, interpret mode on CPU) and the banded-vmap JAX path make identical
abandon decisions, identical surviving values (to float32), and identical
rows/cells pruning counters — including ragged shapes where K is not a
multiple of ``block_k`` and n is not a multiple of ``row_block``.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.backend import BACKENDS, resolve_backend
from repro.core.batch import ea_pruned_dtw_batch
from repro.core.ea_pruned_dtw import ea_pruned_dtw_banded
from repro.core.lower_bounds import _lb_keogh_terms, envelope
from repro.kernels.ops import dtw_ea
from repro.search import subsequence_search
from repro.search.znorm import znorm


def _mk(n, k, seed):
    rng = np.random.default_rng(seed)
    q = znorm(jnp.asarray(np.cumsum(rng.normal(size=n)), jnp.float32))
    c = znorm(jnp.asarray(np.cumsum(rng.normal(size=(k, n)), axis=1), jnp.float32))
    return q, c


def _banded_ref(q, c, ub, w, cb=None, band_width=None):
    if cb is None:
        fn = lambda cc: ea_pruned_dtw_banded(
            q, cc, ub, window=w, band_width=band_width, with_info=True
        )
        return jax.vmap(fn)(c)
    fn = lambda cc, cbv: ea_pruned_dtw_banded(
        q, cc, ub, window=w, band_width=band_width, with_info=True, cb=cbv
    )
    return jax.vmap(fn)(c, cb)


def _assert_kernel_matches_banded(q, c, ub, w, cb=None, block_k=8, row_block=32):
    got, rows, cells = dtw_ea(
        q, c, ub, window=w, cb=cb, block_k=block_k, row_block=row_block,
        interpret=True, with_info=True,
    )
    ref, info = _banded_ref(q, c, ub, w, cb=cb)
    got, ref = np.asarray(got), np.asarray(ref)
    assert np.array_equal(np.isfinite(got), np.isfinite(ref)), (got, ref)
    fin = np.isfinite(ref)
    np.testing.assert_allclose(got[fin], ref[fin], rtol=1e-5)
    assert np.array_equal(np.asarray(rows), np.asarray(info.rows))
    assert np.array_equal(np.asarray(cells), np.asarray(info.cells))


@pytest.mark.parametrize(
    "n,k,w,block_k,row_block",
    [
        (96, 16, 10, 8, 32),    # windowed, aligned
        (100, 13, 7, 8, 32),    # K % block_k != 0 and n % row_block != 0
        (70, 9, 5, 4, 16),      # both ragged, small blocks
        (64, 8, 63, 8, 32),     # window ~ whole matrix -> full-width band
    ],
)
def test_kernel_banded_parity_windowed(n, k, w, block_k, row_block):
    q, c = _mk(n, k, seed=n * 3 + k)
    from repro.kernels.ref import dtw_exact_ref

    exact = np.asarray(dtw_exact_ref(q, c, w))
    for ub in (np.median(exact), exact.max() * 1.01):
        _assert_kernel_matches_banded(
            q, c, float(ub), w, block_k=block_k, row_block=row_block
        )


def test_kernel_banded_parity_cb_tightened():
    n, k, w = 96, 20, 10
    q, c = _mk(n, k, seed=17)
    u, low = envelope(q, w)
    terms = _lb_keogh_terms(c, u, low)
    cb = jnp.flip(jnp.cumsum(jnp.flip(terms, -1), -1), -1)
    from repro.kernels.ref import dtw_exact_ref

    exact = np.asarray(dtw_exact_ref(q, c, w))
    _assert_kernel_matches_banded(q, c, float(np.median(exact)), w, cb=cb)


def test_kernel_banded_parity_abandon_heavy():
    """A hopeless ub: every lane must abandon, and early (few rows issued)."""
    n, k, w = 128, 24, 12
    q, c = _mk(n, k, seed=23)
    got, rows, cells = dtw_ea(
        q, c, 1e-3, window=w, block_k=8, row_block=32, interpret=True,
        with_info=True,
    )
    ref, info = _banded_ref(q, c, 1e-3, w)
    assert not np.any(np.isfinite(np.asarray(got)))
    assert not np.any(np.isfinite(np.asarray(ref)))
    assert np.array_equal(np.asarray(rows), np.asarray(info.rows))
    assert np.array_equal(np.asarray(cells), np.asarray(info.cells))
    # early abandon means far fewer rows than the full DP
    assert int(np.asarray(rows).sum()) < k * n // 4


def test_batch_dispatch_backends_agree():
    n, k, w = 96, 20, 10
    q, c = _mk(n, k, seed=5)
    ub = 30.0
    d_jax = np.asarray(ea_pruned_dtw_batch(q, c, ub, window=w, backend="jax"))
    d_pal = np.asarray(
        ea_pruned_dtw_batch(q, c, ub, window=w, backend="pallas_interpret")
    )
    assert np.array_equal(np.isfinite(d_jax), np.isfinite(d_pal))
    fin = np.isfinite(d_jax)
    np.testing.assert_allclose(d_pal[fin], d_jax[fin], rtol=1e-5)


def test_resolve_backend_rules():
    assert resolve_backend("jax") == "jax"
    assert resolve_backend("pallas_interpret") == "pallas_interpret"
    on_tpu = jax.default_backend() == "tpu"
    assert resolve_backend("auto") == ("pallas" if on_tpu else "jax")
    with pytest.raises(ValueError):
        resolve_backend("mosaic")
    for b in ("jax", "pallas"):
        assert b in BACKENDS


def test_pallas_backend_raises_off_tpu():
    """``pallas`` never falls back to the interpreter in silence."""
    if jax.default_backend() == "tpu":
        assert resolve_backend("pallas") == "pallas"
        return
    with pytest.raises(ValueError, match="pallas_interpret"):
        resolve_backend("pallas")


def test_env_var_override_subprocess():
    """REPRO_DTW_BACKEND forces the backend when no argument is given."""
    code = r"""
import sys; sys.path.insert(0, "src")
from repro.core.backend import resolve_backend
print("RESOLVED", resolve_backend())
"""
    env = dict(os.environ, REPRO_DTW_BACKEND="pallas_interpret")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RESOLVED pallas_interpret" in out.stdout


@pytest.fixture(scope="module")
def search_problem():
    rng = np.random.default_rng(3)
    ref = jnp.asarray(np.cumsum(rng.normal(size=900)))
    q = jnp.asarray(np.cumsum(rng.normal(size=96)))
    return ref, q, 96, 9


def test_search_pallas_backend_matches_jax(search_problem):
    """subsequence_search end-to-end through the Pallas (interpret) backend
    finds the same neighbour as the JAX-vmap backend on the tier-1 fixture."""
    ref, q, length, w = search_problem
    r_jax = subsequence_search(
        ref, q, length=length, window=w, batch=64, backend="jax"
    )
    r_pal = subsequence_search(
        ref, q, length=length, window=w, batch=64, backend="pallas_interpret"
    )
    assert int(r_pal.best_start) == int(r_jax.best_start)
    np.testing.assert_allclose(
        float(r_pal.best_dist), float(r_jax.best_dist), rtol=1e-5
    )


def test_search_stats_round_counters_match(search_problem):
    """Stats rounds agree across backends; fast rounds leave counters at -1."""
    ref, q, length, w = search_problem
    fast = subsequence_search(ref, q, length=length, window=w, batch=64)
    assert int(fast.rows) == -1 and int(fast.cells) == -1
    s_jax = subsequence_search(
        ref, q, length=length, window=w, batch=64, backend="jax",
        with_info=True,
    )
    s_pal = subsequence_search(
        ref, q, length=length, window=w, batch=64, backend="pallas_interpret",
        with_info=True,
    )
    assert int(s_jax.rows) > 0 and int(s_jax.cells) > 0
    assert int(s_pal.rows) == int(s_jax.rows)
    assert int(s_pal.cells) == int(s_jax.cells)
    # fast and stats rounds must agree on the search result itself
    assert int(fast.best_start) == int(s_jax.best_start)


def test_search_tuning_knobs_same_answer(search_problem):
    """rows_per_step / block_k / row_block change scheduling, not results."""
    ref, q, length, w = search_problem
    base = subsequence_search(ref, q, length=length, window=w, batch=64)
    tuned_jax = subsequence_search(
        ref, q, length=length, window=w, batch=64, backend="jax",
        rows_per_step=4,
    )
    tuned_pal = subsequence_search(
        ref, q, length=length, window=w, batch=64, backend="pallas_interpret",
        block_k=4, row_block=16,
    )
    assert int(tuned_jax.best_start) == int(base.best_start)
    assert int(tuned_pal.best_start) == int(base.best_start)
    np.testing.assert_allclose(
        float(tuned_jax.best_dist), float(base.best_dist), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(tuned_pal.best_dist), float(base.best_dist), rtol=1e-5
    )
