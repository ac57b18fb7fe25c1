"""Fused in-kernel gather + z-normalization parity (DESIGN.md §2.10).

The tentpole's acceptance gate: retiring the O(K·l) candidate slab must not
move a single result. ``gather="fused"`` (candidates sliced + normalized
from the resident reference inside the DTW stage) and ``gather="slab"``
(the pre-gathered baseline) must produce identical ``(best_start,
best_dist)`` incumbents and identical §2.6 quarantine counts, on both the
``jax`` and ``pallas_interpret`` backends, across the awkward cases:
ragged final candidate blocks, flat (sigma == 0) windows, quarantined
lanes, and warm-started incumbents.

Also pinned here:
  * the slab-budget regression — a persistent sweep completes under a
    ``slab_budget`` that the O(K·l) slab form cannot satisfy (it raises at
    trace time instead of allocating), and its results equal host rounds;
  * the fused kernels' HBM reference — windows fetched by per-lane DMA
    equal the slab kernels' pre-gathered windows bit for bit;
  * the golden pipeline scenario's slab arm — the frontends' ``"slab"``
    comparison mode still matches the fused default they now run by.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import guards
from repro.core.batch import (
    ea_pruned_dtw_multi_batch,
    ea_pruned_dtw_multi_batch_fused,
    ea_pruned_dtw_persistent,
    ea_pruned_dtw_persistent_fused,
)
from repro.core.common import BIG, DEAD_LANE_UB, norm_window_slice
from repro.core.lower_bounds import envelope
from repro.search import multi_query_search, subsequence_search
from repro.search.pipeline import make_plan
from repro.search.znorm import clamp_sigma, gather_norm_windows, window_stats

BACKENDS = ("jax", "pallas_interpret")
N_REF, LENGTH, WINDOW = 420, 48, 5


def _series(flat=True, nan_at=None):
    rng = np.random.default_rng(7)
    ref = np.cumsum(rng.normal(size=N_REF)).astype(np.float32)
    if flat:
        ref[100:170] = ref[100]  # sigma == 0 for a run of windows
    if nan_at is not None:
        ref[nan_at] = np.nan
    queries = np.cumsum(
        rng.normal(size=(2, LENGTH)), axis=1
    ).astype(np.float32)
    return jnp.asarray(ref), jnp.asarray(queries)


def _znorm(q):
    mu = q.mean(axis=-1, keepdims=True)
    sd = np.maximum(q.std(axis=-1, keepdims=True), 1e-8)
    return jnp.asarray((q - mu) / sd)


def test_norm_window_slice_matches_gather():
    """The fused slice helper is bit-identical to the slab gather."""
    ref, _ = _series()
    mu, sigma = window_stats(ref, LENGTH)
    starts = jnp.asarray([0, 17, 99, 120, N_REF - LENGTH], jnp.int32)
    a = norm_window_slice(ref, starts, LENGTH, mu, sigma)
    b = gather_norm_windows(ref, starts, LENGTH, mu, sigma)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("use_cb", (False, True))
def test_multi_batch_fused_parity(backend, use_cb):
    """Round primitive: fused == slab, with dead lanes and flat windows.

    K = 11 lanes against block_k = 4 exercises the ragged final block on
    the Pallas grid; lanes 3/7 ride dead (the sentinel contract) and lanes
    over the flat segment hit the clamp_sigma path.
    """
    ref, queries = _series()
    qn = _znorm(np.asarray(queries))
    mu, sigma = window_stats(ref, LENGTH)
    starts = jnp.asarray(
        [[0, 50, 110, 130, 200, 260, 300, 310, 330, 350, 372]] * 2,
        jnp.int32,
    )
    ub = jnp.full((2, 11), BIG, jnp.float32)
    ub = ub.at[:, 3].set(DEAD_LANE_UB).at[1, 7].set(DEAD_LANE_UB)
    env = None
    if use_cb:
        u, low = jax.vmap(envelope, in_axes=(0, None))(qn, WINDOW)
        env = (u, low)

    d_fused = ea_pruned_dtw_multi_batch_fused(
        qn, ref, starts, ub, window=WINDOW, mu=mu, sigma=sigma,
        envelopes=env, backend=backend, block_k=4,
    )
    cand = jax.vmap(
        lambda s: gather_norm_windows(ref, s, LENGTH, mu, sigma)
    )(starts)
    cb = None
    if use_cb:
        from repro.core.lower_bounds import cascade_keogh_cumulative

        cb = jax.vmap(
            lambda c, uu, ll: jax.vmap(
                lambda cc: cascade_keogh_cumulative(cc, uu, ll)
            )(c)
        )(cand, env[0], env[1])
    d_slab = ea_pruned_dtw_multi_batch(
        qn, cand, ub, window=WINDOW, cb=cb, backend=backend, block_k=4,
    )
    assert np.array_equal(np.asarray(d_fused), np.asarray(d_slab))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("use_cb", (False, True))
def test_persistent_fused_parity(backend, use_cb):
    """Persistent sweep: fused == slab with a ragged, partly dead order."""
    ref, queries = _series()
    qn = _znorm(np.asarray(queries))
    mu, sigma = window_stats(ref, LENGTH)
    # ascending finite lbs, then a +inf (dead) tail; 10 lanes vs block_k=4
    lb = jnp.asarray(
        [[0.1, 0.5, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, np.inf, np.inf]] * 2,
        jnp.float32,
    )
    starts = jnp.asarray(
        [[30, 110, 150, 0, 210, 260, 310, 350, 0, 0]] * 2, jnp.int32
    )
    ub0 = jnp.asarray([BIG, 40.0], jnp.float32)  # one warm incumbent
    env = None
    if use_cb:
        u, low = jax.vmap(envelope, in_axes=(0, None))(qn, WINDOW)
        env = (u, low)

    out_f = ea_pruned_dtw_persistent_fused(
        qn, ref, lb, starts, ub0, window=WINDOW, mu=mu, sigma=sigma,
        envelopes=env, backend=backend, block_k=4,
    )
    cand = jax.vmap(
        lambda s: gather_norm_windows(ref, s, LENGTH, mu, sigma)
    )(starts)
    out_s = ea_pruned_dtw_persistent(
        qn, cand, lb, starts, ub0, window=WINDOW,
        envelopes=env, backend=backend, block_k=4,
    )
    for a, b in zip(out_f, out_s):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("rounds", ("host", "persistent"))
def test_frontend_parity_fused_vs_slab(backend, rounds):
    """multi_query_search: fused == slab with quarantine + warm starts."""
    ref, queries = _series(nan_at=210)  # condemn a window span (§2.6)
    kw = dict(
        length=LENGTH, window=WINDOW, batch=32, backend=backend,
        rounds=rounds, warm_start=2,
    )
    a = multi_query_search(ref, queries, gather="fused", **kw)
    b = multi_query_search(ref, queries, gather="slab", **kw)
    assert np.array_equal(np.asarray(a.best_start), np.asarray(b.best_start))
    assert np.array_equal(np.asarray(a.best_dist), np.asarray(b.best_dist))
    assert int(a.quarantined) == int(b.quarantined) == LENGTH


@pytest.mark.parametrize("backend", BACKENDS)
def test_slab_budget_persistent_regression(backend):
    """Fused persistent completes where the O(K·l) slab busts the budget.

    The budget admits the O(N) reference but not the O(N·l) candidate
    slab: the slab arm must refuse at trace time (no allocation), while the
    fused sweep runs to completion under the same plan knobs — with
    results identical to host rounds, so the memory win costs nothing.
    """
    ref, queries = _series()
    n_win = N_REF - LENGTH + 1
    budget = 8 * n_win  # floor(N·l·4 / ~24): far below any window slab
    assert n_win * LENGTH * 4 > budget
    kw = dict(
        length=LENGTH, window=WINDOW, batch=32, backend=backend,
        slab_budget=budget,
    )
    with pytest.raises(guards.SearchInputError):
        multi_query_search(
            ref, queries, gather="slab", rounds="persistent", **kw
        )
    pers = multi_query_search(
        ref, queries, gather="fused", rounds="persistent", **kw
    )
    host = multi_query_search(ref, queries, gather="fused", rounds="host", **kw)
    assert np.array_equal(
        np.asarray(pers.best_start), np.asarray(host.best_start)
    )
    np.testing.assert_allclose(
        np.asarray(pers.best_dist), np.asarray(host.best_dist), rtol=1e-6
    )


def test_hbm_reference_kernels_match_slab_kernels():
    """Fused kernels DMA windows from the HBM reference; the slab kernels
    take them pre-gathered. Distances and winners are identical."""
    from repro.kernels import ops

    ref, queries = _series()
    qn = _znorm(np.asarray(queries))
    mu, sigma = window_stats(ref, LENGTH)
    starts = jnp.asarray([[0, 60, 120, 180, 240, 300, 350]] * 2, jnp.int32)
    mu_l = mu[starts]                      # ops layer takes per-lane stats
    sg_l = clamp_sigma(sigma)[starts]      # pre-clamped by contract
    cand = jnp.stack([
        gather_norm_windows(ref, s, LENGTH, mu, sigma) for s in starts
    ])
    ub = jnp.full((2, 7), BIG, jnp.float32)
    kw = dict(window=WINDOW, block_k=4, interpret=True)
    d_fused = ops.dtw_ea_multi_fused(
        qn, ref, starts, mu_l, sg_l, ub, length=LENGTH, **kw
    )
    d_slab = ops.dtw_ea_multi(qn, cand, ub, **kw)
    assert np.array_equal(np.asarray(d_fused), np.asarray(d_slab))

    lb = jnp.asarray([[0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7]] * 2, jnp.float32)
    ub0 = jnp.full((2,), BIG, jnp.float32)
    p_fused = ops.dtw_ea_persistent_fused(
        qn, ref, lb, starts, mu_l, sg_l, ub0, length=LENGTH, **kw
    )
    p_slab = ops.dtw_ea_persistent(qn, cand, lb, starts, ub0, **kw)
    for a, b in zip(p_fused, p_slab):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_golden_scenario_slab_arm_matches_fused():
    """The pipeline golden scenario's slab arms equal the fused default.

    ``test_pipeline_parity`` pins all five frontends on the (now fused)
    default; this cross-check pins the retired slab form against the same
    golden incumbents for the frontends that expose the knob.
    """
    import test_pipeline_parity as golden

    ref, queries = golden._scenario()
    g_starts, g_dists, g_quar = golden._golden("jax")

    res = multi_query_search(
        ref, queries, length=golden.LENGTH, window=golden.WINDOW, batch=64,
        backend="jax", gather="slab",
    )
    assert np.array_equal(np.asarray(res.best_start, np.int64), g_starts)
    np.testing.assert_allclose(
        np.asarray(res.best_dist, np.float64), g_dists,
        rtol=golden.DIST_RTOL,
    )
    assert int(res.quarantined) == g_quar

    one = subsequence_search(
        ref, queries[0], length=golden.LENGTH, window=golden.WINDOW,
        batch=64, backend="jax", gather="slab",
    )
    assert int(one.best_start) == int(g_starts[0])


def test_fused_is_default_and_validated():
    plan = make_plan(length=LENGTH, window=WINDOW)
    assert plan.gather == "fused"
    assert plan.slab_budget is None
    with pytest.raises(guards.SearchInputError):
        make_plan(length=LENGTH, window=WINDOW, gather="eager")
    with pytest.raises(guards.SearchInputError):
        make_plan(length=LENGTH, window=WINDOW, slab_budget=0)
