"""Multi-query subsequence search: Q queries amortized over one launch path.

The serving shape of the paper's pipeline. ``subsequence_search`` answers
one query; a serving tier answers a *workload* of queries against the same
reference. Running Q sequential searches wastes exactly what the batched
EAPrunedDTW primitive is good at: lanes. This frontend flattens the Q
queries' candidate rounds into a single ``(Q × batch)`` lane set per
dispatch and keeps one incumbent **per query**.

The machinery lives in ``search.pipeline`` (DESIGN.md §2.8): this module
validates, builds the ``SearchPlan``, and runs the shared offline core
(``pipeline._offline_search_impl`` → ``run_host_rounds`` /
``run_persistent``); the mesh closure below binds the sharded executor
(``pipeline.make_sharded_search``).

(query × candidate) lane layout
-------------------------------
Each round builds a ``(Q, batch, length)`` candidate tensor — row ``q`` is
the next best-first batch of query ``q``'s own LB-ordered candidates — and
evaluates it in one call to ``core.batch.ea_pruned_dtw_multi_batch``. On the
Pallas backend that is literally one kernel launch whose grid carries a
query-block dimension: lanes are flattened query-major (lane ``q * batch +
j`` is candidate ``j`` of query ``q``), every ``block_k`` lane tile shares
one query/envelope, and ``ub`` rides along as a per-lane VMEM vector holding
each query's incumbent broadcast over its lanes. On the ``jax`` backend the
same semantics run as a nested vmap. Either way there is exactly one
dispatch per round for the whole workload — no per-query launches and no
per-query recompilation (one trace serves every Q of the same shape).

Per-query incumbents and drop-out
---------------------------------
State is vectorized over queries: incumbent ``ub[q]``, best start
``best[q]``, and a best-first round pointer ``r[q]`` that advances only
while query ``q`` is *active* (it still has rounds left and its next batch's
smallest lower bound can beat its incumbent). The loop runs while any query
is active; a finished query drops out by having its lanes submitted with the
negative dead-lane sentinel, so the kernel abandons them on row 0 — they
cost one masked row, not a DP.

Amortized stage 1: ``window_stats`` runs once for the workload, and the LB
cascade runs as one vmapped pass over all Q queries (one fused kernel
program instead of Q sequential ones).

``ub_init`` seeds the per-query incumbents (warm starts from a cache or a
previous shard). A query whose seed is already below every candidate's
reachable distance abandons its entire round-0 batch and drops out with
``best_start == -1`` — the serving analogue of the paper's "ub from a
previous query" trick.

``rounds="persistent"`` (DESIGN.md §2.5) replaces the per-round dispatches
with ONE launch for the whole workload: every query's full best-first
candidate order is gathered once, the kernel grid keeps the query dimension
parallel, and each query's incumbent is carried in SMEM across the now
*sequential* candidate-block dimension — tightened every ``block_k`` lanes
and gating LB-pruned blocks on device. Same per-query results, O(1)
dispatches. With the default ``gather="fused"`` the sweep *addresses* the
best-first order instead of materializing a ``(Q, N, l)`` window tensor:
each block's candidates are sliced + z-normalized from the resident
reference on demand (DESIGN.md §2.10). ``warm_start`` works here too: the same prepass dispatch seeds the
sweep's SMEM incumbents and the prepass winner keeps its start when the
sweep cannot beat it (pre-refactor the knob was silently dropped).

The distributed variant (``make_distributed_multi_search``) shards the
(query, candidate-range) work items across the mesh: candidate ranges are
sharded contiguously (each device owns a slice of every query's windows, so
a device's round is Q work items — one (query, local-range) pair per query),
queries ride in the lane dimension, and the per-query incumbent *vector* is
reconciled with one vectorized ``lax.pmin`` all-reduce per round — the
multi-query generalization of ``search/distributed.py``'s scalar ``pmin``
pattern. Devices iterate in lockstep until the global continue flag
(``pmax`` over any-device-any-query-active) clears.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import guards
from repro.search.pipeline import (
    MULTI_VARIANTS,
    ROUND_DRIVERS,
    _offline_search_impl,
    make_plan,
    make_sharded_search,
)

__all__ = [
    "MULTI_VARIANTS",
    "DistMultiSearchResult",
    "MultiSearchResult",
    "make_distributed_multi_search",
    "multi_query_search",
]


class MultiSearchResult(NamedTuple):
    best_start: jax.Array  # (Q,) window start of each query's neighbour (-1: none)
    best_dist: jax.Array   # (Q,) its DTW distance (== ub_init when unbeaten)
    rounds: jax.Array      # (Q,) batch rounds each query stayed active
    lanes: jax.Array       # (Q,) candidate lanes each query submitted
    lb_pruned: jax.Array   # (Q,) candidates never evaluated thanks to LB ordering
    rows: jax.Array        # (Q,) DTW rows issued (-1: fast rounds)
    cells: jax.Array       # (Q,) admissible DTW cells (-1: fast rounds)
    quarantined: jax.Array  # windows excluded by the non-finite quarantine


class DistMultiSearchResult(NamedTuple):
    best_start: jax.Array  # (Q,)
    best_dist: jax.Array   # (Q,)
    rounds: jax.Array      # max rounds any device spent on the workload
    quarantined: jax.Array  # windows excluded by the non-finite quarantine
    #   (scalar: windows are query-independent; psum over shards == the
    #   single-device count)
    lanes: jax.Array       # (Q,) candidate windows submitted, all shards
    lb_pruned: jax.Array   # (Q,) windows never evaluated: n_win - lanes


def multi_query_search(
    ref: jax.Array,
    queries: jax.Array,
    length: int,
    window: int,
    variant: str = "eapruned",
    batch: int = 64,
    band_width: int | None = None,
    chunk: int = 4096,
    with_info: bool = False,
    backend: str | None = None,
    rows_per_step: int = 1,
    block_k: int = 8,
    row_block: int = 128,
    ub_init: jax.Array | None = None,
    warm_start: int = 0,
    rounds: str = "host",
    quarantine: bool = True,
    gather: str = "fused",
    slab_budget: int | None = None,
) -> MultiSearchResult:
    """Nearest z-normalized window of ``ref`` for each of Q queries.

    Equivalent to Q independent ``subsequence_search`` calls (same
    ``best_start`` / ``best_dist`` per query) but amortized: one
    ``window_stats`` pass, one vmapped LB cascade, and one flattened
    ``(Q × batch)``-lane EAPrunedDTW dispatch per round with a per-query
    incumbent vector (see module docstring for the lane layout).

    Args:
      ref: ``(N,)`` long reference series shared by the workload.
      queries: ``(Q, l)`` raw queries (z-normalized internally).
      length: window/query length (static); ``l == length``.
      window: Sakoe-Chiba warping window in samples (static).
      variant: ``"eapruned"`` or ``"eapruned_nolb"`` (the EA batch is the
        primitive being amortized; use ``subsequence_search`` for the
        ``full`` / ``pruned`` baselines).
      batch: candidates per query per round (static) — each round dispatches
        ``Q * batch`` lanes.
      with_info: collect per-query rows/cells pruning counters (stats
        rounds); fast rounds leave them at ``-1``.
      backend: DTW batch backend (see ``core.backend``); resolved here, in
        the un-jitted wrapper, so ``$REPRO_DTW_BACKEND`` is re-read every
        call.
      ub_init: optional per-query initial incumbents — scalar or ``(Q,)``
        (warm starts from a cache or a previous shard). A query that cannot
        beat its seed returns ``best_start == -1`` and
        ``best_dist == ub_init[q]``.
      warm_start: number of best-LB candidates per query to full-DP in a
        tiny prepass dispatch that seeds the incumbents, so no round ever
        runs with an unbounded ``ub`` (0 disables, the default). Changes
        work, not results: it helps the Pallas backend's block-level early
        exit (round-0 blocks can die early instead of running full DPs) but
        adds prepass lanes the vmap backend cannot recoup — leave it off on
        CPU. With the persistent driver the prepass bound seeds the SMEM
        incumbents (and the prepass winner keeps its start when the sweep
        cannot beat it), so ``rounds`` reports 2 dispatches.
      rounds: ``"host"`` (per-round dispatches, the default) or
        ``"persistent"`` — the whole Q-query sweep in one launch with
        per-query incumbents carried in SMEM across candidate blocks (see
        ``search.subsequence`` module docstring for the trade-offs).
        Counter-free: combine with ``with_info`` is rejected.
      quarantine: exclude windows overlapping a non-finite reference sample
        (DESIGN.md §2.6); the excluded count is reported in
        ``result.quarantined``. On (default) even for clean data — the
        prepass is one extra prefix-sum pass.

    Returns: ``MultiSearchResult`` of per-query ``(Q,)`` arrays.
    """
    if rounds not in ROUND_DRIVERS:
        raise ValueError(f"rounds {rounds!r} not in {ROUND_DRIVERS}")
    if rounds == "persistent" and with_info:
        raise ValueError(
            "rounds='persistent' is counter-free; use the host driver for "
            "with_info stats rounds"
        )
    guards.ensure_series(ref, "ref", ndim=1, min_len=length)
    guards.ensure_series(queries, "queries", ndim=2, min_len=length)
    guards.ensure_finite(queries, "queries")
    if ub_init is not None and guards.is_concrete(ub_init):
        if bool(jnp.any(jnp.isnan(jnp.asarray(ub_init)))):
            raise guards.NonFiniteInputError(
                "ub_init contains NaN (use +inf / BIG for a cold start)"
            )
    plan = make_plan(
        length=length, window=window, variant=variant, batch=batch,
        band_width=band_width, chunk=chunk, backend=backend,
        rows_per_step=rows_per_step, block_k=block_k, row_block=row_block,
        rounds=rounds, quarantine=quarantine, warm_start=warm_start,
        gather=gather, slab_budget=slab_budget,
        with_info=with_info, allowed_variants=MULTI_VARIANTS,
    )
    state, stats, n_quar = _offline_search_impl(
        ref, queries, ub_init, plan, with_info
    )
    return MultiSearchResult(
        best_start=state.best,
        best_dist=state.ub,
        rounds=stats.rounds,
        lanes=stats.lanes,
        lb_pruned=stats.lb_pruned,
        rows=stats.rows,
        cells=stats.cells,
        quarantined=n_quar,
    )


def make_distributed_multi_search(
    mesh: jax.sharding.Mesh,
    axis_names: tuple[str, ...],
    length: int,
    window: int,
    batch: int = 64,
    band_width: int | None = None,
    chunk: int = 2048,
    backend: str | None = None,
    rows_per_step: int = 1,
    block_k: int = 8,
    row_block: int = 128,
    quarantine: bool = True,
    gather: str = "fused",
    slab_budget: int | None = None,
):
    """Build a jitted distributed multi-query search fn for a mesh config.

    Returns ``search_fn(ref, queries) -> DistMultiSearchResult`` with
    per-query ``(Q,)`` results — the sharded executor of the pipeline
    (``pipeline.make_sharded_search``). Work items are (query,
    candidate-range) pairs: candidate window starts are sharded contiguously
    across the mesh axes (each device owns a range of every query's
    windows), queries are flattened into the lane dimension of the
    per-device multi-query batch, and after every round the per-query
    incumbent vector is reconciled with one vectorized ``pmin`` all-reduce.
    Devices iterate in lockstep until no device has an active (query, range)
    item left (``pmax`` continue flag); a device whose query finished early
    submits dead lanes for it, so stragglers cost masked rows, not DPs.

    ``backend`` is resolved once, here at closure-build time.

    ``quarantine`` (default on) threads ``znorm.window_finite_mask`` through
    every shard's per-query cascade: poisoned windows are condemned on the
    shard that owns them (``+inf`` LB → dead-lane sentinel, query-
    independent), counts are ``psum``-reduced into
    ``DistMultiSearchResult.quarantined``, and the sanitized reference keeps
    the shared prefix sums finite for survivors — exactly the single-device
    contract of ``multi_query_search`` (DESIGN.md §2.6/§2.7).
    """
    plan = make_plan(
        length=length, window=window, variant="eapruned", batch=batch,
        band_width=band_width, chunk=chunk, backend=backend,
        rows_per_step=rows_per_step, block_k=block_k, row_block=row_block,
        quarantine=quarantine, gather=gather, slab_budget=slab_budget,
        allowed_variants=MULTI_VARIANTS,
    )
    sharded = make_sharded_search(mesh, axis_names, plan)

    def search_fn(ref: jax.Array, queries: jax.Array) -> DistMultiSearchResult:
        best_d, best_s, rounds, n_quar, lanes, pruned = sharded(
            ref, queries
        )
        return DistMultiSearchResult(
            best_start=best_s, best_dist=best_d, rounds=rounds,
            quarantined=n_quar, lanes=lanes, lb_pruned=pruned,
        )

    return search_fn
