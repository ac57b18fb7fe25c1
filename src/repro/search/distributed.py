"""Distributed subsequence search: shard candidates, share the upper bound.

The multi-device mapping of the paper's technique (DESIGN.md §2.4):

  * candidate window starts are sharded contiguously across the mesh axes,
  * the reference series is replicated (a few MB — broadcast once),
  * every device runs its own LB cascade + best-first batched EAPrunedDTW,
  * after every round the incumbent ``ub`` is shared with ``lax.pmin`` —
    the distributed analogue of the UCR suite's upper-bound tightening. A
    tighter global ub makes *every* device abandon earlier, so sharing is
    super-linear in value,
  * devices iterate in lockstep (collectives must stay aligned); a device
    that exhausts its useful candidates keeps issuing no-op rounds until the
    global continue-flag (``pmax``) clears. This is also the straggler story:
    work per round is bounded and uniform, so a slow device delays at most
    one round of its peers.

This module is the *scalar* (single-query) frontend of the mesh program
owned by ``search.pipeline.make_sharded_search`` (DESIGN.md §2.8): the SPMD
while_loop, the sharded quarantine accounting, and the lexicographic
``pmin`` reconcile live there, shared with ``make_distributed_multi_search``
and the ``ShardedExecutor`` range seam. Built on ``shard_map`` so the same
code lowers for the 1-device CPU test, the 256-chip pod, and the 512-chip
multi-pod mesh.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.search.pipeline import make_plan, make_sharded_search


class DistSearchResult(NamedTuple):
    best_start: jax.Array
    best_dist: jax.Array
    rounds: jax.Array
    quarantined: jax.Array  # windows excluded by the non-finite quarantine
    lanes: jax.Array      # candidate windows submitted, summed over shards
    lb_pruned: jax.Array  # windows never evaluated: n_win - lanes


def make_distributed_search(
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
):
    """Build a jitted distributed search fn for a given mesh/shape config.

    Returns ``search_fn(ref, query) -> DistSearchResult``. ``ref`` must have
    static length; the number of windows is padded to the mesh size. The
    search runs as the Q=1 case of the pipeline's multi-query mesh program
    — one query lane, the same per-round ``pmin`` incumbent sharing.

    ``backend`` / ``rows_per_step`` / ``block_k`` / ``row_block`` select and
    tune the per-device DTW batch implementation exactly as in
    ``core.batch.ea_pruned_dtw_batch`` — every device runs the same backend.

    ``quarantine`` (default on) threads the non-finite window mask through
    every shard's cascade (DESIGN.md §2.6/§2.7): the mask is computed once
    on the replicated raw reference, sharded alongside the candidate starts,
    and poisoned windows ride each shard's rounds as ``+inf``-LB dead lanes
    — the same sentinel machinery as the single-device drivers, no kernel
    change. Per-shard exclusion counts are ``psum``-reduced into
    ``DistSearchResult.quarantined``, which therefore equals the
    single-device ``subsequence_search(...).quarantined`` exactly.
    """
    plan = make_plan(
        length=length, window=window, variant="eapruned", batch=batch,
        band_width=band_width, chunk=chunk, backend=backend,
        rows_per_step=rows_per_step, block_k=block_k, row_block=row_block,
        quarantine=quarantine,
    )
    sharded = make_sharded_search(mesh, axis_names, plan)

    def search_fn(ref: jax.Array, query: jax.Array) -> DistSearchResult:
        best_d, best_s, rounds, n_quar, lanes, pruned = sharded(
            jnp.asarray(ref), jnp.asarray(query)[None]
        )
        return DistSearchResult(
            best_start=best_s[0], best_dist=best_d[0], rounds=rounds,
            quarantined=n_quar, lanes=lanes[0], lb_pruned=pruned[0],
        )

    return search_fn
