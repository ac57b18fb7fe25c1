"""The one staged search pipeline: SearchPlan → prepare → cascade → execute.

The paper's pipeline is fixed (Herrmann & Webb 2020): z-norm window stats,
LB cascade, then EAPrunedDTW lanes folding into a shared incumbent. This
repo used to implement that skeleton five times — once per frontend
(``subsequence``, ``multi``, ``streaming``, ``distributed``,
``resilient``), each with its own quarantine prepass, cascade, round loop
and incumbent fold. This module is now the single implementation; the
frontends are thin wrappers that build a :class:`SearchPlan` and pick an
executor.

Stages
------
::

    SearchPlan (make_plan: resolved knobs, hashable → a jit static)
        │
        ├─ prepare_ref      window stats + §2.6 quarantine mask/sanitize
        ├─ prepare_queries  z-norm + LB_Keogh envelopes (per standing query)
        ├─ cascade          the one LB gate: LB_Kim/LB_Keogh per window,
        │                   +inf for quarantined/invalid, best-first argsort
        └─ execute          one of three range executors:
             host rounds        best-first (Q × batch)-lane dispatches in a
                                lax.while_loop (run_host_rounds)
             persistent sweep   the whole order in ONE launch, incumbent in
                                SMEM across candidate blocks (run_persistent)
             sharded            shard_map over candidate ranges, per-round
                                vectorized lax.pmin incumbent reconcile
                                (make_sharded_search / ShardedExecutor)

Each stage traces under a ``jax.named_scope`` — ``dtw.prepare``,
``dtw.cascade`` (the argsort in ``dtw.cascade/dtw.sort``), ``dtw.execute``
and, for the mesh collectives inside it, ``dtw.reconcile`` — so every
frontend's compiled ops name their stage in their HLO ``op_name``.

Incumbent state (``ub``/``best``, strict-improvement fold, dead-lane
sentinel) and quarantine counters live in ``search.incumbents``.

Executor seam
-------------
:class:`Executor` (``run_range(plan, state, lo, hi) -> RangeResult``) is the
unit the fault-tolerant layer schedules: ``resilient_search`` retries,
reassigns and coverage-accounts *ranges*, never caring which executor runs
them. Window starts ``[lo, hi)`` of the bound reference are searched
against the carried incumbents; results come back in global window
coordinates. :class:`HedgedExecutor` composes on the same seam: it wraps N
executors behind one ``run_range`` (and ``run_ingest``, for streaming
executors), races a straggling attempt on the next-healthiest wrapped
executor, and merges duplicate completions through the strict-improvement
fold — provably idempotent, see ``incumbents.merge_states`` and
DESIGN.md §2.9.

Frontend ↔ executor binding (public signatures unchanged):

  * ``subsequence_search``  — Q=1 of the multi host/persistent core for the
    univariate EA variants; the ``full``/``pruned`` baselines and
    multivariate queries run the dedicated single-query core here (their
    kernels take a scalar threshold and no (Q, K) lane form exists).
  * ``multi_query_search``  — host rounds or persistent sweep.
  * ``ingest_chunk``        — host rounds with a ``valid`` window mask and a
    stream-coordinate offset (the streaming wrappers own buffering only).
  * ``make_distributed_search`` / ``make_distributed_multi_search`` — the
    sharded executor (scalar search is Q=1 of the multi program).
  * ``resilient_search``    — a host-rounds executor per work range.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Protocol

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import guards
from repro.core.backend import resolve_backend
from repro.core.batch import (
    block_sweep,
    ea_pruned_dtw_batch,
    ea_pruned_dtw_multi_batch,
    ea_pruned_dtw_multi_batch_fused,
    ea_pruned_dtw_persistent,
    ea_pruned_dtw_persistent_fused,
)
from repro.core.common import (
    BIG,
    DEAD_LANE_UB,
    norm_window_slice,
    pad_lanes_to_blocks,
)
from repro.core.dtw import dtw
from repro.core.lower_bounds import (
    cascade_keogh_cumulative,
    envelope,
    lb_keogh,
    lb_kim_fl,
)
from repro.core.pruned_dtw import pruned_dtw
from repro.distributed.fault_tolerance import (
    GUARD_ERRORS,
    TRANSIENT,
    StragglerMonitor,
    WorkerHealth,
    hedge_race,
)
from repro.search.cascade import cascade_lower_bounds
from repro.search.incumbents import (
    IncumbentState,
    fold_min,
    initial_state,
    merge_states,
)
from repro.search.znorm import (
    gather_norm_windows,
    sanitize_series,
    window_finite_mask,
    window_stats,
    znorm,
)

VARIANTS = ("full", "pruned", "eapruned", "eapruned_nolb")
MULTI_VARIANTS = ("eapruned", "eapruned_nolb")
ROUND_DRIVERS = ("host", "persistent")
GATHER_MODES = ("fused", "slab")


def _stage(name: str):
    """Trace the decorated stage under ``jax.named_scope(name)``: its ops
    carry ``name`` in their ``op_name`` (``jit(f)/dtw.cascade/while/...``).
    Metadata only; the compiled program is unchanged."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# ---------------------------------------------------------------------------
# SearchPlan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SearchPlan:
    """Resolved, validated search knobs — hashable, so a jit static arg.

    Frontends build one per call via :func:`make_plan` (the single
    validation/resolution chokepoint: ``backend`` is always a *concrete*
    backend name here, never ``None``/``"auto"``), then hand it to the
    jitted cores where it replaces the dozen positional knob arguments the
    pre-refactor impls threaded through every layer.
    """
    length: int
    window: int
    variant: str = "eapruned"
    batch: int = 64
    band_width: int | None = None
    chunk: int = 4096         # local_cascade's gathered-window block
    backend: str = "jax"
    rows_per_step: int = 1
    block_k: int = 8
    row_block: int = 128
    rounds: str = "host"
    quarantine: bool = True
    warm_start: int = 0
    # Candidate materialization (DESIGN.md §2.10): "fused" (default) slices
    # + z-normalizes windows inside the kernel / round body from the O(N)
    # reference and stats tables; "slab" pre-gathers the O(K·l) normalized
    # window matrix on the host (the retired baseline, kept as the
    # comparison arm and for the full/pruned baseline cores, which have no
    # fused form). Results are identical (bit-for-bit on jax; to the
    # documented O(1)-ulp cb reformulation on the Pallas round path).
    gather: str = "fused"
    # Optional byte ceiling for any host-side candidate slab. "slab" paths
    # that would materialize more than this raise SearchInputError at trace
    # time; fused paths never build one, so they are exempt — the knob pins
    # the "persistent sweep too big to slab" regime in tests/benches.
    slab_budget: int | None = None

    @property
    def use_lb(self) -> bool:
        return self.variant != "eapruned_nolb"

    @property
    def use_cb(self) -> bool:
        return self.variant == "eapruned"

    def knobs(self) -> dict:
        """The batch-primitive keyword block (``core.batch`` tuning)."""
        return dict(
            rows_per_step=self.rows_per_step, backend=self.backend,
            block_k=self.block_k, row_block=self.row_block,
        )


def make_plan(
    *,
    length: int,
    window: int,
    variant: str = "eapruned",
    batch: int = 64,
    band_width: int | None = None,
    chunk: int = 4096,
    backend: str | None = None,
    rows_per_step: int = 1,
    block_k: int = 8,
    row_block: int = 128,
    rounds: str = "host",
    quarantine: bool = True,
    warm_start: int = 0,
    gather: str = "fused",
    slab_budget: int | None = None,
    with_info: bool = False,
    allowed_variants: tuple[str, ...] = VARIANTS,
) -> SearchPlan:
    """Validate knobs and resolve the backend into a :class:`SearchPlan`.

    Called from every un-jitted frontend wrapper, so ``$REPRO_DTW_BACKEND``
    is re-read on every call and rides into the jitted cores as a concrete
    static. Raises the ``core.guards`` taxonomy on bad knobs, matching the
    pre-refactor per-frontend checks.
    """
    if variant not in allowed_variants:
        raise guards.SearchInputError(
            f"variant {variant!r} not in {allowed_variants}"
        )
    if rounds not in ROUND_DRIVERS:
        raise ValueError(f"rounds {rounds!r} not in {ROUND_DRIVERS}")
    if gather not in GATHER_MODES:
        raise guards.SearchInputError(
            f"gather {gather!r} not in {GATHER_MODES}"
        )
    if slab_budget is not None and int(slab_budget) <= 0:
        raise guards.SearchInputError("slab_budget must be positive bytes")
    if rounds == "persistent" and with_info:
        raise ValueError(
            "rounds='persistent' is counter-free; use the host driver for "
            "with_info stats rounds"
        )
    guards.ensure_knobs(
        length=length, window=window, batch=batch, band_width=band_width,
        block_k=block_k, row_block=row_block, rows_per_step=rows_per_step,
    )
    return SearchPlan(
        length=int(length), window=int(window), variant=variant,
        batch=int(batch), band_width=band_width, chunk=int(chunk),
        backend=resolve_backend(backend), rows_per_step=int(rows_per_step),
        block_k=int(block_k), row_block=int(row_block), rounds=rounds,
        quarantine=bool(quarantine), warm_start=int(warm_start),
        gather=gather,
        slab_budget=None if slab_budget is None else int(slab_budget),
    )


def _ensure_slab_budget(plan: SearchPlan, n_lanes: int, what: str) -> None:
    """Trace-time guard: a host-side slab must fit ``plan.slab_budget``.

    ``n_lanes`` is static (shape-derived), so the check runs while tracing
    and raises before any O(K·l) allocation happens. Fused paths never call
    this — not materializing the slab is the point.
    """
    if plan.slab_budget is None:
        return
    need = int(n_lanes) * int(plan.length) * 4  # float32 windows
    if need > plan.slab_budget:
        raise guards.SearchInputError(
            f"{what}: gather='slab' would materialize {need} bytes of "
            f"candidate windows ({n_lanes} lanes x {plan.length} samples) "
            f"but slab_budget={plan.slab_budget}; use gather='fused' or "
            "raise the budget"
        )


# ---------------------------------------------------------------------------
# prepare — window stats + §2.6 quarantine + query envelopes
# ---------------------------------------------------------------------------

class PreparedRef(NamedTuple):
    """Reference-side stage-1 products shared by every executor."""
    ref: jax.Array           # sanitized series (raw when quarantine off)
    mu: jax.Array            # (n_win,) per-window means
    sigma: jax.Array         # (n_win,) per-window stds (clamped)
    valid: jax.Array | None  # (n_win,) surviving-window mask; None = all
    n_quar: jax.Array        # scalar int32: windows newly quarantined here


class PreparedQueries(NamedTuple):
    """Query-side stage-1 products (fixed for a workload / stream)."""
    qn: jax.Array   # (Q, l) z-normalized queries
    u: jax.Array    # (Q, l) upper LB_Keogh envelope
    low: jax.Array  # (Q, l) lower LB_Keogh envelope


@_stage("dtw.prepare")
def prepare_ref(plan: SearchPlan, ref, valid=None) -> PreparedRef:
    """Window stats + the one §2.6 quarantine prepass.

    ``valid`` optionally masks which window starts exist at all (the
    fixed-shape streaming buffers); quarantined windows are folded into it
    and only *previously-valid* windows count toward ``n_quar``. The series
    is zero-filled at the bad samples afterwards so the shared prefix sums
    stay finite for the surviving windows.
    """
    ref = jnp.asarray(ref)
    if plan.quarantine:
        finite_ok = window_finite_mask(ref, plan.length)
        if valid is None:
            n_quar = jnp.sum(~finite_ok).astype(jnp.int32)
            valid = finite_ok
        else:
            n_quar = jnp.sum(
                jnp.logical_and(valid, ~finite_ok)
            ).astype(jnp.int32)
            valid = jnp.logical_and(valid, finite_ok)
        ref = sanitize_series(ref)
    else:
        n_quar = jnp.asarray(0, jnp.int32)
    mu, sigma = window_stats(ref, plan.length)
    return PreparedRef(ref=ref, mu=mu, sigma=sigma, valid=valid, n_quar=n_quar)


@_stage("dtw.prepare")
def prepare_queries(plan: SearchPlan, queries) -> PreparedQueries:
    """Z-normalize the workload's queries and build their envelopes."""
    qn = znorm(jnp.asarray(queries)[:, : plan.length])
    u, low = jax.vmap(envelope, in_axes=(0, None))(qn, plan.window)
    return PreparedQueries(qn=qn, u=u, low=low)


# ---------------------------------------------------------------------------
# cascade — the one LB gate
# ---------------------------------------------------------------------------

def _sort_bounds(lbs) -> tuple[jax.Array, jax.Array]:
    """Best-first order of each row of ``lbs`` and the bounds in it."""
    with jax.named_scope("dtw.sort"):
        order = jnp.argsort(lbs, axis=1)
        return order, jnp.take_along_axis(lbs, order, axis=1)


@_stage("dtw.cascade")
def cascade(plan: SearchPlan, prep: PreparedRef, qn) -> tuple[jax.Array, jax.Array]:
    """Per-query lower bounds → best-first candidate order.

    Returns ``(order, lb_sorted)``, both ``(Q, n_win)``. Quarantined and
    invalid windows carry ``+inf`` lower bounds: the argsort pushes them
    behind every live candidate, the cascade stop never reaches them, and
    any that ride in a partially-live round are dead lanes (the same
    machinery as round padding, DESIGN.md §2.6). The no-cascade variant
    keeps natural scan order among surviving windows via a stable argsort
    of the 0/+inf mask.
    """
    n_win = prep.mu.shape[0]
    nq = qn.shape[0]
    if plan.use_lb:
        lbs = jax.vmap(
            lambda q: cascade_lower_bounds(
                prep.ref, q, prep.mu, prep.sigma, plan.length, plan.window
            )
        )(qn)                                          # (Q, n_win)
        if prep.valid is not None:
            lbs = jnp.where(prep.valid[None, :], lbs, jnp.inf)
        return _sort_bounds(lbs)
    if prep.valid is not None:
        lbs = jnp.broadcast_to(
            jnp.where(prep.valid, 0.0, jnp.inf).astype(qn.dtype),
            (nq, n_win),
        )
        return _sort_bounds(lbs)
    order = jnp.broadcast_to(jnp.arange(n_win), (nq, n_win))
    return order, jnp.zeros((nq, n_win), qn.dtype)


@_stage("dtw.cascade")
def local_cascade(
    plan: SearchPlan, prep: PreparedRef, qn, starts, valid
) -> jax.Array:
    """Per-shard lower bounds for an explicit (gathered) start set.

    The sharded executor's form of the gate: each device owns ``starts``
    (a slice of every query's windows) rather than the dense ``[0, n_win)``
    range, so the bounds are computed per gathered window, chunked through
    ``lax.map`` to bound materialization. Invalid/quarantined starts come
    back ``+inf`` exactly as in :func:`cascade`.
    """
    def one_query(query_n):
        u, low = envelope(query_n, plan.window)
        n_local = starts.shape[0]
        n_chunks = -(-n_local // plan.chunk)
        pad = n_chunks * plan.chunk - n_local
        starts_p = jnp.concatenate([starts, jnp.zeros((pad,), starts.dtype)])
        valid_p = jnp.concatenate([valid, jnp.zeros((pad,), bool)])

        def one(i):
            s = jax.lax.dynamic_slice(starts_p, (i * plan.chunk,), (plan.chunk,))
            v = jax.lax.dynamic_slice(valid_p, (i * plan.chunk,), (plan.chunk,))
            cand = norm_window_slice(
                prep.ref, s, plan.length, prep.mu, prep.sigma
            )
            lb = jnp.maximum(lb_kim_fl(query_n, cand), lb_keogh(cand, u, low))
            return jnp.where(v, lb, jnp.inf)

        lbs = jax.lax.map(one, jnp.arange(n_chunks)).reshape(-1)
        return lbs[:n_local]

    return jax.vmap(one_query)(qn)                     # (Q, n_local)


# ---------------------------------------------------------------------------
# host-rounds executor core
# ---------------------------------------------------------------------------

class SearchStats(NamedTuple):
    """Per-query work accounting of one execution."""
    rounds: jax.Array     # (Q,) batch rounds (persistent: dispatches)
    lanes: jax.Array      # (Q,) candidate lanes submitted
    lb_pruned: jax.Array  # (Q,) candidates never evaluated (LB ordering)
    rows: jax.Array       # (Q,) DTW rows issued (-1: fast rounds)
    cells: jax.Array      # (Q,) admissible DTW cells (-1: fast rounds)


def _round_slicers(batch: int):
    """Vmapped per-query round slicing, shared by both round drivers.

    Returns ``(slice_round, peek_lb)``: ``slice_round(rows, ptrs)`` pulls
    each query's current ``batch``-wide round from its padded row,
    ``peek_lb(rows, ptrs)`` reads the head (smallest) lower bound of that
    round.
    """
    slice_round = jax.vmap(
        lambda row, r: jax.lax.dynamic_slice(row, (r * batch,), (batch,)),
        in_axes=(0, 0),
    )
    peek_lb = jax.vmap(
        lambda row, r: jax.lax.dynamic_slice(row, (r * batch,), (1,))[0],
        in_axes=(0, 0),
    )
    return slice_round, peek_lb


def _dtw_round_fused(
    plan: SearchPlan, prep: PreparedRef, pq, starts, ub_lanes, *,
    use_cb: bool, with_info: bool,
):
    """One fused-gather EAPrunedDTW round over ``(Q, K)`` lane starts.

    Candidates are sliced and z-normalized from ``prep.ref`` inside the
    batch primitive (jax) or the Pallas kernel — no O(Q·K·l) slab is built
    host-side. Returns ``(d, info_or_None)``.
    """
    env = (pq.u, pq.low) if use_cb else None
    out = ea_pruned_dtw_multi_batch_fused(
        pq.qn, prep.ref, starts, ub_lanes, window=plan.window,
        mu=prep.mu, sigma=prep.sigma, envelopes=env,
        band_width=plan.band_width, with_info=with_info, **plan.knobs(),
    )
    if with_info:
        return out
    return out, None


def warm_prepass(
    plan: SearchPlan,
    prep: PreparedRef,
    pq: PreparedQueries,
    order,
    lb_sorted,
    state0: IncumbentState,
    with_info: bool = False,
    offset=0,
):
    """Full-DP each query's best-LB candidates to seed the incumbents.

    One tiny ``(Q × pre)``-lane dispatch (``pre = min(warm_start, batch)``)
    so no subsequent round or sweep ever runs with an unbounded ``ub``. The
    main pass re-encounters these candidates with ``d == ub``;
    strict-improvement keeps the prepass incumbent, so results are
    unchanged — both for the host round loop and for the persistent sweep
    (whose result is folded against this state by the caller).

    Returns ``(state, pre, rows_pre, cells_pre)``.
    """
    nq, n_win = order.shape
    pre = min(int(plan.warm_start), plan.batch)
    if pre <= 0:
        z = jnp.zeros((nq,), jnp.int32)
        return state0, 0, z, z
    if n_win < pre:
        order = jnp.concatenate(
            [order, jnp.zeros((nq, pre - n_win), order.dtype)], axis=1
        )
        lb_sorted = jnp.concatenate(
            [lb_sorted, jnp.full((nq, pre - n_win), jnp.inf, lb_sorted.dtype)],
            axis=1,
        )
    pre_starts = order[:, :pre]
    pre_lbs = lb_sorted[:, :pre]
    ub_pre = jnp.where(
        jnp.logical_and(jnp.isfinite(pre_lbs), pre_lbs < state0.ub[:, None]),
        jnp.broadcast_to(state0.ub[:, None], (nq, pre)),
        DEAD_LANE_UB,
    )
    if plan.gather == "fused":
        d0, info0 = _dtw_round_fused(
            plan, prep, pq, pre_starts, ub_pre,
            use_cb=False, with_info=with_info,
        )
    else:
        _ensure_slab_budget(plan, nq * pre, "warm_prepass")
        cand0 = jax.vmap(
            lambda s: gather_norm_windows(
                prep.ref, s, plan.length, prep.mu, prep.sigma
            )
        )(pre_starts)
        if with_info:
            d0, info0 = ea_pruned_dtw_multi_batch(
                pq.qn, cand0, ub_pre, window=plan.window,
                band_width=plan.band_width, with_info=True, **plan.knobs(),
            )
        else:
            d0 = ea_pruned_dtw_multi_batch(
                pq.qn, cand0, ub_pre, window=plan.window,
                band_width=plan.band_width, **plan.knobs(),
            )
            info0 = None
    if with_info:
        rows_pre = jnp.sum(info0.rows, axis=1, dtype=jnp.int32)
        cells_pre = jnp.sum(info0.cells, axis=1, dtype=jnp.int32)
    else:
        rows_pre = cells_pre = jnp.zeros((nq,), jnp.int32)
    d0 = jnp.where(jnp.isfinite(pre_lbs), d0, jnp.inf)
    state, _ = fold_min(state0, pre_starts, d0, offset=offset)
    return state, pre, rows_pre, cells_pre


@_stage("dtw.execute")
def run_host_rounds(
    plan: SearchPlan,
    prep: PreparedRef,
    pq: PreparedQueries,
    order,
    lb_sorted,
    state0: IncumbentState,
    *,
    with_info: bool = False,
    offset=0,
) -> tuple[IncumbentState, SearchStats]:
    """The host round driver: best-first ``(Q × batch)``-lane dispatches.

    One ``lax.while_loop`` serves every host-rounds frontend — offline
    multi-query (``offset == 0``), Q=1 single-query, streaming ingest
    (``offset`` maps local window starts into stream coordinates) and each
    resilient work range (``offset == lo``). Per-query drop-out: a query
    leaves the loop when it has no rounds left or its next batch's smallest
    lower bound can no longer beat its incumbent; a finished query's lanes
    ride along with the dead-lane sentinel, costing one masked row each.
    ``plan.warm_start`` seeds the incumbents through :func:`warm_prepass`
    first (changes work, not results).
    """
    nq = pq.qn.shape[0]
    n_win = order.shape[1]
    batch = plan.batch
    use_lb, use_cb = plan.use_lb, plan.use_cb

    state0, pre, rows_pre, cells_pre = warm_prepass(
        plan, prep, pq, order, lb_sorted, state0, with_info=with_info,
        offset=offset,
    )

    n_rounds = -(-n_win // batch)
    pad = n_rounds * batch - n_win
    order_p = jnp.concatenate(
        [order, jnp.zeros((nq, pad), order.dtype)], axis=1
    )
    lb_p = jnp.concatenate(
        [lb_sorted, jnp.full((nq, pad), jnp.inf, lb_sorted.dtype)], axis=1
    )

    # A query whose (possibly warm) incumbent already beats its best
    # remaining lower bound never enters the round loop at all.
    active0 = jnp.ones((nq,), bool)
    if use_lb:
        active0 = lb_p[:, 0] < state0.ub

    slice_round, peek_lb = _round_slicers(batch)
    if plan.gather != "fused":
        _ensure_slab_budget(plan, nq * batch, "run_host_rounds")

    class St(NamedTuple):
        r: jax.Array        # (Q,) per-query round pointer
        inc: IncumbentState
        active: jax.Array   # (Q,) still in the round loop?
        lanes: jax.Array    # (Q,)
        rows: jax.Array     # (Q,)
        cells: jax.Array    # (Q,)

    def cond(st: St) -> jax.Array:
        return jnp.any(st.active)

    def body(st: St) -> St:
        starts = slice_round(order_p, st.r)            # (Q, batch)
        lbs_b = slice_round(lb_p, st.r)                # (Q, batch)
        # Flattened (Q x batch) lane set, per-lane ub. Three per-lane cases
        # the scalar-ub form cannot express: finished queries submit dead
        # lanes; within an active query's batch, lanes whose own lower bound
        # already reaches the incumbent are submitted dead too (lane-level
        # LB gating — the batch-head check only gates the round); the rest
        # carry their query's incumbent.
        lane_live = jnp.logical_and(
            st.active[:, None], lbs_b < st.inc.ub[:, None]
        )
        ub_lanes = jnp.where(
            lane_live,
            jnp.broadcast_to(st.inc.ub[:, None], (nq, batch)),
            DEAD_LANE_UB,
        )
        if plan.gather == "fused":
            d, info = _dtw_round_fused(
                plan, prep, pq, starts, ub_lanes,
                use_cb=use_cb, with_info=with_info,
            )
        else:
            cand = jax.vmap(
                lambda s: gather_norm_windows(
                    prep.ref, s, plan.length, prep.mu, prep.sigma
                )
            )(starts)                                  # (Q, batch, l)
            cb = None
            if use_cb:
                cb = jax.vmap(cascade_keogh_cumulative)(cand, pq.u, pq.low)
            if with_info:
                d, info = ea_pruned_dtw_multi_batch(
                    pq.qn, cand, ub_lanes, window=plan.window,
                    band_width=plan.band_width, cb=cb, with_info=True,
                    **plan.knobs(),
                )
            else:
                d = ea_pruned_dtw_multi_batch(
                    pq.qn, cand, ub_lanes, window=plan.window,
                    band_width=plan.band_width, cb=cb, **plan.knobs(),
                )
                info = None
        if with_info:
            rows_q = jnp.sum(info.rows, axis=1, dtype=jnp.int32)
            cells_q = jnp.sum(info.cells, axis=1, dtype=jnp.int32)
        else:
            rows_q = cells_q = jnp.zeros((nq,), st.rows.dtype)
        d = jnp.where(jnp.isfinite(lbs_b), d, jnp.inf)  # padding lanes
        d = jnp.where(st.active[:, None], d, jnp.inf)
        inc, _ = fold_min(st.inc, starts, d, offset=offset)
        r_new = st.r + st.active.astype(st.r.dtype)
        # Drop-out: no rounds left, or the next batch's best lower bound
        # can no longer beat this query's incumbent.
        more = r_new < n_rounds
        if use_lb:
            nxt = peek_lb(lb_p, jnp.minimum(r_new, n_rounds - 1))
            more = jnp.logical_and(more, nxt < inc.ub)
        return St(
            r=r_new,
            inc=inc,
            active=jnp.logical_and(st.active, more),
            lanes=st.lanes + st.active.astype(st.lanes.dtype) * batch,
            rows=st.rows + rows_q,
            cells=st.cells + cells_q,
        )

    # ``lanes`` counts distinct candidates examined: round 0 re-submits the
    # prepass candidates (they lead its best-first batch), so the prepass
    # only stands alone for a query that never enters the round loop.
    st0 = St(
        r=jnp.zeros((nq,), jnp.int32),
        inc=state0,
        active=active0,
        lanes=jnp.where(active0, 0, pre).astype(jnp.int32),
        rows=rows_pre,
        cells=cells_pre,
    )
    st = jax.lax.while_loop(cond, body, st0)
    no_info = jnp.full((nq,), -1)
    return st.inc, SearchStats(
        rounds=st.r,
        lanes=st.lanes,
        lb_pruned=n_win - jnp.minimum(st.lanes, n_win),
        rows=st.rows if with_info else no_info,
        cells=st.cells if with_info else no_info,
    )


# ---------------------------------------------------------------------------
# persistent-sweep executor core
# ---------------------------------------------------------------------------

@_stage("dtw.execute")
def run_persistent(
    plan: SearchPlan,
    prep: PreparedRef,
    pq: PreparedQueries,
    order,
    lb_sorted,
    state0: IncumbentState,
) -> tuple[IncumbentState, SearchStats]:
    """One launch for the whole workload (DESIGN.md §2.5).

    Every query's full best-first candidate order is gathered once; the
    kernel grid keeps the query dimension parallel and carries each query's
    incumbent in SMEM across the *sequential* candidate-block dimension —
    tightened every ``block_k`` lanes, LB-gated per block on device.

    ``plan.warm_start > 0`` runs the same :func:`warm_prepass` as the host
    driver and seeds the sweep's ``ub`` with the prepass bounds; because the
    persistent kernel takes no ``best`` seed (strict improvement returns
    ``-1`` when the seed is unbeaten), the sweep's result is folded against
    the prepass state so a prepass winner keeps its start. Pre-refactor the
    knob was silently dropped here.
    """
    nq = pq.qn.shape[0]
    n_win = order.shape[1]

    state0, pre, _, _ = warm_prepass(
        plan, prep, pq, order, lb_sorted, state0
    )

    lb_p, order_p, _ = pad_lanes_to_blocks(plan.block_k, lb_sorted, order)
    if plan.gather == "fused":
        # The whole best-first order is *addressed*, never materialized:
        # each block of block_k lanes is sliced + normalized from the
        # resident reference on demand (O(N + block_k) working set).
        bd, bs, blocks = ea_pruned_dtw_persistent_fused(
            pq.qn, prep.ref, lb_p, order_p, state0.ub, window=plan.window,
            mu=prep.mu, sigma=prep.sigma, band_width=plan.band_width,
            envelopes=(pq.u, pq.low) if plan.use_cb else None,
            **plan.knobs(),
        )
    else:
        _ensure_slab_budget(plan, nq * order_p.shape[1], "run_persistent")
        cand_all = jax.vmap(
            lambda s: gather_norm_windows(
                prep.ref, s, plan.length, prep.mu, prep.sigma
            )
        )(order_p)                                     # (Q, k_pad, l)
        bd, bs, blocks = ea_pruned_dtw_persistent(
            pq.qn, cand_all, lb_p, order_p, state0.ub, window=plan.window,
            band_width=plan.band_width,
            envelopes=(pq.u, pq.low) if plan.use_cb else None, **plan.knobs(),
        )
    # Strict-improvement fold against the (possibly prepass-seeded) state:
    # unbeaten seeds keep their start, a tighter sweep result adopts its.
    improved = bd < state0.ub
    state = IncumbentState(
        ub=jnp.where(improved, bd, state0.ub),
        best=jnp.where(improved, bs, state0.best),
    )
    # visited blocks are a best-first prefix per query, so only the final
    # padded block can hold non-candidates — clamp to n_win
    lanes = jnp.minimum(blocks * plan.block_k, n_win).astype(jnp.int32)
    no_info = jnp.full((nq,), -1)
    return state, SearchStats(
        # dispatches, not batch rounds: one launch (+ the warm prepass)
        rounds=jnp.full((nq,), 2 if pre else 1, jnp.int32),
        lanes=lanes,
        lb_pruned=n_win - lanes,
        rows=no_info,
        cells=no_info,
    )


# ---------------------------------------------------------------------------
# jitted offline cores
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("plan", "with_info"))
def _offline_search_impl(ref, queries, ub_init, plan: SearchPlan, with_info):
    """prepare → cascade → host rounds / persistent sweep, one jitted program.

    The shared offline core behind ``multi_query_search``,
    ``subsequence_search`` (Q=1) and each resilient work range. Returns
    ``(IncumbentState, SearchStats, n_quar)``.
    """
    prep = prepare_ref(plan, ref)
    pq = prepare_queries(plan, queries)
    nq = pq.qn.shape[0]
    order, lb_sorted = cascade(plan, prep, pq.qn)
    state0 = initial_state(nq, pq.qn.dtype, ub_init, best_dtype=order.dtype)
    if plan.rounds == "persistent":
        state, stats = run_persistent(plan, prep, pq, order, lb_sorted, state0)
    else:
        state, stats = run_host_rounds(
            plan, prep, pq, order, lb_sorted, state0, with_info=with_info
        )
    return state, stats, prep.n_quar


@partial(jax.jit, static_argnames=("plan", "with_info"))
def _baseline_search_impl(ref, query, plan: SearchPlan, with_info):
    """Single-query core for the ``full``/``pruned`` baselines and
    multivariate queries.

    These paths have no ``(Q, K)`` lane form — ``dtw``/``pruned_dtw`` take a
    scalar threshold, and the multi batch is univariate-only — so the paper
    baselines keep a dedicated scalar-incumbent sweep here (the same
    prepare/cascade stages, a scalar round loop or ``block_sweep``).
    Returns scalar-field ``(IncumbentState, SearchStats, n_quar)`` shaped
    like Q=1 (length-1 arrays).
    """
    with jax.named_scope("dtw.prepare"):
        query_n = znorm(jnp.asarray(query)[: plan.length])
        u, low = envelope(query_n, plan.window)
    prep = prepare_ref(plan, ref)
    order, lb_sorted = cascade(plan, prep, query_n[None])
    state, stats = _baseline_execute(
        plan, prep, query_n, u, low, order, lb_sorted, with_info
    )
    return state, stats, prep.n_quar


@_stage("dtw.execute")
def _baseline_execute(
    plan: SearchPlan, prep: PreparedRef, query_n, u, low, order, lb_sorted,
    with_info,
) -> tuple[IncumbentState, SearchStats]:
    """The baselines' scalar-incumbent sweep over the best-first ``(1,
    n_win)`` order: a round loop, or one persistent launch / ``block_sweep``.
    """
    order, lb_sorted = order[0], lb_sorted[0]
    n_win = prep.mu.shape[0]
    use_lb, use_cb = plan.use_lb, plan.use_cb
    knobs = plan.knobs()

    def batch_distances(cand, ub, cb):
        if plan.variant in ("eapruned", "eapruned_nolb"):
            return ea_pruned_dtw_batch(
                query_n, cand, ub, window=plan.window,
                band_width=plan.band_width, cb=cb, **knobs,
            )
        if plan.variant == "pruned":
            return jax.vmap(
                lambda c: pruned_dtw(query_n, c, ub, window=plan.window)
            )(cand)
        return jax.vmap(lambda c: dtw(query_n, c, window=plan.window))(cand)

    def batch_stats(cand, ub, cb):
        if plan.variant in ("eapruned", "eapruned_nolb"):
            d, info = ea_pruned_dtw_batch(
                query_n, cand, ub, window=plan.window,
                band_width=plan.band_width, cb=cb, with_info=True, **knobs,
            )
            return d, jnp.sum(info.rows), jnp.sum(info.cells)
        if plan.variant == "pruned":
            d, info = jax.vmap(
                lambda c: pruned_dtw(
                    query_n, c, ub, window=plan.window, with_info=True
                )
            )(cand)
            return d, jnp.sum(info.rows), jnp.sum(info.cells)
        d = batch_distances(cand, ub, cb)
        m = query_n.shape[-1]
        k = cand.shape[0]
        # full DTW issues every in-window cell
        win_cells = m * (2 * plan.window + 1) - plan.window * (plan.window + 1)
        return d, jnp.asarray(k * m), jnp.asarray(k * min(win_cells, m * m))

    if plan.rounds == "persistent":
        # One gather of the whole best-first order; the sweep itself is a
        # single dispatch (EA variants) or the shared block-granular host
        # sweep (full/pruned kernels take no per-lane threshold).
        lb_p, order_p, _ = pad_lanes_to_blocks(plan.block_k, lb_sorted, order)
        # Baseline cores take pre-gathered candidates by contract, so this
        # slab is sanctioned regardless of plan.gather — but it still has to
        # fit the configured budget.
        _ensure_slab_budget(plan, order_p.shape[0], "baseline persistent")
        cand_all = gather_norm_windows(
            prep.ref, order_p, plan.length, prep.mu, prep.sigma
        )
        if plan.variant in ("eapruned", "eapruned_nolb"):
            envs = (u[None], low[None]) if use_cb else None
            bd, bs, blocks = ea_pruned_dtw_persistent(
                query_n[None], cand_all[None], lb_p[None], order_p[None],
                jnp.full((1,), BIG, query_n.dtype), window=plan.window,
                band_width=plan.band_width, envelopes=envs, **knobs,
            )
            best, ub, blocks = bs[0], bd[0], blocks[0]
        else:
            ub, best, blocks = block_sweep(
                cand_all, lb_p, order_p, jnp.asarray(BIG, query_n.dtype),
                plan.block_k,
                lambda c, lbb, ub_cur: batch_distances(c, ub_cur, None),
            )
        lanes = jnp.minimum(blocks * plan.block_k, n_win).astype(jnp.int32)
        no_info = jnp.asarray(-1)
        state = IncumbentState(ub=ub[None], best=jnp.asarray(best)[None])
        stats = SearchStats(
            rounds=jnp.asarray(1)[None],  # dispatches: one launch per search
            lanes=lanes[None],
            lb_pruned=(jnp.asarray(n_win) - lanes)[None],
            rows=no_info[None],
            cells=no_info[None],
        )
        return state, stats

    batch = plan.batch
    n_rounds = -(-n_win // batch)
    pad = n_rounds * batch - n_win
    order_p = jnp.concatenate([order, jnp.zeros((pad,), order.dtype)])
    lb_p = jnp.concatenate(
        [lb_sorted, jnp.full((pad,), jnp.inf, lb_sorted.dtype)]
    )

    class St(NamedTuple):
        r: jax.Array
        ub: jax.Array
        best: jax.Array
        lanes: jax.Array
        rows: jax.Array
        cells: jax.Array

    def cond(st: St) -> jax.Array:
        more = st.r < n_rounds
        if not use_lb:
            return more
        next_lb = jax.lax.dynamic_slice(lb_p, (st.r * batch,), (1,))[0]
        return jnp.logical_and(more, next_lb < st.ub)

    def body(st: St) -> St:
        starts = jax.lax.dynamic_slice(order_p, (st.r * batch,), (batch,))
        lbs = jax.lax.dynamic_slice(lb_p, (st.r * batch,), (batch,))
        cand = gather_norm_windows(
            prep.ref, starts, plan.length, prep.mu, prep.sigma
        )
        cb = None
        if use_cb:
            cb = cascade_keogh_cumulative(cand, u, low)
        if plan.variant in ("eapruned", "eapruned_nolb"):
            # Per-lane ub: quarantined and round-padding lanes (both marked
            # by +inf lower bounds) ride as dead lanes — the kernel abandons
            # them on row 0 instead of running a DP over masked garbage.
            ub_b = jnp.where(jnp.isfinite(lbs), st.ub, DEAD_LANE_UB)
        else:
            ub_b = st.ub  # full/pruned kernels take a scalar threshold
        if with_info:
            d, rows, cells = batch_stats(cand, ub_b, cb)
        else:
            d = batch_distances(cand, ub_b, cb)
            rows = cells = jnp.asarray(0)
        d = jnp.where(jnp.isfinite(lbs), d, jnp.inf)  # padding lanes
        k = jnp.argmin(d)
        dmin = d[k]
        improved = dmin < st.ub
        return St(
            r=st.r + 1,
            ub=jnp.where(improved, dmin, st.ub),
            best=jnp.where(improved, starts[k], st.best),
            lanes=st.lanes + batch,
            rows=st.rows + rows,
            cells=st.cells + cells,
        )

    st0 = St(
        r=jnp.asarray(0),
        ub=jnp.asarray(BIG, query_n.dtype),
        best=jnp.asarray(-1, order.dtype),
        lanes=jnp.asarray(0),
        rows=jnp.asarray(0),
        cells=jnp.asarray(0),
    )
    st = jax.lax.while_loop(cond, body, st0)
    no_info = jnp.asarray(-1)
    state = IncumbentState(ub=st.ub[None], best=st.best[None])
    stats = SearchStats(
        rounds=st.r[None],
        lanes=st.lanes[None],
        lb_pruned=(jnp.asarray(n_win) - jnp.minimum(st.lanes, n_win))[None],
        rows=(st.rows if with_info else no_info)[None],
        cells=(st.cells if with_info else no_info)[None],
    )
    return state, stats


# ---------------------------------------------------------------------------
# streaming ingest core (traced; the streaming wrappers own buffering)
# ---------------------------------------------------------------------------

def run_stream_ingest(
    plan: SearchPlan, ctx, valid, pq: PreparedQueries, state0: IncumbentState,
    offset,
):
    """One ingest over the windows of ``ctx``: prepare → cascade → rounds.

    ``valid`` masks which of the ``len(ctx) - length + 1`` window starts
    really exist (fixed-shape buffers mask their garbage prefix/padding
    suffix); ``offset`` is the stream coordinate of ``ctx[0]``. The carried
    incumbents ride in as ``state0`` and gate round 0 exactly like a warm
    ``ub_init`` in the offline driver. Returns
    ``(IncumbentState, SearchStats, n_quar)`` with ``best`` in stream
    coordinates.
    """
    prep = prepare_ref(plan, ctx, valid=valid)
    order, lb_sorted = cascade(plan, prep, pq.qn)
    state, stats = run_host_rounds(
        plan, prep, pq, order, lb_sorted, state0, offset=offset
    )
    return state, stats, prep.n_quar


# ---------------------------------------------------------------------------
# sharded executor (shard_map + pmin reconcile)
# ---------------------------------------------------------------------------

def make_sharded_search(
    mesh: jax.sharding.Mesh, axis_names: tuple[str, ...], plan: SearchPlan
):
    """Build the jitted sharded search program for a mesh config.

    Returns ``search_fn(ref, queries) -> (best_dist (Q,), best_start (Q,),
    rounds, n_quar, lanes (Q,), lb_pruned (Q,))``. Work items are (query,
    candidate-range) pairs: candidate window starts are sharded
    contiguously across the mesh axes
    (each device owns a slice of every query's windows), queries ride in
    the lane dimension of the per-device multi-query batch, and after every
    round the per-query incumbent vector is reconciled with one vectorized
    ``lax.pmin`` all-reduce. Devices iterate in lockstep until no device
    has an active (query, range) item left (``pmax`` continue flag); the
    scalar frontend is Q=1 of this same program.

    ``plan.quarantine`` threads the §2.6 mask per shard: poisoned windows
    are condemned on the shard that owns them (``+inf`` LB → dead-lane
    sentinel, query-independent), counts ``psum``-reduce to the
    single-device total, and the sanitized reference keeps the shared
    prefix sums finite for survivors. ``lanes`` and ``lb_pruned`` count
    windows as :func:`run_host_rounds` does, summed over the shards; the
    windows that pad the reference to the mesh size count in neither, so
    ``lanes + lb_pruned`` is the number of windows.
    """
    n_shards = 1
    for a in axis_names:
        n_shards *= mesh.shape[a]
    spec_sharded = P(axis_names)
    spec_rep = P()
    batch = plan.batch

    def local_search(ref, queries_n, starts, valid, q_ok):
        nq = queries_n.shape[0]
        n_win = ref.shape[0] - plan.length + 1

        def all_axes(collective, x):
            with jax.named_scope("dtw.reconcile"):
                for a in axis_names:
                    x = collective(x, a)
                return x

        psum_all = partial(all_axes, jax.lax.psum)
        pmin_all = partial(all_axes, jax.lax.pmin)
        pmax_all = partial(all_axes, jax.lax.pmax)

        with jax.named_scope("dtw.prepare"):
            # Quarantine accounting before the mask folds into ``valid``:
            # each shard counts its own real (non-padding) condemned
            # windows; the psum after the rounds reconciles them into the
            # global count every shard reports.
            quar_local = jnp.sum(
                jnp.logical_and(valid, ~q_ok)
            ).astype(jnp.int32)
            # Mesh padding is no window: its +inf bounds sort it behind
            # every real window (the argsort is stable and padding holds
            # the shard's last starts), so a query's first ``n_real``
            # sorted lanes are its real windows.
            n_real = jnp.sum(valid).astype(jnp.int32)
            valid = jnp.logical_and(valid, q_ok)
            mu, sigma = window_stats(ref, plan.length)
            u, low = jax.vmap(envelope, in_axes=(0, None))(
                queries_n, plan.window
            )
        prep = PreparedRef(
            ref=ref, mu=mu, sigma=sigma, valid=None, n_quar=quar_local
        )
        lbs = local_cascade(plan, prep, queries_n, starts, valid)
        with jax.named_scope("dtw.cascade"):
            order, lb_o = _sort_bounds(lbs)
            starts_o = jnp.take_along_axis(
                jnp.broadcast_to(starts, lbs.shape), order, axis=1
            )
        with jax.named_scope("dtw.execute"):
            n_local = starts.shape[0]
            n_rounds = -(-n_local // batch)
            pad = n_rounds * batch - n_local
            starts_p = jnp.concatenate(
                [starts_o, jnp.zeros((nq, pad), starts_o.dtype)], axis=1
            )
            lb_p = jnp.concatenate(
                [lb_o, jnp.full((nq, pad), jnp.inf, lb_o.dtype)], axis=1
            )
            slice_round, peek_lb = _round_slicers(batch)
            if plan.gather != "fused":
                _ensure_slab_budget(plan, nq * batch, "make_sharded_search")

            class St(NamedTuple):
                r: jax.Array        # (Q,) local per-query round pointer
                ub: jax.Array       # (Q,) globally reconciled incumbents
                loc: IncumbentState  # local best (start, dist per lane fold)
                go: jax.Array       # global continue flag

            def cond(st: St) -> jax.Array:
                return st.go

            def body(st: St) -> St:
                s = slice_round(starts_p, st.r)            # (Q, batch)
                lb = slice_round(lb_p, st.r)
                head = peek_lb(lb_p, st.r)
                local_more = jnp.logical_and(st.r < n_rounds, head < st.ub)
                # Dead-lane sentinel for finished (query, range) items and for
                # lanes whose own lower bound already reaches the incumbent
                # (lane-level LB gating, as in the host round driver).
                lane_live = jnp.logical_and(
                    local_more[:, None], lb < st.ub[:, None]
                )
                ub_lanes = jnp.where(
                    lane_live,
                    jnp.broadcast_to(st.ub[:, None], (nq, batch)),
                    DEAD_LANE_UB,
                )
                if plan.gather == "fused":
                    d = ea_pruned_dtw_multi_batch_fused(
                        queries_n, ref, s, ub_lanes, window=plan.window,
                        mu=mu, sigma=sigma, envelopes=(u, low),
                        band_width=plan.band_width, **plan.knobs(),
                    )
                else:
                    cand = jax.vmap(
                        lambda ss: gather_norm_windows(
                            ref, ss, plan.length, mu, sigma
                        )
                    )(s)
                    cb = jax.vmap(cascade_keogh_cumulative)(cand, u, low)
                    d = ea_pruned_dtw_multi_batch(
                        queries_n, cand, ub_lanes, window=plan.window,
                        band_width=plan.band_width, cb=cb, **plan.knobs(),
                    )
                d = jnp.where(jnp.isfinite(lb), d, jnp.inf)  # padding lanes
                d = jnp.where(local_more[:, None], d, jnp.inf)
                # Local fold keeps this shard's best achieved pair; the global
                # incumbent only needs the bound, reconciled by one vectorized
                # pmin per round.
                loc, _ = fold_min(st.loc, s, d)
                ub = pmin_all(jnp.minimum(st.ub, loc.ub))
                r = st.r + local_more.astype(st.r.dtype)
                nxt = peek_lb(lb_p, jnp.minimum(r, n_rounds - 1))
                local_next = jnp.logical_and(r < n_rounds, nxt < ub)
                return St(
                    r=r, ub=ub, loc=loc, go=pmax_all(jnp.any(local_next)),
                )

            go0 = pmax_all(jnp.asarray(True))
            st0 = St(
                r=jnp.zeros((nq,), jnp.int32),
                ub=jnp.full((nq,), BIG, queries_n.dtype),
                loc=IncumbentState(
                    ub=jnp.full((nq,), BIG, queries_n.dtype),
                    best=jnp.full((nq,), -1, starts.dtype),
                ),
                go=go0,
            )
            st = jax.lax.while_loop(cond, body, st0)
            # Per-query global argmin: vectorized lexicographic
            # (distance, start).
            g_min = pmin_all(st.loc.ub)                    # (Q,)
            is_best = jnp.isclose(st.loc.ub, g_min)
            cand_start = jnp.where(
                is_best, st.loc.best, jnp.iinfo(jnp.int32).max
            )
            g_start = pmin_all(cand_start.astype(jnp.int32))
            # Lanes as ``run_host_rounds`` counts them, real windows only.
            lanes = psum_all(jnp.minimum(st.r * batch, n_real))
            return (g_min, g_start, pmax_all(jnp.max(st.r)),
                    psum_all(quar_local), lanes, n_win - lanes)

    @jax.jit
    def search_fn(ref: jax.Array, queries: jax.Array):
        with jax.named_scope("dtw.prepare"):
            ref = jnp.asarray(ref)
            queries_n = znorm(jnp.asarray(queries)[:, : plan.length])
            n_win = ref.shape[0] - plan.length + 1
            per = -(-n_win // n_shards)
            total = per * n_shards
            starts = jnp.arange(total, dtype=jnp.int32)
            valid = starts < n_win
            starts = jnp.minimum(starts, n_win - 1)
            if plan.quarantine:
                # Mask on the raw series, sanitize before replication so
                # shared prefix sums stay finite for the surviving windows
                # (§2.6).
                finite_ok = window_finite_mask(ref, plan.length)
                ref = sanitize_series(ref)
                q_ok = finite_ok[starts]
            else:
                q_ok = jnp.ones_like(valid)

        # check_vma=False: the per-device round loop mixes device-varying
        # and replicated values.
        shard = jax.shard_map(
            local_search,
            mesh=mesh,
            in_specs=(
                spec_rep, spec_rep, spec_sharded, spec_sharded, spec_sharded,
            ),
            out_specs=(spec_rep,) * 6,
            check_vma=False,
        )
        return shard(ref, queries_n, starts, valid, q_ok)

    return search_fn


# ---------------------------------------------------------------------------
# Executor protocol — the range-execution seam
# ---------------------------------------------------------------------------

class RangeResult(NamedTuple):
    """Outcome of one work range: folded incumbents + accounting."""
    state: IncumbentState   # (Q,) incumbents, best in GLOBAL coordinates
    stats: SearchStats
    quarantined: jax.Array  # windows of this range excluded by §2.6


class Executor(Protocol):
    """``run_range(plan, state, lo, hi)``: search window starts [lo, hi).

    The seam the fault-tolerant layer schedules on: an executor is bound to
    one (reference, queries) workload at construction and searches any
    window-start range of it against carried incumbents, returning results
    in global window coordinates. Implementations: host rounds, persistent
    sweep, sharded mesh program.
    """

    def run_range(
        self, plan: SearchPlan, state: IncumbentState, lo: int, hi: int
    ) -> RangeResult:
        ...


class _OfflineRangeExecutor:
    """Shared range logic for the host-rounds/persistent executors.

    A range is searched as the offline core over its slice: windows
    ``[lo, hi)`` live in ``ref[lo : hi + length - 1]``, the carried
    incumbents ride in as warm ``ub_init`` seeds, and achieved starts map
    back by ``+ lo``. Distinct range lengths trace distinct programs (the
    usual static-shape rule); equal-length ranges share one trace.
    """

    _rounds: str

    def __init__(self, ref, queries):
        self.ref = jnp.asarray(ref)
        self.queries = jnp.atleast_2d(jnp.asarray(queries))

    def run_range(
        self, plan: SearchPlan, state: IncumbentState, lo: int, hi: int
    ) -> RangeResult:
        plan = dataclasses.replace(plan, rounds=self._rounds)
        seg = self.ref[lo : hi + plan.length - 1]
        res_state, stats, n_quar = _offline_search_impl(
            seg, self.queries, jnp.asarray(state.ub, self.queries.dtype),
            plan, False,
        )
        best = jnp.where(res_state.best >= 0, res_state.best + lo, -1)
        # Seed-unbeaten queries keep their incoming start (the seed's
        # achiever lives outside this range).
        best = jnp.where(
            res_state.ub < jnp.asarray(state.ub, res_state.ub.dtype),
            best, state.best,
        )
        return RangeResult(
            state=IncumbentState(ub=res_state.ub, best=best),
            stats=stats, quarantined=n_quar,
        )


class HostRoundsExecutor(_OfflineRangeExecutor):
    """Best-first host-round dispatches over the range (the default)."""
    _rounds = "host"


class PersistentExecutor(_OfflineRangeExecutor):
    """The range's whole best-first order in one launch (DESIGN.md §2.5)."""
    _rounds = "persistent"


class ShardedExecutor:
    """Mesh-parallel range execution: shard_map + ``pmin`` reconcile.

    Satisfies the same ``run_range`` contract as the host executors so the
    resilient layer can schedule mesh-sized ranges too; each distinct range
    length compiles its own program (cached per length). Incoming incumbent
    *bounds* seed nothing on the mesh path today (the SPMD program cold-
    starts at BIG) — the fold afterwards keeps whichever side is tighter.
    """

    def __init__(self, mesh, axis_names, ref, queries):
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.ref = jnp.asarray(ref)
        self.queries = jnp.atleast_2d(jnp.asarray(queries))
        self._fns: dict[SearchPlan, object] = {}

    def _fn(self, plan: SearchPlan):
        if plan not in self._fns:
            self._fns[plan] = make_sharded_search(
                self.mesh, self.axis_names, plan
            )
        return self._fns[plan]

    def run_range(
        self, plan: SearchPlan, state: IncumbentState, lo: int, hi: int
    ) -> RangeResult:
        seg = self.ref[lo : hi + plan.length - 1]
        best_d, best_s, rounds, n_quar, lanes, lb_pruned = self._fn(plan)(
            seg, self.queries
        )
        improved = best_d < jnp.asarray(state.ub, best_d.dtype)
        merged = IncumbentState(
            ub=jnp.where(improved, best_d, state.ub),
            best=jnp.where(improved, best_s + lo, state.best),
        )
        nq = self.queries.shape[0]
        no_info = jnp.full((nq,), -1)
        return RangeResult(
            state=merged,
            stats=SearchStats(
                rounds=jnp.broadcast_to(rounds, (nq,)),
                lanes=lanes, lb_pruned=lb_pruned, rows=no_info,
                cells=no_info,
            ),
            quarantined=n_quar,
        )


def get_executor(
    plan: SearchPlan, ref, queries, *, mesh=None, axis_names=None
) -> Executor:
    """Bind the executor ``plan.rounds`` selects to one workload."""
    if mesh is not None:
        return ShardedExecutor(mesh, axis_names, ref, queries)
    if plan.rounds == "persistent":
        return PersistentExecutor(ref, queries)
    return HostRoundsExecutor(ref, queries)


def _merge_range_results(a: RangeResult, b: RangeResult) -> RangeResult:
    """Fold a duplicate completion into the primary's (idempotent).

    Incumbents merge under strict improvement; stats and the quarantine
    count stay the primary's — both attempts scanned the same windows, so
    counting the backup's quarantined windows again would double-count.
    """
    return a._replace(state=merge_states(a.state, b.state))


def _merge_ingest_results(a, b):
    """Same rule for ``run_ingest``'s ``(new_tail, IngestResult)`` pairs."""
    tail_a, res_a = a
    _tail_b, res_b = b
    merged = merge_states(
        IncumbentState(ub=res_a.ub, best=res_a.best),
        IncumbentState(ub=res_b.ub, best=res_b.best),
    )
    return tail_a, res_a._replace(ub=merged.ub, best=merged.best)


class HedgedExecutor:
    """Race a straggling attempt on the next-healthiest wrapped executor.

    Wraps N executors behind the same seam (``run_range``, and
    ``run_ingest`` when the wrapped executors are streaming ingest
    executors). Every attempt runs on the healthiest available executor;
    when it takes longer than the hedge delay — explicit ``hedge_delay``,
    or derived as ``threshold × EWMA`` of the fleet's attempt latency —
    the same work is raced on up to ``hedge_max_inflight`` backups and the
    race is adjudicated on the virtual timeline
    (``fault_tolerance.hedge_race``; DESIGN.md §2.9 spells out the
    host-serialized emulation vs a concurrent RPC deployment). Duplicate
    completions merge through the strict-improvement fold
    (``incumbents.merge_states``), so a hedge can never change the answer
    — only the latency.

    Health: one ``WorkerHealth`` (EWMA + circuit breaker) per wrapped
    executor. Routing prefers breaker-ready executors that are not
    straggling (EWMA ≤ ``threshold ×`` the fleet EWMA), in index order —
    deterministic whenever the clock is. A transient failure of the
    *primary* attempt records breaker state and re-raises: retry policy
    belongs to the layer above (``resilient_search``, the supervisor),
    composing instead of duplicating it. Backup failures are absorbed —
    the primary's completed result stands.

    Counters: ``hedges_launched`` / ``hedges_won`` (a backup virtually
    finished first) / ``last_effective_dt`` (the latency a client of the
    race would have seen, which is what callers should feed their own
    monitors). ``clock`` is injectable; with a fake clock every race is
    deterministic in tests.
    """

    def __init__(
        self,
        executors,
        *,
        hedge_delay: float | None = None,
        hedge_max_inflight: int = 2,
        threshold: float = 3.0,
        alpha: float = 0.2,
        breaker_threshold: int = 3,
        breaker_cooldown: float = 1.0,
        clock=time.time,
    ):
        self._executors = tuple(executors)
        if not self._executors:
            raise guards.SearchInputError(
                "HedgedExecutor needs at least one executor"
            )
        if hedge_max_inflight < 1:
            raise guards.SearchInputError("hedge_max_inflight must be >= 1")
        self.hedge_delay = hedge_delay
        self.hedge_max_inflight = int(hedge_max_inflight)
        self._clock = clock
        self.monitor = StragglerMonitor(threshold=threshold, alpha=alpha)
        self.health = tuple(
            WorkerHealth(
                threshold=threshold, alpha=alpha,
                breaker_threshold=breaker_threshold,
                breaker_cooldown=breaker_cooldown, clock=clock,
            )
            for _ in self._executors
        )
        self.hedges_launched = 0
        self.hedges_won = 0
        self.last_effective_dt: float | None = None
        self._steps = 0

    # -- routing ----------------------------------------------------------
    def _order(self) -> list[int]:
        """Executor indices, healthiest first: breaker-ready before open,
        non-straggling before straggling, index order as the tiebreak."""
        fleet = self.monitor.ewma

        def key(i: int):
            h = self.health[i]
            slow = (
                h.ewma is not None
                and fleet is not None
                and h.ewma > self.monitor.threshold * fleet
            )
            return (0 if h.ready() else 1, 1 if slow else 0, i)

        return sorted(range(len(self._executors)), key=key)

    def _delay(self) -> float | None:
        if self.hedge_delay is not None:
            return self.hedge_delay
        if self.monitor.ewma is None:
            return None  # no baseline yet: never hedge the first attempt
        return self.monitor.threshold * self.monitor.ewma

    def health_snapshots(self) -> tuple:
        return tuple(h.snapshot() for h in self.health)

    # -- the race ---------------------------------------------------------
    def _attempt(self, method: str, args, kwargs, merge):
        primary = self._order()[0]
        self.health[primary].acquire()
        t0 = self._clock()
        try:
            result = getattr(self._executors[primary], method)(
                *args, **kwargs
            )
        except GUARD_ERRORS:
            raise
        except TRANSIENT:
            self.health[primary].fail()
            raise
        dt_p = self._clock() - t0
        delay = self._delay()  # pre-observe: the baseline excludes this dt
        self.health[primary].observe(dt_p)
        effective = dt_p
        if delay is not None and dt_p > delay and len(self._executors) > 1:
            used = {primary}

            def backups():
                while True:
                    cands = [
                        i for i in self._order()
                        if i not in used and self.health[i].ready()
                    ]
                    if not cands:
                        return
                    i = cands[0]
                    used.add(i)

                    def thunk(i=i):
                        self.health[i].acquire()
                        return getattr(self._executors[i], method)(
                            *args, **kwargs
                        )

                    yield i, thunk

            race = hedge_race(
                dt_p, delay, backups(), clock=self._clock,
                max_inflight=self.hedge_max_inflight,
                on_failure=lambda tag, _e: self.health[tag].fail(),
            )
            self.hedges_launched += race.launched
            if race.won:
                self.hedges_won += 1
            for tag, res_b, dt_b in race.completions:
                self.health[tag].observe(dt_b)
                result = merge(result, res_b)
            effective = race.effective_dt
        self.monitor.observe(self._steps, effective)
        self._steps += 1
        self.last_effective_dt = effective
        return result

    # -- the seam ---------------------------------------------------------
    def run_range(
        self, plan: SearchPlan, state: IncumbentState, lo: int, hi: int
    ) -> RangeResult:
        return self._attempt(
            "run_range", (plan, state, lo, hi), {}, _merge_range_results
        )

    def run_ingest(self, *args, **kwargs):
        """Forward one streaming ingest through the race (duck-typed: the
        wrapped executors must expose ``run_ingest``, e.g.
        ``search.streaming.StreamIngestExecutor``)."""
        return self._attempt(
            "run_ingest", args, kwargs, _merge_ingest_results
        )
