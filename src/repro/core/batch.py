"""Batched EAPrunedDTW — the TPU-native unit of similarity-search work.

The UCR suite streams candidates one at a time, tightening ``ub`` after each.
A TPU wants thousands of independent lanes in flight, so the unit of work
here is a *batch* of lanes evaluated in one dispatch (DESIGN.md §2.4). Each
lane early-abandons independently against **its own** upper bound; the batch
completes when every lane has abandoned or finished; incumbents are then
tightened with the batch minima before the next batch. Best-first ordering
by lower bound (see search/cascade.py) restores most of the sequential
tightening power the paper gets for free.

Two batch shapes share one kernel program:

  * ``ea_pruned_dtw_batch`` — one query against ``K`` candidates. ``ub`` may
    be a scalar (shared, the PR-1 behaviour) or a ``(K,)`` per-lane vector.
  * ``ea_pruned_dtw_multi_batch`` — ``Q`` queries against their own
    ``(Q, K, m)`` candidate rounds, flattened to a ``(Q × K)`` lane set and
    evaluated in **one** launch with a ``(Q, K)`` per-lane ``ub``. This is
    the multi-query serving primitive: no per-query launches, no per-query
    recompilation, and finished queries ride along as dead lanes (negative
    ``ub`` sentinel) that abandon on row 0.

Backend dispatch (see ``core.backend``): both entry points route to one of
two implementations:

  * ``backend="pallas"`` / ``"pallas_interpret"`` — the banded Pallas kernel
    (``kernels.ops.dtw_ea`` / ``dtw_ea_multi``). Tuning knobs: ``band_width``
    (columns per row, lane-aligned default), ``block_k`` (candidate lanes per
    grid block — the early-exit granularity), ``row_block`` (DP rows per
    sequential grid step). ``pallas`` lowers through Mosaic and exists only
    on the TPU (elsewhere it raises); ``pallas_interpret`` runs interpret
    mode on any platform (the CPU test path for the kernel program).
  * ``backend="jax"`` — per-lane banded ``lax.while_loop`` under ``vmap``
    (CPU/GPU fallback, float64-capable reference), with ``ub`` vmapped per
    lane so the semantics match the kernel exactly. Tuning knobs:
    ``band_width``, ``rows_per_step`` (rows per loop iteration — amortizes
    vmap'd loop-control overhead).

``backend=None`` defers to ``$REPRO_DTW_BACKEND``, then the platform default
(``pallas`` on TPU, ``jax`` elsewhere); the env var is re-read on every
(un-jitted) call, so changing it between calls takes effect. Multivariate
queries always take the ``jax`` path. ``with_info=True`` additionally
returns per-lane ``EAInfo`` pruning counters; the default is counter-free —
search fast rounds pay no bookkeeping.

Fused-gather primitives (DESIGN.md §2.10, ``gather="fused"`` — the search
default): ``ea_pruned_dtw_multi_batch_fused`` and
``ea_pruned_dtw_persistent_fused`` take the raw reference series plus
per-lane starts and the O(N) ``(mu, sigma)`` stats tables instead of a
pre-gathered ``(Q, K, m)`` window slab. On the Pallas backends the slicing
and z-normalization happen inside the kernel; on the jax backend the same
fusion is a vmapped ``dynamic_slice`` + normalize inlined into the round
body (and, for persistent mode, into each ``while_loop`` block step — an
O(N + block_k·m) working set matching the kernel, where the slab form
materialized all O(K·m) up front). Values are bit-identical to the slab
form: same copies, same ``clamp_sigma``, same op order.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import guards
from repro.core.backend import resolve_backend
from repro.core.common import (
    DEAD_LANE_UB,
    clamp_sigma,
    pad_lanes_to_blocks,
)
from repro.core.ea_pruned_dtw import EAInfo, ea_pruned_dtw_banded
from repro.core.lower_bounds import cascade_keogh_cumulative


def _slice_norm(ref, starts, length, mu_l, sg_l):
    """Fused normalize-on-slice of one lane set (``(K, length)``).

    ``mu_l``/``sg_l`` are per-lane (already indexed by start, sigma
    pre-clamped) — the trace-inlined form of ``common.norm_window_slice``
    used inside round and while_loop bodies, where the stats lookups have
    already been hoisted.
    """
    win = jax.vmap(
        lambda s: jax.lax.dynamic_slice(ref, (s,), (length,))
    )(starts)
    return (win - mu_l[:, None]) / sg_l[:, None]


def _kernel_ops():
    """Deferred ``repro.kernels.ops`` import, resolved at dispatch time.

    ``repro.kernels`` imports ``repro.core.common``, which triggers this
    package's ``__init__`` — a module-level import here would close a
    ``kernels → core → kernels`` cycle and crash any kernels-first entry
    point (``import repro.kernels`` before ``repro.core``). Python caches
    the module after the first call, so the per-dispatch cost is a dict hit.
    """
    from repro.kernels import ops

    return ops


@partial(
    jax.jit,
    static_argnames=("window", "band_width", "rows_per_step", "with_info"),
)
def _batch_jax(
    query, candidates, ub, window, band_width, cb, rows_per_step, with_info
):
    """vmapped banded-while_loop backend (CPU/GPU fallback), per-lane ub."""
    ub_lanes = jnp.broadcast_to(jnp.asarray(ub), candidates.shape[:1])
    if cb is None:
        fn = lambda c, u: ea_pruned_dtw_banded(
            query, c, u, window=window, band_width=band_width,
            rows_per_step=rows_per_step, with_info=with_info,
        )
        return jax.vmap(fn)(candidates, ub_lanes)
    fn = lambda c, u, cbv: ea_pruned_dtw_banded(
        query, c, u, window=window, band_width=band_width, cb=cbv,
        rows_per_step=rows_per_step, with_info=with_info,
    )
    return jax.vmap(fn)(candidates, ub_lanes, cb)


@partial(
    jax.jit,
    static_argnames=("window", "band_width", "rows_per_step", "with_info"),
)
def _multi_jax(
    queries, candidates, ub, window, band_width, cb, rows_per_step, with_info
):
    """Multi-query jax backend: per-lane batches over the query axis.

    On CPU the query axis runs under ``lax.map`` so each query's lanes get
    their *own* while_loop trip count — under a fused ``vmap`` every lane
    would step until the slowest lane of the slowest query (measured ~20%
    inflation on mixed-tightness workloads), and a finished query's dead
    lanes would be re-masked every iteration instead of exiting after one.
    On accelerators the fused vmap keeps all ``Q × K`` lanes in flight (the
    lockstep cost is what the hardware wants; the Pallas backend is the
    preferred path there anyway).
    """
    ub_lanes = jnp.broadcast_to(jnp.asarray(ub), candidates.shape[:2])

    def _mapped(fn, ops):
        # lax.cond skips the whole while_loop for an all-dead query — the
        # finished-query fast path the round loop relies on. Counter rounds
        # always run for real: a dead lane issues its abandoning row
        # (EAInfo semantics), which the skipped branch could not report.
        if with_info:
            return jax.lax.map(lambda t: fn(*t), ops)
        out_sd = jax.eval_shape(fn, *jax.tree.map(lambda x: x[0], ops))

        def dead():
            return jax.tree.map(
                lambda sd: jnp.full(sd.shape, jnp.inf, sd.dtype), out_sd
            )

        return jax.lax.map(
            lambda t: jax.lax.cond(
                jnp.any(t[2] >= 0), lambda: fn(*t), dead
            ),
            ops,
        )

    if cb is None:
        fn = lambda q, cs, us: _batch_jax(
            q, cs, us, window, band_width, None, rows_per_step, with_info
        )
        if jax.default_backend() == "cpu":
            return _mapped(fn, (queries, candidates, ub_lanes))
        return jax.vmap(fn)(queries, candidates, ub_lanes)
    fn = lambda q, cs, us, cbs: _batch_jax(
        q, cs, us, window, band_width, cbs, rows_per_step, with_info
    )
    if jax.default_backend() == "cpu":
        return _mapped(fn, (queries, candidates, ub_lanes, cb))
    return jax.vmap(fn)(queries, candidates, ub_lanes, cb)


def ea_pruned_dtw_batch(
    query: jax.Array,
    candidates: jax.Array,
    ub: jax.Array,
    window: int,
    band_width: int | None = None,
    cb: jax.Array | None = None,
    rows_per_step: int = 1,
    backend: str | None = None,
    block_k: int = 8,
    row_block: int = 128,
    with_info: bool = False,
):
    """Banded EAPrunedDTW of one query against K candidates.

    Args:
      query: ``(m,)`` or ``(m, dims)``.
      candidates: ``(K, m[, dims])``.
      ub: scalar upper bound shared by the whole batch, or ``(K,)`` per-lane
        upper bounds (each lane abandons against its own).
      window: Sakoe-Chiba window.
      band_width: static band columns per row (defaults to lane-aligned
        ``2*window+1``).
      cb: optional ``(K, m)`` per-candidate cumulative LB_Keogh suffix sums
        for UCR-style threshold tightening.
      rows_per_step: rows per while_loop iteration (``jax`` backend knob).
      backend: ``"pallas"`` / ``"pallas_interpret"`` / ``"jax"`` / ``"auto"``;
        ``None`` defers to ``$REPRO_DTW_BACKEND`` then the platform default.
      block_k, row_block: Pallas grid tiling knobs.
      with_info: also return per-lane ``EAInfo`` pruning counters.

    Returns: ``(K,)`` distances (``+inf`` where abandoned); with ``with_info``
      a ``(distances, EAInfo)`` tuple of per-lane arrays.

    Raises ``core.guards.SearchInputError`` on malformed shapes/knobs and
    ``NonFiniteInputError`` on a non-finite query (value checks run only on
    concrete arrays — trace-safe when called from jitted drivers).
    """
    guards.check_batch_args(query, candidates, ub, window, cb=cb)
    resolved = resolve_backend(backend)
    if resolved != "jax" and jnp.ndim(query) != 1:
        resolved = "jax"  # kernel is univariate; see core.backend docstring
    if resolved == "jax":
        out = _batch_jax(
            query, candidates, ub, window, band_width, cb, rows_per_step,
            with_info,
        )
        return out
    interpret = resolved == "pallas_interpret"
    out = _kernel_ops().dtw_ea(
        query, candidates, ub, window, cb=cb, band_width=band_width,
        block_k=block_k, row_block=row_block, interpret=interpret,
        with_info=with_info,
    )
    if with_info:
        d, rows, cells = out
        return d, EAInfo(rows=rows, cells=cells)
    return out


def ea_pruned_dtw_multi_batch(
    queries: jax.Array,
    candidates: jax.Array,
    ub: jax.Array,
    window: int,
    band_width: int | None = None,
    cb: jax.Array | None = None,
    rows_per_step: int = 1,
    backend: str | None = None,
    block_k: int = 8,
    row_block: int = 128,
    with_info: bool = False,
):
    """Banded EAPrunedDTW of Q queries against their own candidate rounds.

    The flattened ``(Q × K)`` lane set is evaluated in one dispatch: one
    Pallas launch with a query-block grid dimension, or one nested-vmap JAX
    program — no per-query launches or recompiles.

    Args:
      queries: ``(Q, m)`` z-normalized queries (multivariate multi-query is
        not supported — route per query through ``ea_pruned_dtw_batch``).
      candidates: ``(Q, K, m)`` candidate windows per query.
      ub: per-lane upper bounds — scalar, ``(Q, 1)`` or ``(Q, K)``
        (broadcast to ``(Q, K)``). Negative entries are dead-lane sentinels:
        those lanes abandon on row 0 (how finished queries ride along).
      window, band_width, cb, rows_per_step, backend, block_k, row_block,
        with_info: as in ``ea_pruned_dtw_batch`` (``cb`` is ``(Q, K, m)``).

    Returns: ``(Q, K)`` distances (``+inf`` where abandoned); with
      ``with_info`` a ``(distances, EAInfo)`` tuple of ``(Q, K)`` arrays.
    """
    guards.check_batch_args(queries, candidates, ub, window, cb=cb, multi=True)
    resolved = resolve_backend(backend)
    if resolved == "jax":
        return _multi_jax(
            queries, candidates, ub, window, band_width, cb, rows_per_step,
            with_info,
        )
    interpret = resolved == "pallas_interpret"
    out = _kernel_ops().dtw_ea_multi(
        queries, candidates, ub, window, cb=cb, band_width=band_width,
        block_k=block_k, row_block=row_block, interpret=interpret,
        with_info=with_info,
    )
    if with_info:
        d, rows, cells = out
        return d, EAInfo(rows=rows, cells=cells)
    return out


@partial(
    jax.jit,
    static_argnames=(
        "window", "length", "band_width", "rows_per_step", "with_info",
        "use_cb",
    ),
)
def _multi_jax_fused(
    queries, ref, starts, mu_l, sg_l, ub, u, low, window, length,
    band_width, rows_per_step, with_info, use_cb,
):
    """Fused-gather ``_multi_jax``: slice + normalize inside the round body.

    Per query, the candidate tile is built by vmapped ``dynamic_slice`` of
    the resident reference and normalized in place of arriving as a
    pre-gathered operand; with ``use_cb`` the cb suffix is computed from the
    just-built tile (per-lane sequential cumsum — bit-identical to the
    gathered jax path). The CPU ``lax.map`` + dead-query ``cond`` structure
    mirrors ``_multi_jax``, so a finished query skips its gather too.
    """
    ub_lanes = jnp.broadcast_to(jnp.asarray(ub), starts.shape)

    def _mapped(fn, ops):
        if with_info:
            return jax.lax.map(lambda t: fn(*t), ops)
        out_sd = jax.eval_shape(fn, *jax.tree.map(lambda x: x[0], ops))

        def dead():
            return jax.tree.map(
                lambda sd: jnp.full(sd.shape, jnp.inf, sd.dtype), out_sd
            )

        return jax.lax.map(
            lambda t: jax.lax.cond(
                jnp.any(t[4] >= 0), lambda: fn(*t), dead
            ),
            ops,
        )

    def fn(q, sq, muq, sgq, us, uq, lowq):
        c = _slice_norm(ref, sq, length, muq, sgq)
        cb = None
        if use_cb:
            cb = jax.vmap(
                lambda cc: cascade_keogh_cumulative(cc, uq, lowq)
            )(c)
        return _batch_jax(
            q, c, us, window, band_width, cb, rows_per_step, with_info
        )

    ops_t = (queries, starts, mu_l, sg_l, ub_lanes, u, low)
    if jax.default_backend() == "cpu":
        return _mapped(fn, ops_t)
    return jax.vmap(fn)(*ops_t)


def ea_pruned_dtw_multi_batch_fused(
    queries: jax.Array,
    ref: jax.Array,
    starts: jax.Array,
    ub: jax.Array,
    window: int,
    mu: jax.Array,
    sigma: jax.Array,
    envelopes: tuple[jax.Array, jax.Array] | None = None,
    band_width: int | None = None,
    rows_per_step: int = 1,
    backend: str | None = None,
    block_k: int = 8,
    row_block: int = 128,
    with_info: bool = False,
):
    """Fused-gather ``ea_pruned_dtw_multi_batch``: no candidate slab.

    Candidate windows are described, not materialized: the raw (sanitized)
    reference rides in once per dispatch and each lane carries
    ``(start, mu, sigma)``. Slicing + z-normalization happen inside the
    kernel (Pallas) or inside the jitted round body (jax) — results are
    bit-identical to gathering with ``gather_norm_windows`` first, because
    the copies, ``clamp_sigma``, and op order are the same.

    Args (where they differ from ``ea_pruned_dtw_multi_batch``):
      ref: ``(N,)`` raw (sanitized) reference series.
      starts: ``(Q, K)`` int32 window start per lane.
      mu, sigma: full ``(N_win,)`` per-window stats tables
        (``znorm.window_stats``); indexed by ``starts`` here — ``sigma`` is
        raw, the clamp is applied at this boundary.
      envelopes: optional ``(u, low)`` pair of ``(Q, m)`` query envelopes —
        enables UCR ``cb`` tightening, computed from the fused tile (the
        Pallas round kernel builds it in-kernel with a tree-order suffix
        sum: the documented O(1)-ulp reformulation; the jax path is
        bit-identical to the gathered jax path).

    Returns: as ``ea_pruned_dtw_multi_batch``.
    """
    if jnp.ndim(queries) != 2:
        raise guards.SearchInputError(
            "fused multi batch requires (Q, m) univariate queries"
        )
    length = int(queries.shape[1])
    starts = jnp.asarray(starts, jnp.int32)
    mu_l = jnp.asarray(mu)[starts]
    sg_l = clamp_sigma(jnp.asarray(sigma))[starts]
    use_cb = envelopes is not None
    u, low = envelopes if use_cb else (None, None)
    resolved = resolve_backend(backend)
    if resolved == "jax":
        nq, m = queries.shape
        dt = queries.dtype
        if u is None:
            u_arr = jnp.zeros((nq, m), dt)
            low_arr = jnp.zeros((nq, m), dt)
        else:
            u_arr, low_arr = jnp.asarray(u, dt), jnp.asarray(low, dt)
        return _multi_jax_fused(
            queries, ref, starts, mu_l, sg_l, ub, u_arr, low_arr, window,
            length, band_width, rows_per_step, with_info, use_cb,
        )
    interpret = resolved == "pallas_interpret"
    out = _kernel_ops().dtw_ea_multi_fused(
        queries, ref, starts, mu_l, sg_l, ub, window, length,
        u=u, low=low, use_cb=use_cb, band_width=band_width,
        block_k=block_k, row_block=row_block, interpret=interpret,
        with_info=with_info,
    )
    if with_info:
        d, rows, cells = out
        return d, EAInfo(rows=rows, cells=cells)
    return out


def block_sweep(cand, lb, starts, ub0, block_k, block_fn):
    """Best-first sweep over ``block_k``-lane candidate blocks, carried ub.

    The host-side equivalent of the persistent kernel's sequential candidate
    grid dimension (DESIGN.md §2.5), shared by every driver that needs the
    block-granular loop: carried incumbent as loop state, the on-device
    cascade stop as the loop condition. Because lower bounds arrive sorted
    and the incumbent is non-increasing, the first gated block implies every
    later block is gated too, so exiting there visits exactly the blocks
    the kernel runs (a gated block on the kernel side is a no-op, here it
    is the loop exit). Incumbent updates are strict-improvement with
    first-lane tie-breaking — the one copy of that rule on the host side.

    Args:
      cand: ``(K_pad, m)`` candidate windows, ascending-``lb`` order,
        ``K_pad`` a multiple of ``block_k``.
      lb: ``(K_pad,)`` sorted lower bounds (``+inf`` padding lanes).
      starts: ``(K_pad,)`` global start per lane.
      ub0: scalar initial incumbent.
      block_fn: ``(cand_block, lb_block, ub) -> (block_k,)`` distances for
        one block (``+inf`` = abandoned; padding lanes are masked here).

    Returns ``(ub, best, blocks)`` scalars.
    """
    k_pad, m = cand.shape
    n_blocks = k_pad // block_k

    class St(NamedTuple):
        b: jax.Array     # next block index
        ub: jax.Array    # carried incumbent
        best: jax.Array  # carried best start

    def cond(st: St) -> jax.Array:
        head = jax.lax.dynamic_slice(
            lb, (jnp.minimum(st.b, n_blocks - 1) * block_k,), (1,)
        )[0]
        return jnp.logical_and(st.b < n_blocks, head < st.ub)

    def body(st: St) -> St:
        o = st.b * block_k
        c = jax.lax.dynamic_slice(cand, (o, jnp.zeros_like(o)), (block_k, m))
        lbb = jax.lax.dynamic_slice(lb, (o,), (block_k,))
        ss = jax.lax.dynamic_slice(starts, (o,), (block_k,))
        d = block_fn(c, lbb, st.ub)
        d = jnp.where(jnp.isfinite(lbb), d, jnp.inf)  # padding lanes
        j = jnp.argmin(d)
        dmin = d[j]
        improved = dmin < st.ub  # strict: ties keep the incumbent
        return St(
            b=st.b + 1,
            ub=jnp.where(improved, dmin, st.ub),
            best=jnp.where(improved, ss[j], st.best),
        )

    st0 = St(
        b=jnp.asarray(0, jnp.int32),
        ub=jnp.asarray(ub0),
        best=jnp.asarray(-1, starts.dtype),
    )
    st = jax.lax.while_loop(cond, body, st0)
    return st.ub, st.best, st.b


@partial(
    jax.jit,
    static_argnames=(
        "window", "band_width", "rows_per_step", "block_k", "use_cb"
    ),
)
def _persistent_jax(
    queries, candidates, lb, starts, ub_init, u, low, window, band_width,
    rows_per_step, block_k, use_cb,
):
    """JAX-backend persistent sweep: ``block_sweep`` per query.

    Per-lane arithmetic is ``_batch_jax`` — identical to the host round
    driver's jax backend, so surviving distances are bit-equal.
    """

    def one(q, cand, lbq, sq, ub0, uq, lowq):
        def block_fn(c, lbb, ub):
            cb = None
            if use_cb:
                cb = cascade_keogh_cumulative(c, uq, lowq)
            # Lane gating: a lane whose own bound reaches the incumbent is
            # submitted dead (same sentinel the kernel writes).
            ubl = jnp.where(lbb < ub, ub, DEAD_LANE_UB)
            return _batch_jax(
                q, c, ubl, window, band_width, cb, rows_per_step, False
            )

        return block_sweep(
            cand, lbq, sq, jnp.asarray(ub0, queries.dtype), block_k, block_fn
        )

    ops = (queries, candidates, lb, starts, ub_init, u, low)
    if jax.default_backend() == "cpu":
        # Per-query trip counts (see _multi_jax on why lax.map here).
        return jax.lax.map(lambda t: one(*t), ops)
    return jax.vmap(one)(*ops)


def ea_pruned_dtw_persistent(
    queries: jax.Array,
    candidates: jax.Array,
    lb: jax.Array,
    starts: jax.Array,
    ub_init: jax.Array,
    window: int,
    band_width: int | None = None,
    envelopes: tuple[jax.Array, jax.Array] | None = None,
    rows_per_step: int = 1,
    backend: str | None = None,
    block_k: int = 8,
    row_block: int = 128,
):
    """Persistent best-first EAPrunedDTW: the whole sweep in one dispatch.

    The round primitives (``ea_pruned_dtw_batch`` / ``_multi_batch``) leave
    incumbent tightening to their caller — one argmin + ``ub`` update per
    dispatched round. This primitive internalizes the loop: candidates for
    the *entire* best-first order come in at once, and the incumbent is
    carried across ``block_k``-lane candidate blocks inside a single
    dispatch (the Pallas kernel's sequential grid dimension with ``ub`` in
    SMEM, or one jitted while_loop on the jax backend). Tightening happens
    every ``block_k`` lanes instead of every ``batch`` lanes, and blocks
    whose lower bounds cannot beat the carried incumbent never run.

    Args:
      queries: ``(Q, m)`` z-normalized queries.
      candidates: ``(Q, K, m)`` windows in ascending-``lb`` order per query.
      lb: ``(Q, K)`` sorted lower bounds; ``+inf`` marks padding lanes. Pass
        zeros (with ``+inf`` padding) for the no-cascade variant — gating
        then never skips a live block, and the sweep visits all of them.
      starts: ``(Q, K)`` global window start per lane.
      ub_init: ``(Q,)`` incumbent seeds (``BIG`` cold).
      envelopes: optional ``(u, low)`` pair of ``(Q, m)`` query envelopes —
        enables UCR ``cb`` threshold tightening, computed per block inside
        the sweep (no precomputed ``(Q, K, m)`` cb slab exists anywhere).
      window, band_width, rows_per_step, backend, block_k, row_block: as in
        ``ea_pruned_dtw_multi_batch``.

    Returns: ``(best_dist, best_start, blocks)`` — ``(Q,)`` each; ``blocks``
      counts candidate blocks actually evaluated (the work metric; the
      dispatch count is 1 by construction).
    """
    if jnp.ndim(queries) != 2:
        raise ValueError("persistent sweep requires (Q, m) univariate queries")
    use_cb = envelopes is not None
    u, low = envelopes if use_cb else (None, None)
    resolved = resolve_backend(backend)
    if resolved == "jax":
        nq, m = queries.shape
        dt = queries.dtype
        lb_arr, starts_arr, candidates = pad_lanes_to_blocks(
            block_k, jnp.asarray(lb, dt), jnp.asarray(starts), candidates
        )
        if u is None:
            u_arr = jnp.zeros((nq, m), dt)
            low_arr = jnp.zeros((nq, m), dt)
        else:
            u_arr, low_arr = jnp.asarray(u, dt), jnp.asarray(low, dt)
        return _persistent_jax(
            queries, candidates, lb_arr, starts_arr,
            jnp.asarray(ub_init, dt), u_arr, low_arr,
            window, band_width, rows_per_step, block_k, use_cb,
        )
    interpret = resolved == "pallas_interpret"
    return _kernel_ops().dtw_ea_persistent(
        queries, candidates, lb, starts, ub_init, window, u=u, low=low,
        use_cb=use_cb, band_width=band_width, block_k=block_k,
        row_block=row_block, interpret=interpret,
    )


def block_sweep_fused(lb, starts, mu_l, sg_l, ub0, block_k, block_fn):
    """``block_sweep`` without the candidate matrix: lanes are descriptors.

    The while_loop state and stop condition are identical to
    ``block_sweep``; the body slices the per-block ``(starts, mu, sigma)``
    descriptors instead of a ``(K_pad, m)`` window matrix and hands them to
    ``block_fn(starts_b, mu_b, sg_b, lb_b, ub)``, which materializes the
    O(block_k · length) tile itself — the jax-backend analogue of the
    persistent kernel's in-kernel gather. Nothing O(K·m) exists at any
    point of the sweep.
    """
    k_pad = lb.shape[0]
    n_blocks = k_pad // block_k

    class St(NamedTuple):
        b: jax.Array     # next block index
        ub: jax.Array    # carried incumbent
        best: jax.Array  # carried best start

    def cond(st: St) -> jax.Array:
        head = jax.lax.dynamic_slice(
            lb, (jnp.minimum(st.b, n_blocks - 1) * block_k,), (1,)
        )[0]
        return jnp.logical_and(st.b < n_blocks, head < st.ub)

    def body(st: St) -> St:
        o = st.b * block_k
        lbb = jax.lax.dynamic_slice(lb, (o,), (block_k,))
        sb = jax.lax.dynamic_slice(starts, (o,), (block_k,))
        mub = jax.lax.dynamic_slice(mu_l, (o,), (block_k,))
        sgb = jax.lax.dynamic_slice(sg_l, (o,), (block_k,))
        d = block_fn(sb, mub, sgb, lbb, st.ub)
        d = jnp.where(jnp.isfinite(lbb), d, jnp.inf)  # padding lanes
        j = jnp.argmin(d)
        dmin = d[j]
        improved = dmin < st.ub  # strict: ties keep the incumbent
        return St(
            b=st.b + 1,
            ub=jnp.where(improved, dmin, st.ub),
            best=jnp.where(improved, sb[j], st.best),
        )

    st0 = St(
        b=jnp.asarray(0, jnp.int32),
        ub=jnp.asarray(ub0),
        best=jnp.asarray(-1, starts.dtype),
    )
    st = jax.lax.while_loop(cond, body, st0)
    return st.ub, st.best, st.b


@partial(
    jax.jit,
    static_argnames=(
        "window", "length", "band_width", "rows_per_step", "block_k",
        "use_cb",
    ),
)
def _persistent_jax_fused(
    queries, ref, lb, starts, mu_l, sg_l, ub_init, u, low, window, length,
    band_width, rows_per_step, block_k, use_cb,
):
    """JAX-backend fused persistent sweep: gather per block, in the loop.

    The slab form (``_persistent_jax``) receives the full candidate matrix
    even though the sweep visits blocks sequentially; here each while_loop
    step slices + normalizes only its own ``block_k`` windows out of the
    resident reference — O(N + block_k·m) live at any point, matching the
    fused kernel. Per-lane arithmetic is still ``_batch_jax``, so surviving
    distances stay bit-equal to the slab form.
    """

    def one(q, lbq, sq, muq, sgq, ub0, uq, lowq):
        def block_fn(sb, mub, sgb, lbb, ub):
            c = _slice_norm(ref, sb, length, mub, sgb)
            cb = None
            if use_cb:
                cb = cascade_keogh_cumulative(c, uq, lowq)
            # Lane gating: a lane whose own bound reaches the incumbent is
            # submitted dead (same sentinel the kernel writes).
            ubl = jnp.where(lbb < ub, ub, DEAD_LANE_UB)
            return _batch_jax(
                q, c, ubl, window, band_width, cb, rows_per_step, False
            )

        return block_sweep_fused(
            lbq, sq, muq, sgq, jnp.asarray(ub0, queries.dtype), block_k,
            block_fn,
        )

    ops = (queries, lb, starts, mu_l, sg_l, ub_init, u, low)
    if jax.default_backend() == "cpu":
        # Per-query trip counts (see _multi_jax on why lax.map here).
        return jax.lax.map(lambda t: one(*t), ops)
    return jax.vmap(one)(*ops)


def ea_pruned_dtw_persistent_fused(
    queries: jax.Array,
    ref: jax.Array,
    lb: jax.Array,
    starts: jax.Array,
    ub_init: jax.Array,
    window: int,
    mu: jax.Array,
    sigma: jax.Array,
    envelopes: tuple[jax.Array, jax.Array] | None = None,
    band_width: int | None = None,
    rows_per_step: int = 1,
    backend: str | None = None,
    block_k: int = 8,
    row_block: int = 128,
):
    """Fused-gather persistent sweep: whole search, O(N + K) operands.

    ``ea_pruned_dtw_persistent`` without the O(K·m) best-first window
    matrix: lanes arrive as ``(start, lb)`` descriptors plus the O(N)
    stats tables, and each visited block's tile is materialized inside the
    sweep (in-kernel on Pallas, inside the while_loop body on jax). This is
    the form that completes sweeps over references whose window slab could
    never be allocated.

    Args (where they differ from ``ea_pruned_dtw_persistent``):
      ref: ``(N,)`` raw (sanitized) reference series.
      mu, sigma: full ``(N_win,)`` per-window stats tables (``sigma`` raw;
        clamped at this boundary).

    Returns: ``(best_dist, best_start, blocks)`` — as the slab form.
    """
    if jnp.ndim(queries) != 2:
        raise ValueError("persistent sweep requires (Q, m) univariate queries")
    length = int(queries.shape[1])
    use_cb = envelopes is not None
    u, low = envelopes if use_cb else (None, None)
    dt = queries.dtype
    lb_arr, starts_arr, _ = pad_lanes_to_blocks(
        block_k, jnp.asarray(lb, dt), jnp.asarray(starts, jnp.int32)
    )
    mu_l = jnp.asarray(mu, dt)[starts_arr]
    sg_l = clamp_sigma(jnp.asarray(sigma, dt))[starts_arr]
    resolved = resolve_backend(backend)
    if resolved == "jax":
        nq, m = queries.shape
        if u is None:
            u_arr = jnp.zeros((nq, m), dt)
            low_arr = jnp.zeros((nq, m), dt)
        else:
            u_arr, low_arr = jnp.asarray(u, dt), jnp.asarray(low, dt)
        return _persistent_jax_fused(
            queries, ref, lb_arr, starts_arr, mu_l, sg_l,
            jnp.asarray(ub_init, dt), u_arr, low_arr,
            window, length, band_width, rows_per_step, block_k, use_cb,
        )
    interpret = resolved == "pallas_interpret"
    return _kernel_ops().dtw_ea_persistent_fused(
        queries, ref, lb_arr, starts_arr, mu_l, sg_l, ub_init, window,
        length, u=u, low=low, use_cb=use_cb, band_width=band_width,
        block_k=block_k, row_block=row_block, interpret=interpret,
    )


@partial(
    jax.jit,
    static_argnames=(
        "window", "band_width", "rows_per_step", "backend", "block_k",
        "row_block",
    ),
)
def _ea_search_round_impl(
    query, candidates, ub, best_idx, cand_idx, window, band_width, cb,
    rows_per_step, backend, block_k, row_block,
):
    d = ea_pruned_dtw_batch(
        query, candidates, ub, window, band_width, cb,
        rows_per_step=rows_per_step, backend=backend, block_k=block_k,
        row_block=row_block,
    )
    k = jnp.argmin(d)
    dmin = d[k]
    improved = dmin < ub
    new_ub = jnp.where(improved, dmin, ub)
    new_best = jnp.where(improved, cand_idx[k], best_idx)
    return new_ub, new_best


def ea_search_round(
    query: jax.Array,
    candidates: jax.Array,
    ub: jax.Array,
    best_idx: jax.Array,
    cand_idx: jax.Array,
    window: int,
    band_width: int | None = None,
    cb: jax.Array | None = None,
    rows_per_step: int = 1,
    backend: str | None = None,
    block_k: int = 8,
    row_block: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """One search round: batch EAPrunedDTW + incumbent update.

    ``cand_idx`` carries the global index of each candidate (for argmin
    bookkeeping across rounds). Returns updated ``(ub, best_idx)``. Ties keep
    the incumbent (strict improvement only), matching the paper's strictness
    rule for early abandoning.

    The backend is resolved here, outside jit, so ``$REPRO_DTW_BACKEND`` is
    re-read on every call and becomes the static ``backend`` argument of the
    jitted round (changing the env var between calls correctly retraces).
    """
    return _ea_search_round_impl(
        query, candidates, ub, best_idx, cand_idx, window, band_width, cb,
        rows_per_step, resolve_backend(backend), block_k, row_block,
    )
