"""Jitted public wrappers for the Pallas kernels.

``interpret=False`` (the default) lowers through Mosaic for the TPU;
``interpret=True`` runs the kernel body in Python, the CPU test path. No
wrapper picks interpret mode on its own.

Backend dispatch: ``dtw_ea`` / ``dtw_ea_multi`` are the Pallas side of the
``core.backend`` dispatch layer — similarity search reaches them through
``core.batch.ea_pruned_dtw_batch`` / ``ea_pruned_dtw_multi_batch`` with
``backend="pallas"|"pallas_interpret"`` rather than calling them directly.
``backend="pallas"`` lowers through Mosaic and exists only on the TPU;
``"pallas_interpret"`` runs interpret mode on any platform (the CPU
test/CI path).

Lane layout (multi-query): ``dtw_ea_multi`` evaluates a flattened
``(Q × K)`` lane set in one launch. Candidates are reshaped to
``(Q * k_pad, tile)`` query-major, the grid is
``(Q, cand_blocks, row_blocks)``, and each grid program's ``block_k`` lanes
all belong to one query — the query/envelope tile is selected by the
leading grid index while ``ub`` rides along as a per-lane
``(block_k, 1)`` VMEM vector. Scalar ``ub`` broadcasts to every lane;
padding lanes (``K`` rounded up to ``block_k``) get a ``-1`` sentinel so
they abandon on their first row and never delay a block's early exit.

Block forms (the ones Mosaic accepts, see ``kernels.dtw_band``): query
rows arrive as SMEM scalars (``_query_rows``), window starts as
``(1, block_k)`` SMEM blocks (``_lane_starts``), query envelopes as
``(1, m)`` rows of ``(Q, 1, m)`` arrays, per-lane results leave as
``(block_k, 1)`` blocks and per-query results as ``(1, 128)`` rows.

The banded column mode (``band_width``) mirrors
``core.ea_pruned_dtw.ea_pruned_dtw_banded``: ``band_width=None`` picks the
smallest lane-aligned width covering ``2*window + 1`` columns; band mode
requires ``n == m`` (subsequence-search shape) and silently widens to full
rows otherwise. ``with_info=True`` additionally returns per-lane
``(rows, cells)`` pruning counters (``EAInfo`` semantics) at the cost of two
int32 accumulators per lane — the search fast round runs counter-free.

Fused operand form (DESIGN.md §2.10, the ``gather="fused"`` default):
``dtw_ea_multi_fused`` / ``dtw_ea_persistent_fused`` take the raw reference
series once plus per-lane ``(start, mu, sigma)`` vectors and slice +
z-normalize each block's windows inside the kernel — no pre-gathered
``(Q, K, m)`` slab crosses the host→device boundary. The reference stays in
HBM (``memory_space=ANY``) at every size and the kernel DMAs each lane's
window. The slab-form wrappers remain as the ``gather="slab"`` comparison arm and the
baseline cores' entry point.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.common import (
    DEAD_LANE_UB,
    default_band_width,
    pad_lanes_to_blocks,
)
from repro.kernels.dtw_band import (
    LANES,
    _dtw_ea_fused_kernel,
    _dtw_ea_kernel,
    _dtw_ea_persistent_kernel,
    cand_tile_width,
    cb_tile_width,
    span_width,
)
from repro.kernels.lb_keogh import _lb_kernel


def _band(window: int, n: int, m: int, band_width: int | None):
    """Clamp the window; resolve and validate the static band width."""
    window = int(min(window, m))
    if band_width is None:
        band_width = default_band_width(window, m) if n == m else m
    bw = int(min(band_width, m))
    full = min(2 * window + 1, m)
    if bw < full:
        raise ValueError(f"band_width {bw} < 2*window+1 = {full}")
    if bw < m and n != m:
        raise ValueError("banded dtw_ea requires equal lengths (n == m)")
    return window, bw


def _query_rows(queries: jax.Array, row_block: int):
    """Queries as SMEM row blocks, plus their ``BlockSpec``.

    ``(Q, n)`` is padded to whole row blocks and reshaped to
    ``(Q * row_blocks, 1, row_block)``; grid step ``(qi, ci, ri)`` sees
    ``(1, row_block)`` and reads DP row ``r`` as the scalar ``q_ref[0, r]``.
    """
    nq, n = queries.shape
    n_pad = -(-n // row_block) * row_block
    if n_pad != n:
        queries = jnp.pad(queries, ((0, 0), (0, n_pad - n)))
    nrb = n_pad // row_block
    spec = pl.BlockSpec(
        (None, 1, row_block),
        lambda qi, ci, ri: (qi * nrb + ri, 0, 0),
        memory_space=pltpu.SMEM,
    )
    return queries.reshape(nq * nrb, 1, row_block), spec, nrb


def _lane_starts(starts: jax.Array, block_k: int, ncb: int):
    """``(Q, k_pad)`` window starts as ``(1, block_k)`` SMEM blocks."""
    blocks = starts.reshape(-1, 1, block_k)
    spec = pl.BlockSpec(
        (None, 1, block_k),
        lambda qi, ci, ri: (qi * ncb + ci, 0, 0),
        memory_space=pltpu.SMEM,
    )
    return blocks, spec


def _lane_spec(block_k: int, ncb: int, width: int = 1) -> pl.BlockSpec:
    """Block ``(qi, ci)`` of a query-major ``(Q * k_pad, width)`` array."""
    return pl.BlockSpec((block_k, width), lambda qi, ci, ri: (qi * ncb + ci, 0))


def _query_spec(width: int) -> pl.BlockSpec:
    """Row ``qi`` of a ``(Q, 1, width)`` per-query array, seen as
    ``(1, width)`` (a ``(1, width)`` block of ``(Q, width)`` is refused)."""
    return pl.BlockSpec((None, 1, width), lambda qi, ci, ri: (qi, 0, 0))


def _pad_cols(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad the last axis to ``width`` columns."""
    extra = width - x.shape[-1]
    if extra == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def padded_ref_len(n: int, m: int) -> int:
    """Length of the padded ``(1, N_pad)`` reference row.

    Long enough that the 128-aligned span covering any window
    ``[s, s + m)``, ``s <= n - m``, lies inside the row.
    """
    return max(-(-n // LANES) * LANES, (n - m) // LANES * LANES + span_width(m))


def _ref_operand(ref: jax.Array, m: int):
    """The reference as a padded ``(1, N_pad)`` row (TPU wants 2-D) and its
    spec: left in HBM, read only by the kernels' window DMAs."""
    ref = jnp.asarray(ref, jnp.float32)
    n = ref.shape[0]
    ref = jnp.pad(ref, (0, padded_ref_len(n, m) - n))[None, :]
    return ref, pl.BlockSpec(memory_space=pl.ANY)


def _gather_scratch(block_k: int, m: int, bw: int):
    """Candidate tile and aligned-span staging scratch of the fused kernels."""
    return [
        pltpu.VMEM((block_k, cand_tile_width(m, bw)), jnp.float32),
        pltpu.VMEM((block_k, 1, span_width(m)), jnp.float32),
    ]


def _dma_sems(block_k: int):
    """One DMA semaphore per lane of a block (``_gather_norm_block``)."""
    return pltpu.SemaphoreType.DMA((block_k,))


def _envelopes(u, low, nq: int, m: int):
    """Query envelopes as ``(Q, 1, m)`` operands (zeros when unused)."""
    if u is None:
        zeros = jnp.zeros((nq, 1, m), jnp.float32)
        return zeros, zeros
    as3 = lambda e: jnp.asarray(e, jnp.float32).reshape(nq, 1, m)
    return as3(u), as3(low)


def _per_lane(out, nq: int, k_pad: int, k: int, with_info: bool):
    """``(Q * k_pad, 1)`` kernel outputs back to ``(Q, K)``."""
    unpad = lambda a: a.reshape(nq, k_pad)[:, :k]
    if with_info:
        return tuple(unpad(a) for a in out)
    return unpad(out)


def _per_lane_outputs(block_k: int, ncb: int, rows: int, with_info: bool):
    """Distances (and ``rows``/``cells`` counters) as ``(block_k, 1)``
    blocks of ``(Q * k_pad, 1)`` arrays."""
    specs = [_lane_spec(block_k, ncb)]
    shapes = [jax.ShapeDtypeStruct((rows, 1), jnp.float32)]
    if with_info:
        specs += [_lane_spec(block_k, ncb)] * 2
        shapes += [jax.ShapeDtypeStruct((rows, 1), jnp.int32)] * 2
        return specs, shapes
    return specs[0], shapes[0]


def _per_query_outputs(nq: int):
    """``(best_dist, best_start, blocks)`` as ``(Q, 1, 128)`` rows."""
    spec = pl.BlockSpec((None, 1, LANES), lambda qi, ci, ri: (qi, 0, 0))
    shapes = [
        jax.ShapeDtypeStruct((nq, 1, LANES), dt)
        for dt in (jnp.float32, jnp.int32, jnp.int32)
    ]
    return [spec] * 3, shapes


@partial(
    jax.jit,
    static_argnames=(
        "window", "band_width", "block_k", "row_block", "interpret", "with_info"
    ),
)
def dtw_ea_multi(
    queries: jax.Array,
    candidates: jax.Array,
    ub: jax.Array,
    window: int,
    cb: jax.Array | None = None,
    band_width: int | None = None,
    block_k: int = 8,
    row_block: int = 128,
    interpret: bool = False,
    with_info: bool = False,
):
    """Multi-query batched EAPrunedDTW: one launch, ``Q × K`` lanes.

    Args:
      queries: ``(Q, n)`` z-normalized queries (rows of the DP).
      candidates: ``(Q, K, m)`` candidate windows per query.
      ub: per-lane upper bounds — scalar, ``(Q, 1)`` or ``(Q, K)``
        (broadcast to ``(Q, K)``). Lanes abandon against their own value; a
        negative entry kills its lane on row 0 (finished-query sentinel).
      window: Sakoe-Chiba window shared by all queries (``>= m`` for
        unconstrained).
      cb: optional ``(Q, K, m)`` cumulative LB_Keogh suffix sums (UCR
        tightening); ``None`` disables.
      band_width: static band columns per row. ``None`` picks the smallest
        lane-aligned width covering ``2*window + 1`` (full width when
        ``n != m`` — band mode needs the square subsequence-search shape).
      block_k: candidate lanes per grid block (a parallel grid dim).
      row_block: DP rows per sequential grid step (early-exit granularity).
      interpret: run the kernel body in Python (CPU tests) instead of
        lowering through Mosaic.
      with_info: also return per-lane ``(rows, cells)`` int32 counters.
    Returns: ``(Q, K)`` float32 distances, ``+inf`` where abandoned; with
      ``with_info`` a ``(dists, rows, cells)`` tuple of ``(Q, K)`` arrays.
    """
    queries = jnp.asarray(queries, jnp.float32)
    candidates = jnp.asarray(candidates, jnp.float32)
    nq, n = queries.shape
    q_, k, m = candidates.shape
    assert q_ == nq, (q_, nq)
    window, bw = _band(window, n, m, band_width)

    use_cb = cb is not None
    if cb is None:
        cb_arr = jnp.zeros((nq, k, m), jnp.float32)
    else:
        cb_arr = jnp.asarray(cb, jnp.float32)

    k_pad = -(-k // block_k) * block_k
    ub_arr = jnp.broadcast_to(jnp.asarray(ub, jnp.float32), (nq, k))
    if k_pad != k:
        candidates = jnp.pad(candidates, ((0, 0), (0, k_pad - k), (0, 0)))
        cb_arr = jnp.pad(cb_arr, ((0, 0), (0, k_pad - k), (0, 0)))
        ub_arr = jnp.pad(
            ub_arr, ((0, 0), (0, k_pad - k)), constant_values=DEAD_LANE_UB
        )
    q_rows, q_spec, nrb = _query_rows(queries, row_block)

    ncb = k_pad // block_k
    grid = (nq, ncb, nrb)
    tile = cand_tile_width(m, bw)
    cb_w = cb_tile_width(m)
    # query-major flattened lane set: block row qi * ncb + ci
    cand_flat = _pad_cols(candidates.reshape(nq * k_pad, m), tile)
    cb_flat = _pad_cols(cb_arr.reshape(nq * k_pad, m), cb_w)
    ub_flat = ub_arr.reshape(nq * k_pad, 1)

    kernel = partial(
        _dtw_ea_kernel,
        n_rows=n,
        m=m,
        window=window,
        row_block=row_block,
        band_width=bw,
        use_cb=use_cb,
        emit_info=with_info,
    )
    out_specs, out_shape = _per_lane_outputs(
        block_k, ncb, nq * k_pad, with_info
    )
    out = pl.pallas_call(
        kernel,
        name="dtw_ea_multi",
        grid=grid,
        in_specs=[
            _lane_spec(block_k, ncb),
            q_spec,
            _lane_spec(block_k, ncb, tile),
            _lane_spec(block_k, ncb, cb_w),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_k, bw), jnp.float32),
            pltpu.VMEM((block_k, 1), jnp.int32),
            pltpu.VMEM((block_k, 2), jnp.int32),
            pltpu.VMEM((block_k, 1), jnp.int32),
            pltpu.VMEM((block_k, 1), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        ub_flat,
        q_rows,
        cand_flat,
        cb_flat,
    )
    return _per_lane(out, nq, k_pad, k, with_info)


def dtw_ea(
    query: jax.Array,
    candidates: jax.Array,
    ub: jax.Array,
    window: int,
    cb: jax.Array | None = None,
    band_width: int | None = None,
    block_k: int = 8,
    row_block: int = 128,
    interpret: bool = False,
    with_info: bool = False,
):
    """Single-query batched EAPrunedDTW — ``dtw_ea_multi`` with ``Q = 1``.

    Args:
      query: ``(n,)`` z-normalized query (rows of the DP).
      candidates: ``(K, m)`` candidate windows (columns of the DP).
      ub: scalar upper bound shared by every lane, or a ``(K,)`` per-lane
        vector.
      window, cb, band_width, block_k, row_block, interpret, with_info: as
        in ``dtw_ea_multi`` (``cb`` is ``(K, m)`` here).
    Returns: ``(K,)`` float32 distances, ``+inf`` where abandoned; with
      ``with_info`` a ``(dists, rows, cells)`` tuple.
    """
    ub = jnp.asarray(ub, jnp.float32)
    out = dtw_ea_multi(
        jnp.asarray(query)[None],
        jnp.asarray(candidates)[None],
        ub[None] if ub.ndim == 1 else ub,
        window,
        cb=None if cb is None else jnp.asarray(cb)[None],
        band_width=band_width,
        block_k=block_k,
        row_block=row_block,
        interpret=interpret,
        with_info=with_info,
    )
    if with_info:
        d, rows, cells = out
        return d[0], rows[0], cells[0]
    return out[0]


@partial(
    jax.jit,
    static_argnames=(
        "window", "length", "use_cb", "band_width", "block_k", "row_block",
        "interpret", "with_info",
    ),
)
def dtw_ea_multi_fused(
    queries: jax.Array,
    ref: jax.Array,
    starts: jax.Array,
    mu: jax.Array,
    sg: jax.Array,
    ub: jax.Array,
    window: int,
    length: int,
    u: jax.Array | None = None,
    low: jax.Array | None = None,
    use_cb: bool = False,
    band_width: int | None = None,
    block_k: int = 8,
    row_block: int = 128,
    interpret: bool = False,
    with_info: bool = False,
):
    """Fused-gather ``dtw_ea_multi``: windows sliced + normalized in-kernel.

    Same DP program and return contract as ``dtw_ea_multi``, but the
    candidate operand is the raw reference series (resident once, O(N))
    plus per-lane ``(start, mu, sigma)`` vectors — the kernel materializes
    each block's normalized tile into VMEM scratch, so no O(Q·K·m) window
    slab is built on the host or shipped to the device. With ``use_cb`` the
    UCR ``cb`` suffix is likewise built in-kernel from the query envelopes
    (tree-order suffix sum — the documented O(1)-ulp reformulation vs the
    host drivers' sequential cumsum; thresholds may shift by an ulp, the
    winner cannot change).

    Args (where they differ from ``dtw_ea_multi``):
      ref: ``(N,)`` raw (sanitized) reference series, shared by all lanes.
      starts: ``(Q, K)`` int32 window start per lane (in ``[0, N - length]``;
        padding lanes may repeat any valid start).
      mu, sg: ``(Q, K)`` per-lane window mean and **pre-clamped** sigma
        (``clamp_sigma`` applied by the caller — the kernel divides as-is,
        keeping flat-window output bit-identical to the retired slab).
      length: static candidate window length ``m``.
      u, low: ``(Q, m)`` query envelopes — required when ``use_cb``.
    """
    queries = jnp.asarray(queries, jnp.float32)
    starts = jnp.asarray(starts, jnp.int32)
    nq, n = queries.shape
    q_, k = starts.shape
    assert q_ == nq, (q_, nq)
    m = int(length)
    window, bw = _band(window, n, m, band_width)
    if use_cb and (u is None or low is None):
        raise ValueError("use_cb requires the query envelopes (u, low)")

    ref2, ref_spec = _ref_operand(ref, m)

    k_pad = -(-k // block_k) * block_k
    ub_arr = jnp.broadcast_to(jnp.asarray(ub, jnp.float32), (nq, k))
    mu_arr = jnp.asarray(mu, jnp.float32)
    sg_arr = jnp.asarray(sg, jnp.float32)
    if k_pad != k:
        pw = ((0, 0), (0, k_pad - k))
        starts = jnp.pad(starts, pw)  # start 0 is always in range
        mu_arr = jnp.pad(mu_arr, pw)
        sg_arr = jnp.pad(sg_arr, pw, constant_values=1.0)
        ub_arr = jnp.pad(ub_arr, pw, constant_values=DEAD_LANE_UB)
    q_rows, q_spec, nrb = _query_rows(queries, row_block)
    u_arr, low_arr = _envelopes(u, low, nq, m)

    ncb = k_pad // block_k
    grid = (nq, ncb, nrb)
    starts_blk, starts_spec = _lane_starts(starts, block_k, ncb)

    kernel = partial(
        _dtw_ea_fused_kernel,
        n_rows=n,
        m=m,
        window=window,
        row_block=row_block,
        band_width=bw,
        use_cb=use_cb,
        emit_info=with_info,
    )
    out_specs, out_shape = _per_lane_outputs(
        block_k, ncb, nq * k_pad, with_info
    )
    scratch = _gather_scratch(block_k, m, bw) + [
        pltpu.VMEM((block_k, cb_tile_width(m)), jnp.float32),  # cb
        pltpu.VMEM((block_k, bw), jnp.float32),   # prev band
        pltpu.VMEM((block_k, 1), jnp.int32),      # next_start
        pltpu.VMEM((block_k, 2), jnp.int32),      # flags
        pltpu.VMEM((block_k, 1), jnp.int32),      # rows counter
        pltpu.VMEM((block_k, 1), jnp.int32),      # cells counter
        pltpu.SMEM((1,), jnp.int32),              # block done flag
        _dma_sems(block_k),                       # window DMA semaphores
    ]
    out = pl.pallas_call(
        kernel,
        name="dtw_ea_multi_fused",
        grid=grid,
        in_specs=[
            _lane_spec(block_k, ncb),  # ub
            q_spec,
            ref_spec,                  # raw reference
            starts_spec,
            _lane_spec(block_k, ncb),  # mu
            _lane_spec(block_k, ncb),  # sigma
            _query_spec(m),            # envelope u
            _query_spec(m),            # envelope low
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        ub_arr.reshape(nq * k_pad, 1),
        q_rows,
        ref2,
        starts_blk,
        mu_arr.reshape(nq * k_pad, 1),
        sg_arr.reshape(nq * k_pad, 1),
        u_arr,
        low_arr,
    )
    return _per_lane(out, nq, k_pad, k, with_info)


@partial(
    jax.jit,
    static_argnames=(
        "window", "use_cb", "band_width", "block_k", "row_block", "interpret"
    ),
)
def dtw_ea_persistent(
    queries: jax.Array,
    candidates: jax.Array,
    lb: jax.Array,
    starts: jax.Array,
    ub_init: jax.Array,
    window: int,
    u: jax.Array | None = None,
    low: jax.Array | None = None,
    use_cb: bool = False,
    band_width: int | None = None,
    block_k: int = 8,
    row_block: int = 128,
    interpret: bool = False,
):
    """Whole best-first EAPrunedDTW search in ONE launch per query set.

    The persistent form of ``dtw_ea_multi`` (DESIGN.md §2.5): instead of the
    host looping best-first rounds around kernel dispatches, the candidate
    dimension of the grid turns sequential and the incumbent is carried in
    SMEM scratch across candidate blocks — tightened by each block's
    surviving minimum and gating the next block's lower bound on device.
    This wrapper is the pre-gathered **slab** arm (``gather="slab"``): it
    still takes the O(K·m) normalized window matrix, and is kept as the
    comparison baseline; the default execution form is
    ``dtw_ea_persistent_fused``, which ships the raw reference once and
    slices windows in-kernel. Lanes must arrive in best-first
    (ascending-``lb``) order in either form; gating correctness only needs
    ``lb`` to be a true lower bound, but the on-device cascade stop is only
    as good as the ordering.

    Args:
      queries: ``(Q, n)`` z-normalized queries.
      candidates: ``(Q, K, m)`` z-normalized windows, best-first per query.
      lb: ``(Q, K)`` ascending per-lane lower bounds (``+inf`` marks padding
        lanes — they never run).
      starts: ``(Q, K)`` int32 global window start of each lane (the value
        reported back for the winning lane).
      ub_init: ``(Q,)`` initial incumbents (``BIG`` for a cold start; a warm
        seed that no candidate beats is returned unchanged with start -1).
      window: Sakoe-Chiba window shared by all queries.
      u, low: ``(Q, m)`` query envelopes — required when ``use_cb`` (the cb
        suffix is computed as a kernel prologue; no host-side cb slab).
      use_cb: UCR threshold tightening on/off.
      band_width, block_k, row_block, interpret: as in ``dtw_ea_multi``.

    Returns: ``(best_dist, best_start, blocks)`` of shapes ``(Q,)`` —
      float32 incumbent distances, int32 winning window starts (-1 when the
      seed was never beaten), int32 count of candidate blocks that actually
      ran (the block-granular work metric; dispatches are 1 by construction).
    """
    queries = jnp.asarray(queries, jnp.float32)
    candidates = jnp.asarray(candidates, jnp.float32)
    nq, n = queries.shape
    q_, k, m = candidates.shape
    assert q_ == nq, (q_, nq)
    window, bw = _band(window, n, m, band_width)
    if use_cb and (u is None or low is None):
        raise ValueError("use_cb requires the query envelopes (u, low)")

    lb_arr, starts_arr, candidates = pad_lanes_to_blocks(
        block_k, jnp.asarray(lb, jnp.float32),
        jnp.asarray(starts, jnp.int32), candidates,
    )
    k_pad = candidates.shape[1]
    q_rows, q_spec, nrb = _query_rows(queries, row_block)
    u_arr, low_arr = _envelopes(u, low, nq, m)

    ncb = k_pad // block_k
    grid = (nq, ncb, nrb)
    tile = cand_tile_width(m, bw)
    cand_flat = _pad_cols(candidates.reshape(nq * k_pad, m), tile)
    starts_blk, starts_spec = _lane_starts(starts_arr, block_k, ncb)

    kernel = partial(
        _dtw_ea_persistent_kernel,
        n_rows=n,
        m=m,
        window=window,
        row_block=row_block,
        band_width=bw,
        use_cb=use_cb,
    )
    out_specs, out_shape = _per_query_outputs(nq)
    dist, idx, blocks = pl.pallas_call(
        kernel,
        name="dtw_ea_persistent",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # ub_init (Q,)
            q_spec,
            _lane_spec(block_k, ncb, tile),         # candidates
            _lane_spec(block_k, ncb),               # lb
            starts_spec,
            _query_spec(m),                         # envelope u
            _query_spec(m),                         # envelope low
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_k, bw), jnp.float32),   # prev band
            pltpu.VMEM((block_k, 1), jnp.int32),      # next_start
            pltpu.VMEM((block_k, 2), jnp.int32),      # flags
            pltpu.VMEM((block_k, 1), jnp.float32),    # per-lane thresholds
            pltpu.VMEM((block_k, cb_tile_width(m)), jnp.float32),  # cb
            pltpu.SMEM((1,), jnp.int32),              # block done flag
            pltpu.SMEM((1,), jnp.float32),            # carried incumbent
            pltpu.SMEM((1,), jnp.int32),              # carried best start
            pltpu.SMEM((1,), jnp.int32),              # live-block counter
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.asarray(ub_init, jnp.float32),
        q_rows,
        cand_flat,
        lb_arr.reshape(nq * k_pad, 1),
        starts_blk,
        u_arr,
        low_arr,
    )
    return dist[:, 0, 0], idx[:, 0, 0], blocks[:, 0, 0]


@partial(
    jax.jit,
    static_argnames=(
        "window", "length", "use_cb", "band_width", "block_k", "row_block",
        "interpret",
    ),
)
def dtw_ea_persistent_fused(
    queries: jax.Array,
    ref: jax.Array,
    lb: jax.Array,
    starts: jax.Array,
    mu: jax.Array,
    sg: jax.Array,
    ub_init: jax.Array,
    window: int,
    length: int,
    u: jax.Array | None = None,
    low: jax.Array | None = None,
    use_cb: bool = False,
    band_width: int | None = None,
    block_k: int = 8,
    row_block: int = 128,
    interpret: bool = False,
):
    """Fused-gather persistent sweep: the whole search, no window slab.

    ``dtw_ea_persistent`` with the candidate matrix replaced by the raw
    reference series plus per-lane ``(start, mu, sigma)`` vectors — each
    live candidate block's normalized tile is sliced out of the resident
    reference inside the kernel (gated blocks skip the copies entirely),
    so the launch's working set is O(N + block_k·m) instead of O(K·m).
    That is the form that completes persistent sweeps over references whose
    O(N·l) slab could never be materialized. Lanes must still arrive in
    best-first (ascending-``lb``) order.

    Args (where they differ from ``dtw_ea_persistent``):
      ref: ``(N,)`` raw (sanitized) reference series.
      mu, sg: ``(Q, K)`` per-lane window mean and **pre-clamped** sigma.
      length: static candidate window length ``m``.

    Returns: ``(best_dist, best_start, blocks)`` — as ``dtw_ea_persistent``.
    """
    queries = jnp.asarray(queries, jnp.float32)
    nq, n = queries.shape
    m = int(length)
    window, bw = _band(window, n, m, band_width)
    if use_cb and (u is None or low is None):
        raise ValueError("use_cb requires the query envelopes (u, low)")

    ref2, ref_spec = _ref_operand(ref, m)

    lb_arr = jnp.asarray(lb, jnp.float32)
    starts_arr = jnp.asarray(starts, jnp.int32)
    mu_arr = jnp.asarray(mu, jnp.float32)
    sg_arr = jnp.asarray(sg, jnp.float32)
    k = lb_arr.shape[-1]
    k_pad = -(-k // block_k) * block_k
    if k_pad != k:
        pw = ((0, 0), (0, k_pad - k))
        lb_arr = jnp.pad(lb_arr, pw, constant_values=jnp.inf)
        starts_arr = jnp.pad(starts_arr, pw)  # start 0 is always in range
        mu_arr = jnp.pad(mu_arr, pw)
        sg_arr = jnp.pad(sg_arr, pw, constant_values=1.0)
    q_rows, q_spec, nrb = _query_rows(queries, row_block)
    u_arr, low_arr = _envelopes(u, low, nq, m)

    ncb = k_pad // block_k
    grid = (nq, ncb, nrb)
    starts_blk, starts_spec = _lane_starts(starts_arr, block_k, ncb)

    kernel = partial(
        _dtw_ea_persistent_kernel,
        n_rows=n,
        m=m,
        window=window,
        row_block=row_block,
        band_width=bw,
        use_cb=use_cb,
        fused=True,
    )
    out_specs, out_shape = _per_query_outputs(nq)
    scratch = _gather_scratch(block_k, m, bw) + [
        pltpu.VMEM((block_k, bw), jnp.float32),   # prev band
        pltpu.VMEM((block_k, 1), jnp.int32),      # next_start
        pltpu.VMEM((block_k, 2), jnp.int32),      # flags
        pltpu.VMEM((block_k, 1), jnp.float32),    # per-lane thresholds
        pltpu.VMEM((block_k, cb_tile_width(m)), jnp.float32),  # cb
        pltpu.SMEM((1,), jnp.int32),              # block done flag
        pltpu.SMEM((1,), jnp.float32),            # carried incumbent
        pltpu.SMEM((1,), jnp.int32),              # carried best start
        pltpu.SMEM((1,), jnp.int32),              # live-block counter
        _dma_sems(block_k),                       # window DMA semaphores
    ]
    dist, idx, blocks = pl.pallas_call(
        kernel,
        name="dtw_ea_persistent_fused",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # ub_init (Q,)
            q_spec,
            ref_spec,                               # raw reference
            _lane_spec(block_k, ncb),               # lb
            starts_spec,
            _lane_spec(block_k, ncb),               # mu
            _lane_spec(block_k, ncb),               # sigma
            _query_spec(m),                         # envelope u
            _query_spec(m),                         # envelope low
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.asarray(ub_init, jnp.float32),
        q_rows,
        ref2,
        lb_arr.reshape(nq * k_pad, 1),
        starts_blk,
        mu_arr.reshape(nq * k_pad, 1),
        sg_arr.reshape(nq * k_pad, 1),
        u_arr,
        low_arr,
    )
    return dist[:, 0, 0], idx[:, 0, 0], blocks[:, 0, 0]


@partial(
    jax.jit,
    static_argnames=("length", "chunk", "interpret"),
)
def lb_keogh_all_windows(
    ref: jax.Array,
    mu: jax.Array,
    sigma: jax.Array,
    upper: jax.Array,
    lower: jax.Array,
    qends: jax.Array,
    length: int,
    chunk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """LB_Kim + LB_Keogh for every z-normalized window of ``ref``.

    Args:
      ref: ``(N,)`` reference series (resident in VMEM — suitable for
        references up to a few MB; shard first for longer ones).
      mu, sigma: per-window stats ``(N_win,)`` (from search.znorm).
      upper, lower: query envelope ``(length,)``.
      qends: ``(2,)`` first/last value of the z-normalized query (LB_Kim).
      interpret: run the kernel body in Python instead of through Mosaic.
    Returns: ``(N_win,)`` lower bounds (max of Kim and Keogh).
    """
    ref = jnp.asarray(ref, jnp.float32)
    n = ref.shape[0]
    n_win = n - length + 1
    n_pad = -(-n_win // chunk) * chunk
    mu_p = jnp.pad(jnp.asarray(mu, jnp.float32), (0, n_pad - n_win))
    sg_p = jnp.pad(jnp.asarray(sigma, jnp.float32), (0, n_pad - n_win), constant_values=1.0)
    # pad ref so every chunk can read ``chunk + length`` samples
    ref_p = jnp.pad(ref, (0, n_pad + length - n))

    grid = (n_pad // chunk,)
    kernel = partial(_lb_kernel, length=length, chunk=chunk, n_win=n_win)
    out = pl.pallas_call(
        kernel,
        name="lb_keogh_all_windows",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # query endpoints (2,)
            pl.BlockSpec(memory_space=pltpu.VMEM),  # full ref in VMEM
            pl.BlockSpec((chunk,), lambda ci: (ci,)),
            pl.BlockSpec((chunk,), lambda ci: (ci,)),
            pl.BlockSpec(memory_space=pltpu.VMEM),  # envelope upper, full
            pl.BlockSpec(memory_space=pltpu.VMEM),  # envelope lower, full
        ],
        out_specs=pl.BlockSpec((chunk,), lambda ci: (ci,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(
        jnp.asarray(qends, jnp.float32),
        ref_p,
        mu_p,
        sg_p,
        jnp.asarray(upper, jnp.float32),
        jnp.asarray(lower, jnp.float32),
    )
    return out[:n_win]
