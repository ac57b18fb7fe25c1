"""Pallas TPU kernel: batched early-abandoning pruned DTW, banded columns.

TPU-native shape of EAPrunedDTW (DESIGN.md §2): a grid of
``(query_blocks, candidate_blocks, row_blocks)`` programs. The query and
candidate dimensions are embarrassingly parallel
(``dimension_semantics[:2] = ("parallel", "parallel")``); the row dimension
is sequential ("arbitrary") with the DP carry living in VMEM scratch across
grid steps.

Multi-query lane layout: one launch evaluates a flattened ``(Q × K)`` lane
set. Lanes are laid out query-major — candidate block ``ci`` of query ``qi``
lives at flattened block row ``qi * num_cand_blocks + ci`` — so each grid
program still sees a plain ``(block_k, m)`` VMEM tile whose lanes all share
one query and one envelope, while the grid's leading dimension walks the Q
distinct queries. ``Q == 1`` degenerates to the single-query kernel of PR 1.

Per-lane upper bounds: ``ub`` is a ``(block_k, 1)`` VMEM vector per block —
every lane carries its own incumbent. That is what turns the kernel into a
multi-query serving primitive: lanes belonging to different queries (or to
padding) abandon against their own thresholds, and a lane whose ``ub`` is
negative (the padding / finished-query sentinel) dies on its first row
without holding the block's early-exit flag hostage. The UCR ``cb``
threshold-tightening slab is likewise per-lane (``(block_k, m)``), so the
per-row threshold ``ub[lane] - cb[lane, i + w + 1]`` is fully vectorized.

Banded column mode (the serving hot path, mirroring
``core.ea_pruned_dtw.ea_pruned_dtw_banded``): instead of full-width ``m``
rows, each row step computes only a ``band_width`` slice of columns starting
at the *window-following* offset ``lo(i) = clip(i - window, 0, m - bw)``.
Because every lane of a block shares its query and the Sakoe-Chiba window,
``lo`` is lane-uniform and a pure function of the row index, advancing by at
most one column per row. That buys two TPU-critical properties:

  * the candidate slice is a lane-uniform ``pl.ds(lo, bw)`` dynamic slice
    (no per-lane gather), and
  * realigning the previous row's band is a single select between the
    unshifted band and a static shift-by-one — ``shift = lo(i) - lo(i-1)``
    is always 0 or 1.

Per-lane pruning state (``next_start``) is kept as a mask on top of the
band, so pruning decisions are bit-identical to the full-width kernel and to
the banded JAX reference. Work per row drops from O(m) to O(band), i.e. the
prefix-scan doubling runs log2(band) steps instead of log2(m).
``band_width == m`` degenerates to the original full-width kernel
(``lo == 0`` always) and is used when ``n != m`` or the window covers the
whole matrix.

Per (block_k)-lane row step, entirely in VMEM/VREGs:
  * cost row  ``c[k, r] = (q_i - cand[k, lo + r])^2``        (VPU)
  * ``d = c + min(top, left)`` with top/left from the realigned band
  * row recurrence via prefix-sum + cumulative-min doubling (log2(band))
  * band bookkeeping: ``next_start`` per lane, per-lane abandon flags, UCR
    ``cb`` threshold tightening — all vectorized mask reductions against the
    per-lane ``ub`` column.

Early abandoning at TPU granularity: a lane whose row has no cell under its
own threshold freezes (its updates are masked out); when *every* lane of a
candidate block has abandoned, an SMEM flag turns all remaining row-blocks of
that block into ``pl.when`` no-ops — the kernel-level analogue of the paper's
border-collision early exit, at (query, candidate-block) granularity.

Optional pruning counters (``emit_info``): per-lane rows-issued and
admissible-cells accumulators, matching ``core.ea_pruned_dtw.EAInfo``
semantics, so ``SearchResult`` stats survive when search runs through the
Pallas backend. The counter-free variant carries no accumulator traffic —
the search fast round uses it by default.

Persistent search mode (DESIGN.md §2.5): ``_dtw_ea_persistent_kernel``
collapses the *entire* best-first sweep of a search into one launch. The
candidate-block grid dimension turns sequential (``"arbitrary"``), the shared
incumbent ``ub`` lives in SMEM scratch and is min-reduced from each block's
surviving lane distances before the next block is gated, and a block whose
precomputed lower bound cannot beat the carried incumbent becomes a
``pl.when`` no-op on device — the cascade stop condition without returning
to the host. The UCR ``cb`` suffix is computed as a per-block kernel
prologue (LB_Keogh terms + reverse cumsum from the query envelope), so the
host neither materializes nor streams a ``cb`` slab. One launch per search,
O(1) dispatches instead of O(rounds), with ``ub`` tightening at candidate-
block granularity instead of round granularity.

Fused in-kernel gather + z-normalization (DESIGN.md §2.10): the default
operand form no longer ships pre-gathered ``(block_k, m)`` normalized
windows. Instead the kernels take the **raw reference series** — resident
once, O(N) — plus per-lane ``(start, mu, sigma)`` vectors, and each block's
``_init`` phase slices its lanes' windows out of the series and normalizes
them into VMEM scratch (``_gather_norm_block``): one DMA per lane of the
128-aligned span that covers its window (the Python loop over the static
``block_k`` lane index unrolls at trace time), a lane rotation that moves
the window to lane 0, then one vectorized ``(cand - mu) / sigma``.
``sigma`` arrives pre-clamped by the host wrapper (``clamp_sigma``), so
flat windows normalize to exactly the same zeros as the retired host-side
slab. The reference stays in HBM (``memory_space=ANY``) at every size; only
the DMA'd spans occupy VMEM. The working set drops
from O(N·l) (every overlapping window re-copied) to O(N + block_k·m), which
is what lets persistent mode sweep references whose window slab could never
be materialized. The UCR ``cb`` suffix is likewise built in-kernel from the
just-normalized tile (LB_Keogh terms + tree-order suffix sum — the same
documented O(1)-ulp reformulation as the persistent prologue below).

Mosaic alignment rules (what the TPU compiler accepts): a vector load or
a DMA may start only at a lane offset that is provably a multiple of 128,
and a rank-1 block must be 128 long. So every unaligned dynamic column
range (a lane's window, the DP band at ``lo``, the ``cb`` column) is read
as the covering aligned span and rotated into place (``_rotate_left``, an
exact bit move); query samples and window starts are SMEM scalars;
per-lane results are ``(block_k, 1)`` blocks and per-query results
``(1, 128)`` rows. Interpret mode runs the same program, so the interpret
parity tests pin its winners and pruning logic. They do not pin the chip's
float32 bits: on the same inputs a TPU v5e's distances differ from interpret
mode's by up to ~2e-4 relative at l=1024, because the prefix-scan rows
amplify a one-ulp difference in a normalized sample. ``chip_smoke.py``
checks the chip's distances against a float64 DTW.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.common import BIG, DEAD_LANE_UB
from repro.core.lower_bounds import _lb_keogh_terms


LANES = 128  # TPU vector lane count: the alignment unit of loads and DMAs


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def span_width(width: int) -> int:
    """Lanes of the aligned span that covers ``width`` columns at any offset."""
    return _round_up(width + LANES - 1, LANES)


def cb_tile_width(m: int) -> int:
    """Lane width of a ``cb`` tile: room for the aligned span ``_cols``
    loads to read any column ``j < m``."""
    return _round_up(m, LANES)


def cand_tile_width(m: int, band_width: int) -> int:
    """Lane width of a candidate tile: ``m`` columns plus room for the
    aligned span ``_dp_row`` loads to cut out the band at any ``lo``."""
    w = cb_tile_width(m)
    if band_width < m:
        lo_base_max = (m - band_width) // LANES * LANES
        w = max(w, lo_base_max + span_width(band_width))
    return w


def _rotate_left(x: jax.Array, off) -> jax.Array:
    """Rotate the last axis left by a dynamic ``off`` (exact; no arithmetic)."""
    n = x.shape[-1]
    return pltpu.roll(x, (n - off) % n, x.ndim - 1)


def _cols(ref, start, width: int) -> jax.Array:
    """``ref[:, start:start + width]`` for a dynamic, unaligned ``start``.

    Loads the 128-aligned span covering the columns and rotates them to
    lane 0 — the form Mosaic accepts for an unaligned dynamic lane slice.
    The caller guarantees the span lies inside ``ref``.
    """
    base = pl.multiple_of(start // LANES * LANES, LANES)
    x = ref[:, pl.ds(base, span_width(width))]
    return _rotate_left(x, start - base)[:, :width]


def _shift_right(x: jax.Array, off: int, fill: float) -> jax.Array:
    """Shift last axis right by ``off`` lanes, filling with ``fill``."""
    pad = jnp.full(x.shape[:-1] + (off,), fill, x.dtype)
    return jnp.concatenate([pad, x[..., :-off]], axis=-1)


def _shift_left(x: jax.Array, off: int, fill: float) -> jax.Array:
    """Shift last axis left by ``off`` lanes, filling with ``fill``."""
    pad = jnp.full(x.shape[:-1] + (off,), fill, x.dtype)
    return jnp.concatenate([x[..., off:], pad], axis=-1)


def _prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last axis (Hillis-Steele doubling)."""
    n = x.shape[-1]
    off = 1
    while off < n:
        x = x + _shift_right(x, off, 0.0)
        off *= 2
    return x


def _suffix_sum(x: jax.Array) -> jax.Array:
    """Inclusive suffix sum along the last axis (reverse-cumsum, doubling)."""
    n = x.shape[-1]
    off = 1
    while off < n:
        x = x + _shift_left(x, off, 0.0)
        off *= 2
    return x


def _prefix_min(x: jax.Array) -> jax.Array:
    """Inclusive prefix min along the last axis (doubling)."""
    n = x.shape[-1]
    off = 1
    while off < n:
        x = jnp.minimum(x, _shift_right(x, off, jnp.inf))
        off *= 2
    return x


def _gather_norm_block(
    ref_ref,      # (1, N_pad) raw reference, left in HBM
    starts_ref,   # (1, block_k) int32 SMEM window start per lane
    mu_ref,       # (block_k, 1) per-lane window mean
    sg_ref,       # (block_k, 1) per-lane window sigma (pre-clamped)
    cand_ref,     # (block_k, >= m) VMEM scratch: normalized windows out
    stage_ref,    # (block_k, 1, span_width(m)) VMEM scratch: aligned spans
    sems,         # (block_k,) DMA semaphores, one per lane
    *,
    m: int,
):
    """Slice + z-normalize one block's candidate windows in-kernel.

    The fused replacement for the host-side ``gather_norm_windows`` slab.
    Each lane's window ``ref[start:start + m]`` is fetched as the 128-aligned
    span that covers it (Mosaic refuses DMAs at unaligned lane offsets):
    all ``block_k`` DMAs are started, then each is awaited and its span
    rotated so the window starts at lane 0. Every lane's DMA signals its
    own semaphore, so a lane's wait returns only once that lane's span has
    landed (a shared semaphore would count bytes from any copy in flight).
    The lane index is static (the Python loops unroll at trace time).
    Normalization is one vectorized step over the tile; ``sg_ref`` is
    pre-clamped on the host (``clamp_sigma``), so the output is
    bit-identical to the retired pre-gathered slab.
    """
    block_k = cand_ref.shape[0]
    span = stage_ref.shape[-1]
    copies, offs = [], []
    for k in range(block_k):
        s = starts_ref[0, k]
        base = pl.multiple_of(s // LANES * LANES, LANES)
        cp = pltpu.make_async_copy(
            ref_ref.at[:, pl.ds(base, span)], stage_ref.at[k], sems.at[k]
        )
        cp.start()
        copies.append(cp)
        offs.append(s - base)
    for k in range(block_k):
        copies[k].wait()
        cand_ref[pl.ds(k, 1), :m] = _rotate_left(stage_ref[k], offs[k])[:, :m]
    cand_ref[:, :m] = (cand_ref[:, :m] - mu_ref[...]) / sg_ref[...]


def _dp_row(
    i,
    q_i,          # query sample for DP row ``i`` (SMEM scalar)
    cand_ref,     # (block_k, cand_tile_width(m, bw)) candidate block
    prev_ref,     # (block_k, bw) previous-row band scratch
    ns_ref,       # (block_k, 1) per-lane next_start scratch
    flags_ref,    # (block_k, 2) per-lane [abandoned, ok_last] scratch
    ub,           # (block_k, 1) per-lane thresholds (fixed for the block)
    cb_ref,       # (block_k, >= round_up(m, 128)) LB suffix (iff use_cb)
    rel,          # (block_k, bw) column iota
    rows_ref,     # (block_k, 1) rows counter scratch (used iff emit_info)
    cells_ref,    # (block_k, 1) cells counter scratch (used iff emit_info)
    *,
    n_rows: int,
    m: int,
    window: int,
    band_width: int,
    use_cb: bool,
    emit_info: bool,
):
    """One banded DP row, shared by the round and persistent kernels.

    Mutates the per-block scratch refs in place; a lane whose row has no
    cell under its own threshold freezes (abandon flag), and padding rows
    (``i >= n_rows``) are no-ops.
    """
    block_k = cand_ref.shape[0]
    bw = band_width
    lo_max = m - bw  # 0 in full-width mode

    valid = i < n_rows
    lo = jnp.clip(i - window, 0, lo_max)
    lo_prev = jnp.clip(i - 1 - window, 0, lo_max)
    shift = lo - lo_prev  # the window edge advances by 0 or 1

    cand = cand_ref[:, :bw] if lo_max == 0 else _cols(cand_ref, lo, bw)
    c = (q_i - cand) ** 2

    cols = lo + rel
    hi = jnp.minimum(m - 1, i + window)
    ns = ns_ref[...]  # (block_k, 1)
    exists = jnp.logical_and(
        jnp.logical_and(cols >= ns, cols >= i - window), cols <= hi
    )

    # Realign the previous row's band from offset lo_prev to lo.
    prev = prev_ref[...]
    big_col = jnp.full((block_k, 1), BIG, jnp.float32)
    # top[r]  = prev-row value at col lo + r      (shift left by shift)
    top = jnp.where(
        shift == 1,
        jnp.concatenate([prev[:, 1:], big_col], axis=1),
        prev,
    )
    # left[r] = prev-row value at col lo + r - 1  (shift by shift - 1)
    border = jnp.where(i == 0, 0.0, BIG)  # virtual corner at (-1, -1)
    left = jnp.where(
        shift == 1,
        prev,
        jnp.concatenate(
            [jnp.full((block_k, 1), border, jnp.float32), prev[:, :-1]],
            axis=1,
        ),
    )

    d = c + jnp.minimum(top, left)
    d = jnp.where(exists, d, BIG)
    p = _prefix_sum(c)
    curr = p + _prefix_min(d - p)
    curr = jnp.minimum(curr, BIG)
    curr = jnp.where(exists, curr, BIG)

    if use_cb:
        jcb = jnp.minimum(i + window + 1, m - 1)
        tail = _cols(cb_ref, jcb, 1)  # (block_k, 1)
        tail = jnp.where(i + window + 1 <= m - 1, tail, 0.0)
        thr = ub - tail
    else:
        thr = ub

    le = jnp.logical_and(curr <= thr, exists)
    any_le = jnp.any(le, axis=1, keepdims=True)  # (block_k, 1)
    alive = flags_ref[:, 0:1] == 0
    upd = jnp.logical_and(jnp.logical_and(alive, any_le), valid)

    ns_new = jnp.min(jnp.where(le, cols, m), axis=1, keepdims=True)
    ns_ref[...] = jnp.where(upd, ns_new.astype(jnp.int32), ns)
    prev_ref[...] = jnp.where(upd, curr, prev)
    newly_dead = jnp.logical_and(
        alive, jnp.logical_and(jnp.logical_not(any_le), valid)
    )
    flags_ref[:, 0:1] = jnp.where(
        newly_dead, jnp.ones_like(ns), flags_ref[:, 0:1]
    )
    is_last = i == n_rows - 1
    ok_last = jnp.logical_and(
        jnp.any(jnp.logical_and(le, cols == m - 1), axis=1, keepdims=True),
        jnp.logical_and(upd, is_last),
    )
    flags_ref[:, 1:2] = jnp.where(
        jnp.logical_and(valid, is_last),
        ok_last.astype(jnp.int32),
        flags_ref[:, 1:2],
    )
    if emit_info:
        # EAInfo semantics: the abandoning row is counted too.
        issued = jnp.logical_and(alive, valid)
        rows_ref[...] = rows_ref[...] + issued.astype(jnp.int32)
        n_exist = jnp.sum(
            exists.astype(jnp.int32), axis=1, keepdims=True
        ).astype(jnp.int32)
        cells_ref[...] = (
            cells_ref[...] + jnp.where(issued, n_exist, 0)
        ).astype(jnp.int32)


def _round_init_scratch(
    prev_ref, ns_ref, flags_ref, rows_ref, cells_ref, done_ref,
    *, band_width: int, emit_info: bool,
):
    """Reset one block's DP scratch at its first row block."""
    block_k = prev_ref.shape[0]
    prev_ref[...] = jnp.full((block_k, band_width), BIG, jnp.float32)
    ns_ref[...] = jnp.zeros((block_k, 1), jnp.int32)
    flags_ref[...] = jnp.zeros((block_k, 2), jnp.int32)
    if emit_info:
        rows_ref[...] = jnp.zeros((block_k, 1), jnp.int32)
        cells_ref[...] = jnp.zeros((block_k, 1), jnp.int32)
    done_ref[0] = jnp.asarray(0, jnp.int32)  # literal 0 is int64 under x64


def _round_sweep(
    ri, ub_ref, q_ref, cand_ref, cb_ref, out_ref, rows_out, cells_out,
    prev_ref, ns_ref, flags_ref, rows_ref, cells_ref, done_ref,
    *,
    n_rows: int,
    m: int,
    window: int,
    row_block: int,
    band_width: int,
    use_cb: bool,
    emit_info: bool,
):
    """Row sweep + finish shared by the gathered and fused round kernels."""
    block_k = cand_ref.shape[0]
    bw = band_width
    lo_max = m - bw  # 0 in full-width mode

    @pl.when(done_ref[0] == 0)
    def _rows():
        ub = ub_ref[...]  # (block_k, 1) per-lane incumbents
        rel = jax.lax.broadcasted_iota(jnp.int32, (block_k, bw), 1)

        def row(r, _):
            _dp_row(
                ri * row_block + r, q_ref[0, r], cand_ref,
                prev_ref, ns_ref, flags_ref, ub, cb_ref, rel,
                rows_ref, cells_ref,
                n_rows=n_rows, m=m, window=window, band_width=bw,
                use_cb=use_cb, emit_info=emit_info,
            )
            return 0

        # int32 bounds: the row index stays 32-bit under jax_enable_x64.
        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(row_block), row, 0, unroll=False
        )
        done_ref[0] = jnp.asarray(
            jnp.all(flags_ref[:, 0:1] == 1), jnp.int32
        ).astype(jnp.int32)

    @pl.when(ri == pl.num_programs(2) - 1)
    def _finish():
        ok = jnp.logical_and(flags_ref[:, 0:1] == 0, flags_ref[:, 1:2] == 1)
        lo_fin = min(max(n_rows - 1 - window, 0), lo_max)  # static
        col = (m - 1) - lo_fin
        out_ref[...] = jnp.where(ok, prev_ref[:, col : col + 1], jnp.inf)
        if emit_info:
            rows_out[...] = rows_ref[...]
            cells_out[...] = cells_ref[...]


def _dtw_ea_kernel(
    # operands
    ub_ref,      # (block_k, 1) per-lane upper bounds
    q_ref,       # (1, row_block) SMEM query slice for this (query, row) block
    cand_ref,    # (block_k, tile) candidate block (lanes share one query)
    cb_ref,      # (block_k, >= m) cumulative LB suffix (zeros if disabled)
    # outputs
    out_ref,     # (block_k, 1) distances
    *rest,       # [rows_out, cells_out] if emit_info, then scratch
    n_rows: int,
    m: int,
    window: int,
    row_block: int,
    band_width: int,
    use_cb: bool,
    emit_info: bool,
):
    """Gathered-slab round kernel (``gather="slab"`` comparison arm)."""
    if emit_info:
        rows_out, cells_out = rest[0], rest[1]
        rest = rest[2:]
    else:
        rows_out = cells_out = None
    prev_ref, ns_ref, flags_ref, rows_ref, cells_ref, done_ref = rest

    ri = pl.program_id(2)

    @pl.when(ri == 0)
    def _init():
        _round_init_scratch(
            prev_ref, ns_ref, flags_ref, rows_ref, cells_ref, done_ref,
            band_width=band_width, emit_info=emit_info,
        )

    _round_sweep(
        ri, ub_ref, q_ref, cand_ref, cb_ref, out_ref, rows_out, cells_out,
        prev_ref, ns_ref, flags_ref, rows_ref, cells_ref, done_ref,
        n_rows=n_rows, m=m, window=window, row_block=row_block,
        band_width=band_width, use_cb=use_cb, emit_info=emit_info,
    )


def _dtw_ea_fused_kernel(
    # operands
    ub_ref,      # (block_k, 1) per-lane upper bounds
    q_ref,       # (1, row_block) SMEM query slice for this (query, row) block
    ref_ref,     # (1, N_pad) raw reference, left in HBM
    starts_ref,  # (1, block_k) int32 SMEM window start per lane
    mu_ref,      # (block_k, 1) per-lane window mean
    sg_ref,      # (block_k, 1) per-lane window sigma (pre-clamped)
    u_ref,       # (1, m) query envelope upper (read iff use_cb)
    low_ref,     # (1, m) query envelope lower (read iff use_cb)
    # outputs
    out_ref,     # (block_k, 1) distances
    *rest,       # [rows_out, cells_out] if emit_info, then scratch
    n_rows: int,
    m: int,
    window: int,
    row_block: int,
    band_width: int,
    use_cb: bool,
    emit_info: bool,
):
    """Fused round kernel: windows sliced + normalized in-kernel.

    Same DP program as ``_dtw_ea_kernel``, but the candidate tile is VMEM
    *scratch* filled by ``_gather_norm_block`` at each block's first row
    step, and the UCR ``cb`` suffix — when enabled — is built in-kernel from
    that tile and the query envelope. The in-kernel suffix sum runs in tree
    order, so fused-round ``cb`` matches the host drivers' sequential cumsum
    to the documented O(1)-ulp reformulation rounding (DESIGN.md §2.2/§2.5)
    — abandon thresholds can shift by an ulp, the winner cannot change.
    """
    if emit_info:
        rows_out, cells_out = rest[0], rest[1]
        rest = rest[2:]
    else:
        rows_out = cells_out = None
    (cand_ref, stage_ref, cb_ref, prev_ref, ns_ref, flags_ref, rows_ref,
     cells_ref, done_ref, sems) = rest

    ri = pl.program_id(2)

    @pl.when(ri == 0)
    def _init():
        _gather_norm_block(
            ref_ref, starts_ref, mu_ref, sg_ref, cand_ref, stage_ref, sems, m=m
        )
        if use_cb:
            terms = _lb_keogh_terms(cand_ref[:, :m], u_ref[...], low_ref[...])
            cb_ref[:, :m] = _suffix_sum(terms)
        _round_init_scratch(
            prev_ref, ns_ref, flags_ref, rows_ref, cells_ref, done_ref,
            band_width=band_width, emit_info=emit_info,
        )

    _round_sweep(
        ri, ub_ref, q_ref, cand_ref, cb_ref, out_ref, rows_out, cells_out,
        prev_ref, ns_ref, flags_ref, rows_ref, cells_ref, done_ref,
        n_rows=n_rows, m=m, window=window, row_block=row_block,
        band_width=band_width, use_cb=use_cb, emit_info=emit_info,
    )


def _dtw_ea_persistent_kernel(
    # operands
    ub_init_ref,  # (Q,) SMEM per-query initial incumbents
    q_ref,        # (1, row_block) SMEM query slice for this (query, row) block
    *rest,
    n_rows: int,
    m: int,
    window: int,
    row_block: int,
    band_width: int,
    use_cb: bool,
    fused: bool = False,
):
    """Whole best-first search in one launch (DESIGN.md §2.5).

    Operand forms (after ``ub_init``/``q``):

    * gathered (``fused=False``, the ``gather="slab"`` comparison arm):
      ``cand (block_k, tile)`` pre-normalized best-first windows, then
      ``lb, starts, u, low`` — the O(N·l) slab form.
    * fused (``fused=True``, default execution form): ``ref (1, N_pad)``
      raw reference, left in HBM (``memory_space=ANY``), then
      ``lb, starts, mu, sg, u, low``; the
      candidate tile becomes VMEM scratch filled by ``_gather_norm_block``
      in each block's ``_init_block`` (gated off for skipped blocks, so a
      cascade-stopped tail costs no copies/DMAs). O(N + block_k·m) resident,
      which is what lets one launch sweep references whose window slab could
      never be materialized.

    Grid ``(Q, cand_blocks, row_blocks)`` with the candidate dimension
    *sequential*: the incumbent ``ub_s`` (and the running best start /
    block counter) live in SMEM scratch and are carried across candidate
    blocks, re-initialized from ``ub_init`` whenever a query's sweep starts
    (``ci == ri == 0``), so a core that serves several queries of a parallel
    query dimension never leaks state between them.

    Per candidate block:
      * gate: a block none of whose lanes' lower bounds beat the carried
        incumbent is a no-op (``done`` set at ``ri == 0``) — the on-device
        cascade stop. Lane-level gating rides the same comparison: a lane
        whose own bound reaches ``ub`` gets the dead-lane sentinel. The
        non-finite quarantine (DESIGN.md §2.6) rides it too: a quarantined
        window arrives with a ``+inf`` lower bound, so the kernel kills its
        lane on row 0 with no quarantine-specific code or retrace.
      * prologue (``use_cb``): the UCR ``cb`` suffix is built in VMEM from
        the candidate tile and the query envelope (LB_Keogh terms + suffix
        sum) instead of being streamed from HBM.
      * rows: the shared ``_dp_row`` banded recurrence, per-lane abandon.
      * epilogue (last row block): surviving lane distances are min-reduced
        into ``ub_s`` with first-lane tie-breaking; strict improvement only,
        matching the host round driver's incumbent update.
    """
    if fused:
        (ref_ref, lb_ref, starts_ref, mu_ref, sg_ref, u_ref, low_ref,
         dist_ref, idx_ref, blocks_ref,
         cand_ref, stage_ref, prev_ref, ns_ref, flags_ref, ubv_ref, cb_ref,
         done_ref, ub_s, best_s, blocks_s, sems) = rest
    else:
        (cand_ref, lb_ref, starts_ref, u_ref, low_ref,
         dist_ref, idx_ref, blocks_ref,
         prev_ref, ns_ref, flags_ref, ubv_ref, cb_ref,
         done_ref, ub_s, best_s, blocks_s) = rest

    qi = pl.program_id(0)
    ci = pl.program_id(1)
    ri = pl.program_id(2)
    block_k = cand_ref.shape[0]
    bw = band_width
    lo_max = m - bw

    @pl.when(jnp.logical_and(ci == 0, ri == 0))
    def _init_query():
        ub_s[0] = ub_init_ref[qi]
        best_s[0] = jnp.asarray(-1, jnp.int32)
        blocks_s[0] = jnp.asarray(0, jnp.int32)

    @pl.when(ri == 0)
    def _init_block():
        prev_ref[...] = jnp.full((block_k, bw), BIG, jnp.float32)
        ns_ref[...] = jnp.zeros((block_k, 1), jnp.int32)
        flags_ref[...] = jnp.zeros((block_k, 2), jnp.int32)
        # Block + lane gating against the carried incumbent. Lower bounds
        # arrive sorted, so "any lane live" == "head lane live", but the
        # any() form is order-independent.
        ub_cur = ub_s[0]
        live = lb_ref[...] < ub_cur  # (block_k, 1)
        ubv_ref[...] = jnp.where(live, ub_cur, DEAD_LANE_UB)
        skip = jnp.logical_not(jnp.any(live))
        done_ref[0] = skip.astype(jnp.int32)
        blocks_s[0] = blocks_s[0] + jnp.logical_not(skip).astype(jnp.int32)

        @pl.when(jnp.logical_not(skip))
        def _materialize():
            if fused:
                # Fused tier: slice + normalize this block's windows out of
                # the resident reference. Gated blocks (cascade stop / all
                # lanes dead) skip the DMAs entirely.
                _gather_norm_block(
                    ref_ref, starts_ref, mu_ref, sg_ref, cand_ref, stage_ref,
                    sems, m=m,
                )
            if use_cb:
                # (1, m) envelope broadcasts over the block's lanes. The
                # suffix sum runs in tree order (log-depth doubling) rather
                # than the host drivers' sequential cumsum — cb rounding
                # only shifts abandon thresholds by an ulp, which cannot
                # change the winner (DESIGN.md §2.2/§2.5).
                terms = _lb_keogh_terms(
                    cand_ref[:, :m], u_ref[...], low_ref[...]
                )
                cb_ref[:, :m] = _suffix_sum(terms)

    @pl.when(done_ref[0] == 0)
    def _rows():
        ub = ubv_ref[...]
        rel = jax.lax.broadcasted_iota(jnp.int32, (block_k, bw), 1)

        def row(r, _):
            _dp_row(
                ri * row_block + r, q_ref[0, r], cand_ref,
                prev_ref, ns_ref, flags_ref, ub, cb_ref, rel,
                None, None,
                n_rows=n_rows, m=m, window=window, band_width=bw,
                use_cb=use_cb, emit_info=False,
            )
            return 0

        # int32 bounds: the row index stays 32-bit under jax_enable_x64.
        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(row_block), row, 0, unroll=False
        )
        done_ref[0] = jnp.asarray(
            jnp.all(flags_ref[:, 0:1] == 1), jnp.int32
        ).astype(jnp.int32)

    @pl.when(ri == pl.num_programs(2) - 1)
    def _block_epilogue():
        # Min-reduce this block's surviving distances into the incumbent.
        # A gated block left flags at zero (ok_last == 0), so it contributes
        # nothing — the same no-op the host loop's stop condition implies.
        ok = jnp.logical_and(flags_ref[:, 0:1] == 0, flags_ref[:, 1:2] == 1)
        lo_fin = min(max(n_rows - 1 - window, 0), lo_max)  # static
        last = prev_ref[:, (m - 1) - lo_fin : (m - 1) - lo_fin + 1]
        d = jnp.where(ok, last, jnp.inf)  # (block_k, 1)
        dmin = jnp.min(d)
        improved = dmin < ub_s[0]  # strict: ties keep the incumbent

        @pl.when(improved)
        def _tighten():
            lane = jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            k = jnp.min(jnp.where(d == dmin, lane, block_k))  # first argmin
            ub_s[0] = dmin
            best_s[0] = starts_ref[0, k]

    @pl.when(
        jnp.logical_and(
            ci == pl.num_programs(1) - 1, ri == pl.num_programs(2) - 1
        )
    )
    def _emit():
        dist_ref[...] = jnp.full((1, LANES), ub_s[0], jnp.float32)
        idx_ref[...] = jnp.full((1, LANES), best_s[0], jnp.int32)
        blocks_ref[...] = jnp.full((1, LANES), blocks_s[0], jnp.int32)
