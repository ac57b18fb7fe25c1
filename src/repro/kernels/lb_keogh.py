"""Pallas TPU kernel: LB_Kim + LB_Keogh for every window, one pass.

The TPU-native formulation iterates over the *query offset* ``i`` instead of
the window start: for fixed ``i``, the contribution of offset ``i`` to all
``chunk`` windows is a unit-stride ``(chunk,)`` slice of the reference —
perfect VPU lanes — normalized per window and clamped against the scalar
envelope values ``U[i]``/``L[i]``. ``length`` iterations of ``(chunk,)``-wide
FMAs replace the CPU suite's per-candidate loop.

The search does not run this kernel: its cascade
(``search/cascade.py:cascade_lower_bounds``) is the same offset-major pass
in plain jnp, one code path for every backend. The kernel stays a tested
alternative in interpret mode; the v5e's compiler refuses it, since the
offset slices ``ref_ref[pl.ds(c0 + i, chunk)]`` are unaligned vector loads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EPS = 1e-8


def _lb_kernel(
    qends_ref,  # SMEM (2,): z-normed query first/last values
    ref_ref,    # VMEM (N_pad,) reference series
    mu_ref,     # (chunk,) per-window means
    sg_ref,     # (chunk,) per-window stds
    u_ref,      # VMEM (length,) envelope upper
    l_ref,      # VMEM (length,) envelope lower
    out_ref,    # (chunk,) lower bounds
    *,
    length: int,
    chunk: int,
    n_win: int,
):
    ci = pl.program_id(0)
    c0 = ci * chunk
    mu = mu_ref[...]
    inv = 1.0 / jnp.maximum(sg_ref[...], EPS)

    def offset_step(i, acc):
        seg = ref_ref[pl.ds(c0 + i, chunk)]
        v = (seg - mu) * inv
        ui = u_ref[pl.ds(i, 1)][0]
        li = l_ref[pl.ds(i, 1)][0]
        over = jnp.maximum(v - ui, 0.0)
        under = jnp.maximum(li - v, 0.0)
        return acc + over * over + under * under

    keogh = jax.lax.fori_loop(
        0, length, offset_step, jnp.zeros((chunk,), jnp.float32)
    )

    # LB_Kim (first/last points)
    v0 = (ref_ref[pl.ds(c0, chunk)] - mu) * inv
    vl = (ref_ref[pl.ds(c0 + length - 1, chunk)] - mu) * inv
    kim = (v0 - qends_ref[0]) ** 2 + (vl - qends_ref[1]) ** 2

    out_ref[...] = jnp.maximum(keogh, kim)
