"""Pallas kernel harness checks, in interpret mode on every platform.

The kernels run as interpreted Python here, so every row name ends in
``/interpret``: its wall time validates the harness and says nothing about
the chip, even when it is measured on one. CSV: name,us_per_call,derived.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.lower_bounds import envelope
from repro.kernels.ops import dtw_ea, lb_keogh_all_windows
from repro.kernels.ref import dtw_ea_ref
from repro.search.znorm import window_stats, znorm


def _bench(fn, repeats=2):
    out = fn()
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.time()
        out = fn()
        jax.block_until_ready(out)
        best = min(best, time.time() - t0)
    return best, out


def main() -> None:
    rng = np.random.default_rng(0)
    rows = []

    n, k, w = 128, 64, 12
    q = znorm(jnp.asarray(rng.normal(size=n), jnp.float32))
    c = znorm(jnp.asarray(rng.normal(size=(k, n)), jnp.float32))
    exact = np.asarray(dtw_ea_ref(q, c, jnp.inf, window=w))
    ub = float(np.median(exact))
    t, out = _bench(
        lambda: dtw_ea(
            q, c, ub, window=w, block_k=8, row_block=64, interpret=True
        )
    )
    ref = np.asarray(dtw_ea_ref(q, c, ub, window=w))
    ok = np.array_equal(np.isfinite(np.asarray(out)), np.isfinite(ref))
    rows.append((f"kernel/dtw_ea/l{n}/k{k}/interpret", t * 1e6, f"match_ref={ok}"))

    n_ref, length = 4096, 128
    ref_s = jnp.asarray(np.cumsum(rng.normal(size=n_ref)), jnp.float32)
    qr = znorm(jnp.asarray(np.cumsum(rng.normal(size=length)), jnp.float32))
    mu, sg = window_stats(ref_s, length)
    u, low = envelope(qr, w)
    qe = jnp.asarray([qr[0], qr[-1]], jnp.float32)
    t, _ = _bench(
        lambda: lb_keogh_all_windows(
            ref_s, mu, sg, u, low, qe, length=length, chunk=512,
            interpret=True,
        )
    )
    rows.append((f"kernel/lb_keogh/N{n_ref}/l{length}/interpret", t * 1e6, "all_windows"))

    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
