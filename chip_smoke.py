#!/usr/bin/env python
"""Chip smoke: the paper's 1M-sample DTW search, end to end, on a TPU.

Drives the deployment ``configs.dtw_search.CONFIG`` defines (a 1,000,000-
sample reference, 1,024-sample queries, window ratio 0.1) through the
public frontends in one process and checks every answer:

  * a query planted (with a little noise) at a known offset of the seeded
    reference is found there by the host-round driver (``subsequence_search``)
    and by the persistent driver (``SearchConfig.make_plan`` + the executor
    seam), and each winner's distance equals a float64 NumPy DTW of the
    winning window to ``RTOL`` relative;
  * ``multi_query_search`` with Q=8 planted queries matches 8 single-query
    calls (``best_start`` exactly);
  * ``backend="jax"`` on the same chip, the plain reference, gives the same
    ``best_start`` and distance;
  * the compiled search program holds the Pallas kernel (``tpu_custom_call``);
  * the kernel, fed host-built inputs for the winning window with its mean
    moved by -4..+3 ulp across the lanes, stays within ``RTOL`` of the
    float64 DTW (``ulp_block`` prints the values, so the same inputs can be
    run in interpret mode elsewhere and compared bit for bit).

``--chips 4`` runs only the sharded search over a 4-device mesh and the
same search on one of those devices, and checks that they agree and that
the sharded result lives on four distinct devices.

Usage, from the root of a checkout:

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the sharded path, four chips

Timings printed on the way are smoke timings, not benchmark numbers. The
last line of output is one JSON object, ``{"ok": true, "device": {...}}``.
Exits non-zero, printing no result, when JAX finds no TPU, when the repo's
sources are missing, or when any check fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

DATASET = "ECG"
N_QUERIES = 8
NOISE = 0.05   # planted-copy noise, relative to the query's spread
# Distance agreement. The banded DP resolves each row by a float32
# prefix-sum/prefix-min scan (core/ea_pruned_dtw.py), whose rounding moves
# by up to 2.4e-4 relative at l=1024 when one input moves by one ulp: the
# planted windows' mean moved by -4..+3 ulp spreads the kernel's distance
# over 2.37e-4 (query 0) and 2.21e-4 (query 1) in interpret mode on a CPU,
# and over 2.51e-4 (query 0, ``ulp_block``) on a TPU v5e. Window statistics and query normalization computed by two compiled
# programs can differ by such an ulp. Inputs rounded to bfloat16 depart by
# 1.5e-3 to 3.9e-3, and the planted windows' neighbours by 9% and more.
RTOL = 5e-4
# backend="jax" (the vmapped while_loop) took 326 s for one 1M search on a
# v5e, so the comparison runs on a prefix of the reference at the same l, r.
JAX_REF_LEN = 100_000


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"check ok: {what}", flush=True)


def check_close(a: float, b: float, what: str) -> None:
    rel = abs(a - b) / max(abs(a), abs(b))
    check(rel <= RTOL, f"{what}: {a!r} vs {b!r}, relative difference "
          f"{rel:.2e} <= {RTOL:g}")


def timed(fn):
    """``fn()`` with its outputs ready, and the seconds it took."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def planted_workload(cfg, seed: int):
    """Seeded reference with ``N_QUERIES`` queries planted at known offsets.

    The noise keeps each planted distance well above the rounding of the
    search's float32 window statistics, so the float64 comparison is well
    conditioned, and far below the distance to any other window.
    """
    import numpy as np

    from repro.data.synthetic import make_dataset, make_queries

    ref = make_dataset(DATASET, cfg.ref_len, seed=seed)
    queries = make_queries(DATASET, N_QUERIES, cfg.query_len, seed=seed + 1)
    rng = np.random.default_rng(seed)
    stride = cfg.ref_len // (N_QUERIES + 1)
    offsets = [(i + 1) * stride - int(rng.integers(0, stride // 4))
               for i in range(N_QUERIES)]
    for q, p in zip(queries, offsets):
        noise = rng.normal(0.0, NOISE * q.std(), q.shape)
        ref[p : p + cfg.query_len] = q + noise
    return ref, queries, offsets


def dtw64(ref, query, start: int, cfg) -> float:
    """Float64 NumPy DTW of ``query`` against the window at ``start``."""
    import numpy as np

    from repro.core.ea_pruned_dtw_np import dtw_naive

    def zn(x):
        x = np.asarray(x, np.float64)
        return (x - x.mean()) / max(x.std(), 1e-8)

    win = ref[start : start + cfg.query_len]
    return dtw_naive(zn(query), zn(win), window=cfg.window)


def ulp_block(ref_np, query, start: int, cfg, interpret: bool = False):
    """The fused round kernel on one block of host-built float32 inputs.

    Every lane holds the window at ``start``; lane ``k`` gets the window's
    float64 mean, rounded to float32, moved by ``k - 4`` ulp. The inputs are
    built in NumPy and the band is the TPU's 128-lane-aligned one, so every
    platform runs the same program on the same bits. Returns the
    ``block_k`` float32 distances.
    """
    import numpy as np

    import jax.numpy as jnp

    from repro.core.common import BIG
    from repro.kernels import ops

    k = cfg.block_k
    band = cfg.band_width or min(
        cfg.query_len, -(-(2 * cfg.window + 1) // 128) * 128)
    win = np.asarray(ref_np[start : start + cfg.query_len], np.float32)
    win = win.astype(np.float64)
    mu = np.float32(win.mean())
    mus = mu + (np.arange(k, dtype=np.float32) - k // 2) * np.spacing(mu)
    q = np.asarray(query, np.float64)
    q = ((q - q.mean()) / q.std()).astype(np.float32)
    d = ops.dtw_ea_multi_fused(
        jnp.asarray(q)[None], jnp.asarray(ref_np, jnp.float32),
        jnp.full((1, k), start, jnp.int32), jnp.asarray(mus)[None],
        jnp.full((1, k), np.float32(win.std())), jnp.full((1, k), BIG),
        window=cfg.window, length=cfg.query_len, band_width=band,
        block_k=k, row_block=cfg.row_block, interpret=interpret,
    )
    return [float(x) for x in np.asarray(d[0])]


def device_line(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def one_chip(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.search import (
        get_executor, initial_state, multi_query_search, subsequence_search,
    )
    from repro.search.pipeline import _offline_search_impl

    ref_np, queries, offsets = planted_workload(cfg, seed)
    ref = jnp.asarray(ref_np, jnp.float32)
    qs = jnp.asarray(queries, jnp.float32)
    n_win = cfg.ref_len - cfg.query_len + 1
    knobs = dict(
        length=cfg.query_len, window=cfg.window, variant=cfg.variant,
        batch=cfg.batch, band_width=cfg.band_width, block_k=cfg.block_k,
        row_block=cfg.row_block, gather=cfg.gather, quarantine=cfg.quarantine,
    )
    print(f"workload: {DATASET} N={cfg.ref_len} l={cfg.query_len} "
          f"w={cfg.window} batch={cfg.batch} planted at {offsets}", flush=True)

    # 1. Default host-round driver, through the single-query frontend.
    host = lambda: subsequence_search(ref, qs[0], rounds=cfg.rounds, **knobs)
    res, cold = timed(host)
    res, warm = timed(host)
    print(f"smoke timing (not a benchmark): subsequence_search rounds=host "
          f"cold {cold:.3f} s (with compile), warm {warm:.3f} s", flush=True)
    start, dist = int(res.best_start), float(res.best_dist)
    print(f"host: best_start={start} best_dist={dist!r} "
          f"rounds={int(res.rounds)}", flush=True)
    check(start == offsets[0], f"host rounds find the planted offset {offsets[0]}")
    d64 = dtw64(ref_np, queries[0], start, cfg)
    check_close(dist, d64, "host distance vs float64 DTW")

    # 2. Persistent driver, through the SearchConfig -> SearchPlan bridge.
    plan = cfg.make_plan(rounds="persistent")
    check(plan.backend == "pallas", "the plan resolved backend 'pallas'")
    executor = get_executor(plan, ref, qs[:1])
    rr, t = timed(lambda: executor.run_range(
        plan, initial_state(1, jnp.float32), 0, n_win))
    print(f"smoke timing (not a benchmark): persistent run_range "
          f"{t:.3f} s (with compile)", flush=True)
    p_start, p_dist = int(rr.state.best[0]), float(rr.state.ub[0])
    print(f"persistent: best_start={p_start} best_dist={p_dist!r}", flush=True)
    check(p_start == offsets[0],
          f"persistent sweep finds the planted offset {offsets[0]}")
    check_close(p_dist, d64, "persistent distance vs float64 DTW")

    # 3. Multi-query: one Q=8 call against 8 single-query calls.
    mq, t = timed(lambda: multi_query_search(ref, qs, rounds=cfg.rounds, **knobs))
    print(f"smoke timing (not a benchmark): multi_query_search Q={N_QUERIES} "
          f"{t:.3f} s (with compile)", flush=True)
    singles, t = timed(lambda: [
        subsequence_search(ref, qs[i], rounds=cfg.rounds, **knobs)
        for i in range(N_QUERIES)
    ])
    print(f"smoke timing (not a benchmark): {N_QUERIES} single-query calls "
          f"{t:.3f} s", flush=True)
    mq_starts = [int(s) for s in mq.best_start]
    one_starts = [int(r.best_start) for r in singles]
    print(f"multi: best_start={mq_starts}", flush=True)
    check(mq_starts == one_starts,
          f"Q={N_QUERIES} multi-query best_start == {N_QUERIES} single calls")
    for i in range(N_QUERIES):
        check_close(float(mq.best_dist[i]), float(singles[i].best_dist),
                    f"query {i}: multi-query vs single-call distance")
    check(mq_starts == offsets, "every planted query found at its offset")

    # 4. The plain reference: backend="jax" on the same chip.
    n_cmp = min(JAX_REF_LEN, cfg.ref_len)
    ref_cmp = ref[:n_cmp]
    pal, _ = timed(lambda: subsequence_search(
        ref_cmp, qs[0], rounds=cfg.rounds, **knobs))
    ref_jax, t = timed(lambda: subsequence_search(
        ref_cmp, qs[0], rounds=cfg.rounds, backend="jax", **knobs))
    print(f"smoke timing (not a benchmark): backend=jax over {n_cmp} samples "
          f"{t:.3f} s (with compile)", flush=True)
    print(f"jax: best_start={int(ref_jax.best_start)} "
          f"best_dist={float(ref_jax.best_dist)!r}; pallas: "
          f"best_start={int(pal.best_start)} "
          f"best_dist={float(pal.best_dist)!r}", flush=True)
    check(int(pal.best_start) == int(ref_jax.best_start),
          f"pallas best_start == jax best_start over {n_cmp} samples")
    check_close(float(pal.best_dist), float(ref_jax.best_dist),
                "pallas vs jax distance")

    # 5. The compiled search program holds the Pallas kernel.
    host_plan = cfg.make_plan()
    hlo = _offline_search_impl.lower(
        ref, qs[:1], None, host_plan, False).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the compiled search program contains a tpu_custom_call")

    # 6. Rounding: one ulp of input moves the float32 DP by up to ~2e-4.
    d64 = dtw64(ref_np, queries[0], offsets[0], cfg)
    lanes = ulp_block(ref_np, queries[0], offsets[0], cfg)
    print(f"ulp block: start={offsets[0]} mean moved -4..+3 ulp: "
          f"{lanes!r}; float64 DTW {d64!r}", flush=True)
    for i, d in enumerate(lanes):
        check_close(d, d64, f"ulp block lane {i} vs float64 DTW")


def four_chips(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.search import make_distributed_search, subsequence_search

    devs = jax.devices()
    check(len(devs) == 4, f"four devices visible (found {len(devs)})")
    ref_np, queries, offsets = planted_workload(cfg, seed)
    ref = jnp.asarray(ref_np, jnp.float32)
    q = jnp.asarray(queries[0], jnp.float32)
    knobs = dict(
        length=cfg.query_len, window=cfg.window, batch=cfg.batch,
        band_width=cfg.band_width, block_k=cfg.block_k,
        row_block=cfg.row_block, quarantine=cfg.quarantine,
    )

    mesh = jax.make_mesh((4,), ("data",), devices=devs)
    sharded = make_distributed_search(mesh, ("data",), **knobs)
    res, t = timed(lambda: sharded(ref, q))
    print(f"smoke timing (not a benchmark): sharded search on 4 chips "
          f"{t:.3f} s (with compile)", flush=True)
    res, t = timed(lambda: sharded(ref, q))
    print(f"smoke timing (not a benchmark): sharded search on 4 chips, warm "
          f"{t:.3f} s", flush=True)
    shard_devs = {s.device.id for s in res.best_dist.addressable_shards}
    print(f"sharded: best_start={int(res.best_start)} "
          f"best_dist={float(res.best_dist)!r} rounds={int(res.rounds)} "
          f"devices={sorted(shard_devs)}", flush=True)
    check(len(shard_devs) == 4 and res.best_dist.sharding.device_set
          == set(devs), "the sharded result spans four distinct devices")

    single, t = timed(lambda: subsequence_search(
        jax.device_put(ref, devs[0]), jax.device_put(q, devs[0]),
        variant=cfg.variant, gather=cfg.gather, rounds=cfg.rounds, **knobs))
    print(f"smoke timing (not a benchmark): single-device search on "
          f"device {devs[0].id} {t:.3f} s (with compile)", flush=True)
    print(f"single: best_start={int(single.best_start)} "
          f"best_dist={float(single.best_dist)!r}", flush=True)
    check(int(res.best_start) == int(single.best_start),
          "sharded best_start == single-device best_start")
    check(int(res.best_start) == offsets[0],
          f"the sharded search finds the planted offset {offsets[0]}")
    check_close(float(res.best_dist), float(single.best_dist),
                "sharded vs single-device distance")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the whole one-chip smoke; 4: the sharded path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    from repro.configs.dtw_search import CONFIG
    from repro.core.backend import resolve_backend
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{jax.devices()[0].platform!r})", file=sys.stderr)
        return 2
    if resolve_backend() != "pallas":
        print(f"chip_smoke: backend resolved to {resolve_backend()!r}, "
              "not 'pallas'", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {device_line(jax)}", flush=True)

    try:
        if args.chips == 4:
            four_chips(CONFIG, args.seed)
        else:
            one_chip(CONFIG, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_line(jax)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
