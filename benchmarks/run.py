"""Benchmark entry point. One section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV:
  * suite/*        — paper Fig. 5 analogue (four suites x dataset x l x w);
                     suite/SPEEDUP/* rows carry the headline ratios
  * search/multiq/* — one multi_query_search call vs Q sequential searches
  * search/stream/* — streaming engine ingest vs full recompute per chunk
  * search/robustness/* — quarantine-prepass overhead on clean data
                     (must sit within noise of the prepass compiled out)
  * search/resilient/* — fault-tolerant sharded executor vs the plain
                     offline driver on a healthy system (coverage 1.0)
  * search/hedged/* — hedged dispatch: healthy-path overhead (≲5%) plus
                     the deterministic tail win under one injected
                     straggler on a virtual clock (DESIGN.md §2.9)
  * search/persistent/* — one-launch persistent sweep vs host round driver
                     (both backends; dispatch counts in the speedup rows)
  * search/gather/* — fused in-kernel window gather + z-normalization vs
                     the pre-gathered O(K·l) candidate slab (§2.10); the
                     speedup rows carry the working-set byte accounting
  * search/pipeline/* — frontend wrapper (validation + plan resolution)
                     vs the bare jitted pipeline core; the overhead ratio
                     must stay ≈1 (the §2.8 refactor's dispatch guard)
  * dtw/*          — per-computation EA/Pruned/full work + time comparison
  * dtw/backend/*  — batch-backend dispatch comparison (vmap vs
                     Pallas-interpret) across K x l x block_k x Q shapes
  * kernel/*       — Pallas kernel harness checks (interpret mode)
  * roofline/*     — dry-run-derived roofline terms per (arch x shape)

``--json`` additionally writes a ``BENCH_dtw.json`` artifact so the perf
trajectory stays machine-readable across PRs: per-suite ``us_per_call`` and
``cells_ratio``, the ``multiq`` and ``stream`` suites, plus every dtw/*
micro-bench row.

Usage: PYTHONPATH=src python -m benchmarks.run
         [--quick] [--skip-roofline] [--json [PATH]]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _suite_record(name: str, us: float, derived: str) -> dict:
    rec = {"name": name, "us_per_call": round(us, 1)}
    for part in str(derived).split(";"):
        if "=" in part:
            key, val = part.split("=", 1)
            try:
                rec[key] = float(val)
            except ValueError:
                rec[key] = val
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument(
        "--json", nargs="?", const="BENCH_dtw.json", default=None,
        metavar="PATH",
        help="also write a machine-readable artifact (default BENCH_dtw.json)",
    )
    args = ap.parse_args()

    from benchmarks import (
        bench_dtw_micro,
        bench_gather,
        bench_kernels,
        bench_multiq,
        bench_persistent,
        bench_pipeline,
        bench_robustness,
        bench_stream,
        bench_suites,
    )

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    # quick-scale and full-scale runs are different workloads; the meta block
    # keeps cross-PR comparisons scoped to like-for-like artifacts
    artifact = {
        "meta": {"quick": bool(args.quick), "backend": jax.default_backend()},
        "suites": [], "multiq": [], "stream": [], "robustness": [],
        "resilient": [], "hedged": [], "persistent": [], "gather": [],
        "pipeline": [], "dtw": [], "roofline": [],
    }

    print("name,us_per_call,derived")
    if args.quick:
        rows = bench_suites.run(ref_len=4_000, lengths=(128,), ratios=(0.1,),
                                datasets=("ECG",), repeats=1)
    else:
        rows = bench_suites.run()
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["suites"].append(_suite_record(name, us, derived))

    if args.quick:
        mq_rows = bench_multiq.run(ref_len=8_000, pairs=5)
    else:
        mq_rows = bench_multiq.run()
    for name, us, derived in mq_rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["multiq"].append(_suite_record(name, us, derived))

    if args.quick:
        st_rows = bench_stream.run(ref_len=6_000, chunk=1_500, pairs=3)
    else:
        st_rows = bench_stream.run()
    for name, us, derived in st_rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["stream"].append(_suite_record(name, us, derived))

    if args.quick:
        # like bench_persistent below, the two arms are near-identical in
        # cost, so the ratio needs extra pairs to beat the box's noise
        rb_rows = bench_robustness.run(ref_len=6_000, chunk=1_500, pairs=9)
    else:
        rb_rows = bench_robustness.run()
    for name, us, derived in rb_rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["robustness"].append(_suite_record(name, us, derived))

    if args.quick:
        # few shards over a short ref: the executor's dispatch boundaries
        # dominate, so extra pairs keep the ratio above the box's noise
        rs_rows = bench_robustness.run_resilient(ref_len=6_000, pairs=9)
    else:
        rs_rows = bench_robustness.run_resilient()
    for name, us, derived in rs_rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["resilient"].append(_suite_record(name, us, derived))

    if args.quick:
        # the straggler-tail row is exact (virtual clock) at any scale, so
        # quick mode only shrinks the wall-clock healthy-overhead arm
        hg_rows = bench_robustness.run_hedged(ref_len=6_000, pairs=5)
    else:
        hg_rows = bench_robustness.run_hedged()
    for name, us, derived in hg_rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["hedged"].append(_suite_record(name, us, derived))

    if args.quick:
        # more pairs than the other quick suites: the two arms are within
        # ~15% of each other on CPU, so the median needs the extra samples
        # to sit above the box's timing noise
        ps_rows = bench_persistent.run(ref_len=4_000, pairs=9)
    else:
        ps_rows = bench_persistent.run()
    for name, us, derived in ps_rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["persistent"].append(_suite_record(name, us, derived))

    if args.quick:
        # identical DP work on both arms (the slab is the only difference),
        # so the wall-clock ratio needs extra pairs on a noisy box; the
        # byte-accounting fields are exact at any scale
        gt_rows = bench_gather.run(ref_len=4_000, pairs=9)
    else:
        gt_rows = bench_gather.run()
    for name, us, derived in gt_rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["gather"].append(_suite_record(name, us, derived))

    if args.quick:
        # the two arms are one wrapper apart, so the overhead ratio sits
        # right at 1.0 — extra pairs keep it above the box's timing noise
        pl_rows = bench_pipeline.run(ref_len=8_000, pairs=9)
    else:
        pl_rows = bench_pipeline.run()
    for name, us, derived in pl_rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["pipeline"].append(_suite_record(name, us, derived))

    micro = bench_dtw_micro.run(length=128, k=128, window_ratio=0.1)
    micro += bench_dtw_micro.run_backends(
        shapes=((64, 128),) if args.quick else ((64, 128), (256, 128), (64, 256)),
        block_ks=(8, 16) if args.quick else (4, 8, 16),
        qs=(1, 4),
    )
    for name, us, derived in micro:
        print(f"{name},{us:.1f},{derived}", flush=True)
        artifact["dtw"].append(_suite_record(name, us, derived))

    bench_kernels.main()

    if not args.skip_roofline:
        from repro.roofline.analysis import load_cells

        try:
            cells = load_cells()
        except Exception as e:
            print(f"roofline/unavailable,0.0,{e}")
            cells = []
        for c in cells:
            if "skipped" in c:
                continue
            name = f"roofline/{c['arch']}/{c['shape']}/{c['mesh']}"
            bound_us = max(c["compute_s"], c["memory_s"], c["collective_s"]) * 1e6
            print(
                f"{name},{bound_us:.1f},"
                f"bound={c['dominant']};frac={c['roofline_fraction']:.4f};"
                f"useful={c['useful_ratio']:.3f}",
                flush=True,
            )
            artifact["roofline"].append(
                {"name": name, "bound_us": round(bound_us, 1),
                 "bound": c["dominant"],
                 "roofline_fraction": c["roofline_fraction"]}
            )

    if args.json:
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
