"""Similarity-search driver — the paper's application, as a service entry.

  PYTHONPATH=src python -m repro.launch.search --dataset ECG --ref-len 100000 \
      --query-len 256 --window-ratio 0.1 --variant eapruned

Runs all four suite variants with ``--variant all`` and prints the paper-style
comparison (runtime + pruning counters). ``--distributed`` shards candidates
over the local device mesh with shared-ub rounds.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.data.synthetic import DATASETS, make_dataset, make_queries
from repro.launch.compile_cache import enable_compile_cache
from repro.search import make_distributed_search, subsequence_search
from repro.search.subsequence import VARIANTS


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="ECG", choices=DATASETS)
    ap.add_argument("--ref-len", type=int, default=100_000)
    ap.add_argument("--query-len", type=int, default=256)
    ap.add_argument("--window-ratio", type=float, default=0.1)
    ap.add_argument("--variant", default="eapruned", choices=VARIANTS + ("all",))
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--n-queries", type=int, default=1)
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    ref = jnp.asarray(make_dataset(args.dataset, args.ref_len, args.seed), jnp.float32)
    queries = make_queries(args.dataset, args.n_queries, args.query_len, args.seed)
    window = max(int(args.query_len * args.window_ratio), 1)
    variants = list(VARIANTS) if args.variant == "all" else [args.variant]

    print(
        f"dataset={args.dataset} N={args.ref_len} l={args.query_len} "
        f"w={window} batch={args.batch}"
    )
    if args.distributed:
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        search = make_distributed_search(
            mesh, ("data",), length=args.query_len, window=window, batch=args.batch
        )
        for qi, q in enumerate(queries):
            t0 = time.time()
            res = search(ref, jnp.asarray(q, jnp.float32))
            jax.block_until_ready(res.best_dist)
            print(
                f"  q{qi}: start={int(res.best_start)} dist={float(res.best_dist):.5f} "
                f"rounds={int(res.rounds)} ({time.time() - t0:.2f}s)"
            )
        return

    for variant in variants:
        tot = 0.0
        for qi, q in enumerate(queries):
            t0 = time.time()
            res = subsequence_search(
                ref,
                jnp.asarray(q, jnp.float32),
                length=args.query_len,
                window=window,
                variant=variant,
                batch=args.batch,
            )
            jax.block_until_ready(res.best_dist)
            dt = time.time() - t0
            tot += dt
            print(
                f"  {variant:14s} q{qi}: start={int(res.best_start)} "
                f"dist={float(res.best_dist):.5f} lanes={int(res.lanes)} "
                f"rows={int(res.rows)} cells={int(res.cells)} ({dt:.2f}s)"
            )
        print(f"  {variant:14s} total {tot:.2f}s")


if __name__ == "__main__":
    main()
