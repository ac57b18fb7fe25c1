#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (chip only).

    python3 bench/readings.py --workload <name> --seeds 1,2,3 [--control 3]

For each seed, the cell's traffic is built with that seed as its
``data_seed`` (a new reference and pool; the benchmark's own runs hold the
mix's one fixed), every query of the pool goes once through the same entry
the benchmark's window drives, at the cell's own size, and the answers are
compared with the reference as a run compares them. For the
first ``--control`` seeds the precision control is read too: the
reference computed in bfloat16, the precision below the configuration's
float32, put in the program's place. One JSON line per seed and side:
``{"seed", "side", "answer_err_max", ...}``.

The benchmark's own runs do not run this. Set-up (imports, the first
compile) is paid once for all seeds.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", type=int, default=3,
                    help="read the bfloat16 control on this many seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

    import gc

    import jax

    from benchkit import check, harness, spec, traffic
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = spec.load_cell(args.workload, ROOT)
    Entry = spec.entry_class(cell.config["layout"], ROOT)
    try:
        devs = harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 3
    kn = harness.knobs(cell)
    pool = range(int(cell.traffic["pool"]))
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        wl = traffic.build(cell.config, {**cell.traffic, "data_seed": seed},
                           seed)
        entry = Entry(cell, wl.ref, devs)
        answers = []
        for p in pool:
            s, d, _, _ = entry.fetch(jax.block_until_ready(
                entry.dispatch(wl.pool[p])))
            answers.append(check.Answer(p, s, d))
        del entry
        gc.collect()
        refc = check.Reference(wl.ref, wl.pool, kn["length"], kn["window"],
                               wl.offsets)
        exp = refc.nearest(pool, devices=devs)
        line = {"seed": seed, "side": "program",
                **refc.compare(answers, exp),
                **refc.diagnostics(answers, exp)}
        print(json.dumps(line), flush=True)
        if n < args.control:
            ctl = refc.nearest(pool, dtype="bfloat16", devices=devs)
            line = {"seed": seed, "side": "control_bfloat16",
                    **refc.compare(check.control_answers(ctl, pool), exp)}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
