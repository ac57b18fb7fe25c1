#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout: the system under test is the program
under ``src/``, and the cell, its configuration, traffic and metrics are
found by name from ``BENCHMARK.json`` (see ``bench/benchkit/spec.py``).
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.

The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, when
traced, ``breakdown``), and last ``checks``: each number compared with the
reference beside its limit, also printed as the last lines of standard
error. Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program's sources are missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}; run from the root "
              "of a checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

    from benchkit import harness
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), root=ROOT,
                                  t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
