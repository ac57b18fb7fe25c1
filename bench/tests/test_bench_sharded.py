"""The ``sharded`` entry on four host devices against the one-chip search
and the plain reference."""
from __future__ import annotations

import json
import subprocess
import sys

from bench_fixtures import REPO, TINY, tiny_root

CFG = json.loads((REPO / "bench/configs/ucr-ecg-l128-1m-x4.json").read_text())
N = 16_384


def sharded_against_single(devs, ref_len: int = N, pool: int = 3) -> list:
    """Every planted query through the entry's ``dispatch``/``fetch`` on a
    mesh of ``devs``: its start is one-device ``subsequence_search``'s, its
    distance agrees to 1e-5, and the answers pass the plain reference's
    limit. Returns the answers."""
    import jax
    import numpy as np

    from benchkit import check, spec, traffic
    from benchkit.harness import knobs
    from repro.search import subsequence_search

    config = {**CFG, "ref_len": ref_len}
    mix = json.loads((REPO / "bench/traffic/planted-pool16.json").read_text())
    mix["pool"] = pool
    wl = traffic.build(config, mix, seed=2**31 + 5)
    cell = spec.Cell(name="sharded-test", chips=len(devs), workload={},
                     config=config, traffic=mix, end_to_end=[], per_layer=[])
    entry = spec.entry_class("sharded", REPO)(cell, wl.ref, devs)
    kn = knobs(cell)
    answers = []
    for p, q in enumerate(wl.pool):
        s, d, rounds, pruned = entry.fetch(
            jax.block_until_ready(entry.dispatch(q)))
        one = subsequence_search(jax.device_put(wl.ref, devs[0]), q, **kn)
        assert s == int(one.best_start), (p, s, int(one.best_start))
        assert abs(d - float(one.best_dist)) <= 1e-5 * float(one.best_dist)
        assert rounds >= 1 and 0 <= pruned < ref_len - kn["length"] + 1
        answers.append(check.Answer(p, s, d))
    refc = check.Reference(wl.ref, wl.pool, kn["length"], kn["window"],
                           wl.offsets)
    numbers = refc.compare(answers, refc.nearest(range(pool), devices=devs))
    assert check.verdict(numbers, CFG["limits"]), numbers
    assert np.isfinite(numbers["answer_err_max"])
    return answers


def four_chip_run(tmp: str) -> dict:
    """One harness run, off chip, of a tiny cell of layout ``sharded`` that
    asks for four chips."""
    from pathlib import Path

    import jax

    from benchkit import harness

    root = tiny_root(Path(tmp), ref_len=N, pool=3, layout="sharded")
    bm_path = root / "BENCHMARK.json"
    cfg_path = root / "bench/configs/tiny.json"
    bm, cfg = json.loads(bm_path.read_text()), json.loads(cfg_path.read_text())
    [tiny] = [w for w in bm["workloads"] if w["name"] == TINY]
    tiny["chips"] = cfg["chips"] = 4
    bm_path.write_text(json.dumps(bm))
    cfg_path.write_text(json.dumps(cfg))
    harness.require_chip = lambda chips: jax.devices()[:chips]
    return harness.run_cell(TINY, 2**31 + 99, 1.0, False, root=root)


_FOUR_DEVICES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "bench/tests")
import jax
from test_bench_sharded import sharded_against_single
devs = jax.devices()
assert len(set(devs)) == 4, devs
print("SHARDED OK", sharded_against_single(devs))
"""

_FOUR_CHIP_RUN = r"""
import os, sys, json, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, "bench/tests")
from test_bench_sharded import four_chip_run
with tempfile.TemporaryDirectory() as tmp:
    print("RESULT", json.dumps(four_chip_run(tmp)))
"""


def test_sharded_entry_on_four_devices_gives_the_one_chip_answers():
    out = subprocess.run([sys.executable, "-c", _FOUR_DEVICES],
                         capture_output=True, text=True, timeout=600,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED OK" in out.stdout


def test_harness_runs_a_four_chip_sharded_cell():
    """The benchmark's own path: set-up, the closed loop with no compile
    inside it, and the check, over four devices."""
    out = subprocess.run([sys.executable, "-c", _FOUR_CHIP_RUN],
                         capture_output=True, text=True, timeout=600,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT")]
    res = json.loads(line[0][len("RESULT"):])
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"windows_per_s", "setup_s"}
