"""Shared set-up of the benchmark's CPU tests.

Puts the program (``src``) and the benchmark (``bench``) on ``sys.path``,
builds a tiny cell in a copy of the benchmark's files, and runs the harness
there with its look for a chip stepped over.
"""
from __future__ import annotations

import json
import shutil
import sys
from contextlib import contextmanager
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (REPO / "src", REPO / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = "tiny-planted"


def tiny_root(tmp_path: Path, *, ref_len=4096, query_len=128,
              pool=4, layout="single") -> Path:
    """A checkout-like copy of ``BENCHMARK.json`` and ``bench/`` with one
    more cell, ``tiny-planted``, added by new files and entries only."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((REPO / "bench/configs/ucr-ecg-l128.json").read_text())
    cfg.update(name="tiny", ref_len=ref_len, query_len=query_len,
               layout=layout)
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    mix = {"name": "planted-tiny", "data_seed": 2**31 + 11, "pool": pool,
           "plant_noise": 0.05}
    (tmp_path / "bench/traffic/planted-tiny.json").write_text(json.dumps(mix))
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny", "source": "a test's own cell",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "a size a test run can hold"})
    bm["workloads"].append({"name": TINY, "config": "tiny",
                            "traffic": "planted-tiny", "chips": 1,
                            "why": "a size a test run can hold"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm, indent=1))
    return tmp_path


@contextmanager
def x32():
    """The search runs 32-bit; the repo's ``tests/conftest.py`` turns x64 on
    in any worker that collected it."""
    import jax

    with jax.enable_x64(False):
        yield


def run_off_chip(monkeypatch, root: Path, name: str = TINY,
                 seconds: float = 1.0, trace: bool = False,
                 seed: int = 2**31 + 11) -> dict:
    """One harness run on the CPU: the look for a chip is stepped over."""
    import jax

    from benchkit import harness

    monkeypatch.setattr(harness, "require_chip",
                        lambda chips: jax.devices()[:chips])
    with x32():
        return harness.run_cell(name, seed, seconds, trace, root=root)
