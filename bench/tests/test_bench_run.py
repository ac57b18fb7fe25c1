"""A whole run of a tiny cell off the chip, and the command's refusals.

The harness's look for a chip is stepped over; everything after it runs as
on the chip: set-up, the closed-loop window, the reference, the check and
the metric readers.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench_fixtures import REPO, run_off_chip, tiny_root


def test_interpret_mode_run_is_correct(tmp_path, monkeypatch):
    # The Pallas kernels themselves, in interpret mode, behind the same
    # entry as on the chip.
    monkeypatch.setenv("REPRO_DTW_BACKEND", "pallas_interpret")
    root = tiny_root(tmp_path, ref_len=1024, pool=2)
    res = run_off_chip(monkeypatch, root, seconds=0.5)
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"windows_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"answer_err_max"}


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch):
    root = tiny_root(tmp_path)
    res = run_off_chip(monkeypatch, root, seconds=0.5, trace=True)
    assert res["correct"] is True, res
    # The CPU trace has no TPU plane: the device readers find nothing to
    # read and stay silent; the program's counters are read.
    assert set(res["metrics"]) == {"rounds_per_query", "lb_pruned_share"}
    assert 0 <= res["metrics"]["lb_pruned_share"]["value"] <= 100
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_compile_inside_the_window_is_refused(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    import pytest

    from benchkit import spec

    root = tiny_root(tmp_path)
    calls = []

    class CompilingEntry(spec.entry_class("single", root)):
        def dispatch(self, query):
            calls.append(query)
            if len(calls) > 1:  # after the warm-up: a shape never compiled
                jax.jit(jnp.sin)(jnp.ones(len(calls))).block_until_ready()
            return super().dispatch(query)

    monkeypatch.setattr(spec, "entry_class",
                        lambda layout, root: CompilingEntry)
    with pytest.raises(RuntimeError, match="compilation events inside"):
        run_off_chip(monkeypatch, root, seconds=0.3)


def _command(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=120,
                          env=env)


def test_command_refuses_without_a_tpu(tmp_path):
    out = _command(["--workload", "ecg-l128-planted", "--seed", "1",
                    "--seconds", "1", "--trace", "0"], REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(["--workload", "ecg-l128-planted", "--seed", "1",
                    "--seconds", "1", "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_seed_gives_the_same_answers(tmp_path, monkeypatch):
    # How many queries a 0.3-s window answers depends on the machine's
    # load; the answers to the pool queries both windows hold must agree.
    from benchkit import harness

    windows, closed_loop = [], harness.closed_loop

    def recorded(*args, **kw):
        out = closed_loop(*args, **kw)
        windows.append({(r.pool_index, r.best_start, r.best_dist)
                        for r in out[0]})
        return out

    monkeypatch.setattr(harness, "closed_loop", recorded)
    root = tiny_root(tmp_path)
    a = run_off_chip(monkeypatch, root, seconds=0.3, seed=7)
    b = run_off_chip(monkeypatch, root, seconds=0.3, seed=7)
    assert a["correct"] and b["correct"]
    both = ({p for p, _, _ in windows[0]} & {p for p, _, _ in windows[1]})
    assert both
    assert ({t for t in windows[0] if t[0] in both}
            == {t for t in windows[1] if t[0] in both})
