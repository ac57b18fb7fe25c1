"""``correct`` comes out false when the timed path is broken underneath.

Each fault is planted in the program's search pipeline, under the entry the
harness drives, and a whole run of a tiny cell is made off the chip:

``state_unchanged``    the incumbent fold returns its state unchanged;
``half_left_out``      the second half of the candidate windows is never
                       searched;
``start_altered``      each answer's start is moved by one where it is
                       folded;
``dist_altered``       each DTW distance is scaled by 1.05 where the batch
                       primitive produces it.
"""
from __future__ import annotations

import pytest

from bench_fixtures import run_off_chip, tiny_root


def plant(monkeypatch, fault: str) -> None:
    """Break the program's search pipeline with ``fault``."""
    import jax
    import jax.numpy as jnp

    from repro.search import pipeline

    fold, fused = pipeline.fold_min, pipeline.ea_pruned_dtw_multi_batch_fused
    if fault == "state_unchanged":
        monkeypatch.setattr(pipeline, "fold_min", lambda state, starts, d,
                            offset=0: (state, jnp.zeros(state.ub.shape, bool)))
    elif fault == "half_left_out":
        def first_half(ref, length):
            n = ref.shape[0] - length + 1
            return jnp.arange(n) < n // 2
        monkeypatch.setattr(pipeline, "window_finite_mask", first_half)
    elif fault == "start_altered":
        def moved(state, starts, d, offset=0):
            new, improved = fold(state, starts, d, offset)
            return new._replace(best=jnp.where(improved, new.best + 1,
                                               new.best)), improved
        monkeypatch.setattr(pipeline, "fold_min", moved)
    elif fault == "dist_altered":
        monkeypatch.setattr(pipeline, "ea_pruned_dtw_multi_batch_fused",
                            lambda *a, **k: fused(*a, **k) * 1.05)
    else:
        raise ValueError(fault)
    jax.clear_caches()  # no program traced before the fault may serve


ONE_CHIP_FAULTS = ("state_unchanged", "half_left_out", "start_altered",
                   "dist_altered")


@pytest.mark.parametrize("fault", ONE_CHIP_FAULTS)
def test_fault_on_one_chip_path_is_not_correct(tmp_path, monkeypatch, fault):
    import jax

    root = tiny_root(tmp_path)
    try:
        plant(monkeypatch, fault)
        res = run_off_chip(monkeypatch, root, seconds=0.3)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert res["failed"] == 0
    assert res["correct"] is False, res["checks"]
