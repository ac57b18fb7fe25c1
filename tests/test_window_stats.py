"""Window statistics against the benchmark's float64 reference.

``znorm.window_stats`` takes each window's sums from prefix sums within
blocks of ``length`` samples, so its float32 error does not grow with the
series. The reference is ``benchkit.reference.window_stats64`` (float64
prefix sums), which imports nothing of the program; the data is the
benchmark's seeded ECG analogue.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))

from benchkit.reference import window_stats64  # noqa: E402
from benchkit.synthetic import make_dataset  # noqa: E402
from repro.search import pipeline  # noqa: E402
from repro.search.znorm import window_stats  # noqa: E402

DATA_SEED = 201005371  # the benchmark's planted-pool mixes


def _sigma_rel_err(x: np.ndarray, length: int) -> np.ndarray:
    _, sd = window_stats(jnp.asarray(x), length)
    _, sd64 = window_stats64(x, length)
    return np.abs(np.asarray(sd, np.float64) - sd64) / sd64


@pytest.mark.parametrize("length", [128, 1024])
def test_float32_sigma_error_does_not_grow_to_a_million_samples(length):
    """At 1,000,000 samples a running float32 prefix sum gives sigma a
    relative error of 1.1e-2 (l=128) and 2.2e-4 (l=1024) at its worst;
    the blocked sums stay under 1e-5."""
    x = make_dataset("ECG", 1_000_000, seed=DATA_SEED).astype(np.float32)
    err = _sigma_rel_err(x, length)
    assert err.shape == (1_000_000 - length + 1,)
    assert err.max() <= 1e-4, (err.max(), np.median(err))
    assert np.median(err) <= 1e-6


@pytest.mark.parametrize("n", [41, 50, 81, 5 * 41 + 17, 333])
def test_blocks_cover_every_window_of_any_length(n):
    """A series of one window, shorter than two blocks, exactly two blocks
    minus one sample, and not a multiple of the window: every window's
    mean and std, in float64."""
    length = 41
    x = np.random.default_rng(n).normal(size=n) + 3.0
    mu, sd = window_stats(jnp.asarray(x), length)
    mu64, sd64 = window_stats64(x, length)
    assert mu.shape == sd.shape == (n - length + 1,)
    np.testing.assert_allclose(np.asarray(mu), mu64, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(sd), sd64, rtol=1e-10, atol=1e-12)


def test_quarantined_stretches_leave_other_windows_exact():
    """NaN stretches (one inside a block, one across a block edge, one at
    the end): the prepass condemns exactly the windows that touch them,
    and every other window's statistics are those of the raw float32
    series, as exact as on clean data."""
    length = 128
    x = make_dataset("ECG", 200_003, seed=DATA_SEED).astype(np.float32)
    bad = np.zeros(x.shape, bool)
    for lo, hi in ((1_000, 1_010), (70 * length - 3, 70 * length + 5),
                   (x.size - 20, x.size)):
        bad[lo:hi] = True
    x_nan = np.where(bad, np.float32(np.nan), x)
    plan = pipeline.make_plan(length=length, window=12, backend="jax")
    prep = pipeline.prepare_ref(plan, jnp.asarray(x_nan))
    touched = np.convolve(bad, np.ones(length), "valid") > 0
    assert np.array_equal(np.asarray(prep.valid), ~touched)
    assert int(prep.n_quar) == int(touched.sum())
    mu64, sd64 = window_stats64(x, length)
    ok = ~touched
    mu = np.asarray(prep.mu, np.float64)
    sd = np.asarray(prep.sigma, np.float64)
    assert np.all(np.isfinite(mu)) and np.all(np.isfinite(sd))
    assert np.max(np.abs(sd[ok] - sd64[ok]) / sd64[ok]) <= 1e-4
    assert np.max(np.abs(mu[ok] - mu64[ok]) / sd64[ok]) <= 1e-5
