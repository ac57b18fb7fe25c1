"""Seeded synthetic series shaped like the paper's ECG, PPG and REFIT data.

A copy of the generators of the program's ``repro/data/synthetic.py``, kept
with the benchmark so that the yardstick's data cannot move with the
program. Deterministic across processes: the seed mixes ``zlib.crc32`` of
the name, never Python's salted ``hash()``.
"""
from __future__ import annotations

import zlib

import numpy as np

DATASETS = ("ECG", "PPG", "REFIT")


def _ecg_like(rng: np.random.Generator, n: int, period: int = 180) -> np.ndarray:
    t = np.arange(n)
    phase = (t % period) / period
    qrs = np.exp(-((phase - 0.1) ** 2) / 0.0004) * 2.2
    pwave = np.exp(-((phase - 0.7) ** 2) / 0.004) * 0.4
    drift = 0.3 * np.sin(2 * np.pi * t / (37 * period))
    jitter = rng.normal(0, 0.05, n)
    return qrs + pwave + drift + jitter


def _ppg_like(rng, n, period=220):
    t = np.arange(n)
    base = np.sin(2 * np.pi * t / period) + 0.35 * np.sin(4 * np.pi * t / period + 0.8)
    resp = 0.25 * np.sin(2 * np.pi * t / (period * 4.7))
    return base + resp + rng.normal(0, 0.03, n)


def _walk(rng, n, scale=1.0):
    return np.cumsum(rng.normal(0, scale, n))


def _activity(rng, n, seg=2048):
    out = np.empty(n)
    i = 0
    while i < n:
        k = min(seg + int(rng.integers(-seg // 2, seg // 2)), n - i)
        freq = rng.uniform(0.01, 0.12)
        amp = rng.uniform(0.3, 2.0)
        t = np.arange(k)
        out[i : i + k] = amp * np.sin(2 * np.pi * freq * t + rng.uniform(0, 6.28))
        out[i : i + k] += rng.normal(0, 0.15, k)
        i += k
    return out + 0.05 * _walk(rng, n, 0.2)


def make_dataset(name: str, n: int, seed: int = 0) -> np.ndarray:
    """Long float64 reference series for a paper-analogue dataset."""
    rng = np.random.default_rng((zlib.crc32(name.encode()) + 977 * seed) % (2**31))
    if name == "ECG":
        return _ecg_like(rng, n)
    if name == "PPG":
        return _ppg_like(rng, n)
    if name == "REFIT":
        return np.abs(_walk(rng, n, 0.5)) + _activity(rng, n, 4096) * 0.3
    raise ValueError(f"unknown dataset {name!r}")


def make_queries(
    name: str, n_queries: int, length: int = 1024, seed: int = 1
) -> np.ndarray:
    """Queries cut from a disjoint stretch of the same generator."""
    stream = make_dataset(name, (n_queries + 2) * length * 3, seed=seed + 1000)
    rng = np.random.default_rng(seed)
    starts = rng.choice(len(stream) - length, n_queries, replace=False)
    return np.stack([stream[s : s + length] for s in starts])
