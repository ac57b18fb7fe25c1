"""The reference, and the precision control that the comparison rejects."""
from __future__ import annotations

import json
import subprocess
import sys

import numpy as np
import pytest

from bench_fixtures import REPO, x32
from benchkit import check, reference, traffic

CFG = {"dataset": "ECG", "ref_len": 2048, "query_len": 128,
       "window_ratio": 0.1}
MIX = json.loads((REPO / "bench/traffic/planted-pool8.json").read_text())
LIMITS = json.loads((REPO / "bench/configs/ucr-ecg-l128.json")
                    .read_text())["limits"]


def brute64(ref, query, length, window):
    """Every window's float64 ``dtw_naive``: the reference of references."""
    d = [reference.window_dtw64(ref, query, s, length, window)
         for s in range(len(ref) - length + 1)]
    return int(np.argmin(d)), float(np.min(d))


@pytest.mark.parametrize("dataset,seed", [("ECG", 2**31 + 3), ("PPG", 5),
                                          ("REFIT", 9)])
def test_device_reference_matches_float64_brute_force(dataset, seed):
    cfg = {"dataset": dataset, "ref_len": 400, "query_len": 32}
    wl = traffic.build(
        cfg, {"data_seed": seed, "pool": 2, "plant_noise": 0.05}, seed)
    with x32():
        starts, dists, runner = reference.search(wl.ref, wl.pool, 32, 3)
    for q, s, d, r in zip(wl.pool, starts, dists, runner):
        bs, bd = brute64(wl.ref, q, 32, 3)
        assert s == bs
        assert d == pytest.approx(bd, rel=1e-5)
        assert r >= d


# The fori carry of 64 windows at w=3: 8 queries x (2w + 2) x 4 bytes each.
# 369 windows in blocks of at most 64 make 6 blocks of 62 over two device
# slots, and 8 blocks of 47 over four devices.
CARRY_64 = 64 * 8 * (2 * 3 + 2) * 4


def blocked_against_whole(devs, size):
    """Checks the search in blocks of ``size`` windows dealt to ``devs``
    against one block. Integer samples make the window statistics exact,
    so two copies of a window tie exactly: query 0 at 10 and ``2 * size``
    (the first window of a block), query 1 at ``size - 1`` (the last of
    one) and ``3 * size`` (the first of another)."""
    rng = np.random.default_rng(2**31 + 7)
    ref = rng.integers(-8, 9, 400).astype(np.float32)
    for a, b in ((10, 2 * size), (size - 1, 3 * size)):
        ref[b : b + 32] = ref[a : a + 32]
    queries = np.stack([ref[10:42], ref[size - 1 : size + 31]])
    carry = reference.CARRY_BYTES
    with x32():
        whole = reference.search(ref, queries, 32, 3)
        try:
            reference.CARRY_BYTES = CARRY_64
            blocked = reference.search(ref, queries, 32, 3, devices=devs)
        finally:
            reference.CARRY_BYTES = carry
    assert all(w.tobytes() == b.tobytes() for w, b in zip(whole, blocked))
    starts, dists, runner = blocked
    assert list(starts) == [10, size - 1]
    assert np.array_equal(runner, dists)  # the tie is exact


_FOUR_DEVICES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "bench/tests")
import jax
from test_bench_control import blocked_against_whole
devs = jax.devices()
assert len(set(devs)) == 4, devs
blocked_against_whole(devs, 47)
print("BLOCKS OK")
"""


@pytest.mark.parametrize("devices", ["one dealt twice", "four"])
def test_window_blocks_give_the_unblocked_answers_bit_for_bit(devices):
    if devices == "four":  # separate devices need a process of their own
        out = subprocess.run([sys.executable, "-c", _FOUR_DEVICES],
                             capture_output=True, text=True, timeout=300,
                             cwd=REPO)
        assert out.returncode == 0, out.stderr[-3000:]
        assert "BLOCKS OK" in out.stdout
        return
    import jax

    blocked_against_whole(jax.devices()[:1] * 2, 62)


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_bfloat16_control_is_rejected(seed):
    cfg = dict(CFG, ref_len=4096)
    wl = traffic.build(
        cfg, {"data_seed": seed, "pool": 16, "plant_noise": 0.05}, seed)
    pool = range(len(wl.pool))
    refc = check.Reference(wl.ref, wl.pool, 128, 12, wl.offsets)
    with x32():
        exp = refc.nearest(pool)
        ctl = refc.nearest(pool, dtype="bfloat16")
    # The reference put in the program's place passes; in bfloat16 it fails.
    sound = check.control_answers(exp, pool)
    assert check.verdict(refc.compare(sound, exp), LIMITS)
    numbers = refc.compare(check.control_answers(ctl, pool), exp)
    assert not check.verdict(numbers, LIMITS), numbers
    # Here (16 queries over 4,096 samples) the control reads 3.0e-2 and
    # up; over 256 queries and the cell's 262,144 samples, 4.4e-2 and up.
    assert numbers["answer_err_max"] > 1.5 * LIMITS["answer_err_max"]


def test_near_tie_is_judged_by_distance_not_start():
    # A reference in which query 2's planted copy has a near twin.
    wl = traffic.build(CFG, dict(MIX, data_seed=2**31 + 1), 2**31 + 1)
    refc = check.Reference(wl.ref, wl.pool, 128, 12, wl.offsets)
    with x32():
        exp = refc.nearest([2])
    s = exp[2].start
    # The planted window and its neighbour lie within 0.2% of each other.
    near = refc.d64(2, s + 1)
    assert 0 < (near - refc.d64(2, s)) / refc.d64(2, s) < 5e-3
    got = refc.compare([check.Answer(2, s + 1, near)], exp)
    assert got["dist_err_max"] == 0
    assert 0 < got["nearest_gap_max"] == got["answer_err_max"] < 5e-3
    assert refc.diagnostics([check.Answer(2, s + 1, near)],
                            exp)["start_mismatches"] == 1
    lost = refc.compare([check.Answer(2, -1, 1e30)], exp)
    assert lost["answer_err_max"] == float("inf")
