"""How ``correct`` is decided: the timed answers against the plain reference.

Every query answered in the window is compared. For each distinct pool
query issued, the reference (``benchkit.reference``) finds its nearest
window over the whole reference series, and the float64 ``dtw_naive`` gives
the distance ``D(s)`` of any window ``s`` the comparison needs. One number
is compared, with the limit the configuration file states under
``limits``:

``answer_err_max``  the largest, over the answers, of
                    ``max(|best_dist - D(best_start)|, D(best_start) - D(s*)) / D(s*)``
                    where ``s*`` is the reference's nearest window: the
                    distance reported is that of the window reported, and
                    that window is a nearest one, both to a relative
                    tolerance of the true minimum.

A start that is no window at all reads infinity. The window is not
compared exactly: at l=128 a planted window and its neighbour can lie
within 0.2% of each other, and a float32 search may resolve a near-tie
either way. The two parts are printed beside it as ``dist_err_max`` and
``nearest_gap_max``, with readings that do not decide ``correct``:
``start_mismatches`` (answers whose start is not ``s*``),
``planted_missed`` (pool queries whose ``s*`` is not their planted offset:
the traffic's promise, not the program's) and ``ref_margin_min`` (the
smallest gap, as a share, between a query's nearest window and the next
best one).
"""
from __future__ import annotations

import math
import sys
from dataclasses import dataclass

from benchkit import reference

NUMBERS = ("answer_err_max",)


@dataclass(frozen=True)
class Answer:
    pool_index: int
    best_start: int
    best_dist: float


@dataclass(frozen=True)
class Expected:
    start: int        # the reference's nearest window s*
    dist_ref: float   # its distance as the device reference computed it
    runner_up: float  # the next best window's distance (device reference)


class Reference:
    """The reference answers for one run's workload."""

    def __init__(self, ref, pool, length: int, window: int, offsets=()):
        self.ref, self.pool = ref, pool
        self.length, self.window = length, window
        self.offsets = offsets
        self.n_win = len(ref) - length + 1
        self._d64: dict[tuple[int, int], float] = {}

    def nearest(self, indices, *, dtype="float32", devices=None
                ) -> dict[int, Expected]:
        """Nearest windows of the pool queries ``indices``, searched in
        ``dtype`` on ``devices``. ``"bfloat16"`` is the precision control:
        the same search in the precision below the configuration's
        float32."""
        indices = sorted(set(indices))
        starts, dists, runner = reference.search(
            self.ref, self.pool[indices], self.length, self.window,
            dtype=dtype, devices=devices)
        return {i: Expected(int(s), float(d), float(r))
                for i, s, d, r in zip(indices, starts, dists, runner)}

    def d64(self, i: int, start: int) -> float:
        """Float64 DTW of pool query ``i`` and the window at ``start``."""
        if not 0 <= start < self.n_win:
            return math.inf
        if (i, start) not in self._d64:
            self._d64[i, start] = reference.window_dtw64(
                self.ref, self.pool[i], start, self.length, self.window)
        return self._d64[i, start]

    def compare(self, answers, expected: dict[int, Expected]) -> dict:
        """The compared number over ``answers``, and its two parts
        (module docstring)."""
        err = gap = 0.0
        for a in answers:
            d = self.d64(a.pool_index, a.best_start)
            best = self.d64(a.pool_index, expected[a.pool_index].start)
            err = max(err, _rel(abs(a.best_dist - d), best))
            gap = max(gap, _rel(d - best, best))
        return {"answer_err_max": max(err, gap), "dist_err_max": err,
                "nearest_gap_max": gap}

    def diagnostics(self, answers, expected: dict[int, Expected]) -> dict:
        return {
            "start_mismatches": sum(
                int(a.best_start != expected[a.pool_index].start)
                for a in answers),
            "planted_missed": sum(
                int(bool(self.offsets) and e.start != self.offsets[i])
                for i, e in expected.items()),
            "ref_margin_min": min(
                (e.runner_up - e.dist_ref) / e.dist_ref
                for e in expected.values()),
        }


def _rel(diff: float, base: float) -> float:
    """``diff / base``; infinity where either is not a finite number."""
    if not (math.isfinite(diff) and math.isfinite(base)):
        return math.inf
    return diff / base


def control_answers(control: dict[int, Expected], indices) -> list[Answer]:
    """The control put in the program's place: its answer to each query,
    with the distance it computed itself."""
    return [Answer(i, control[i].start, control[i].dist_ref) for i in indices]


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)


def report(numbers: dict, limits: dict, info: dict) -> dict:
    """Print the comparison on stderr, compared numbers last; return it
    for the result line."""
    parts = {k: v for k, v in numbers.items() if k not in NUMBERS}
    for k, v in {**info, **parts}.items():
        print(f"info {k} {v!r}", file=sys.stderr)
    for k in NUMBERS:
        print(f"check {k} {numbers[k]!r} limit {limits[k]!r}",
              file=sys.stderr, flush=True)
    # JSON has no infinity: a start that is no window reads 1e300.
    return {k: {"value": min(numbers[k], 1e300), "limit": limits[k]}
            for k in NUMBERS}
