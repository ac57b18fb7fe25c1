"""The one traffic generator: a traffic file's parameters plus a seed → inputs.

A traffic mix is a JSON file of parameters under ``bench/traffic/``; this
module is the only code that reads one. A mix names:

``data_seed``     the seed of the reference series and the query pool: the
                  deployment's resident data, the same in every run;
``pool``          how many distinct queries the client cycles through;
``plant_noise``   noise added to each planted copy, as a share of its
                  query's standard deviation (0 plants nothing).

``--seed`` orders the queries and nothing else: the client sends the pool
in cycles, each cycle a permutation of the whole pool drawn from the seed
and the cycle's number. So every seed brings the same set of queries
against the same reference, in another order, and the work of a window
does not move with the seed. (How hard a DTW search is depends on its
data: with the data drawn from the seed, a run's throughput would.)

Copy ``k`` sits at ``(k + 1) * stride``, moved back by a seeded amount
under ``PLANT_JITTER`` of the stride, where ``stride = ref_len // (pool +
1)``. The configuration gives the dataset and the sizes. Queries are cut
from a disjoint stretch of the generator (the UCR suite's protocol); each
is then planted, with a little noise, at a seeded offset of the reference,
so that every query has one well-separated nearest window.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from benchkit.synthetic import make_dataset, make_queries

PLANT_JITTER = 0.25


@dataclass(frozen=True)
class Workload:
    ref: np.ndarray        # (N,) float32 reference, as the program gets it
    pool: np.ndarray       # (P, l) float32 raw queries
    offsets: tuple         # planted start of each pool query (() if none)
    seed: int = 0          # orders the queries (``query_index``)

    def query_index(self, i: int) -> int:
        """Pool entry of the run's ``i``-th query: cycle ``i // P`` sends
        the pool in an order drawn from the seed and the cycle."""
        n = self.pool.shape[0]
        cycle, k = divmod(i, n)
        rng = np.random.default_rng([self.seed % 2**64, cycle])
        return int(rng.permutation(n)[k])


def build(config: dict, traffic: dict, seed: int) -> Workload:
    """The mix's reference and query pool, from its ``data_seed``; the
    queries ordered by ``seed``."""
    n, length = int(config["ref_len"]), int(config["query_len"])
    pool_n = int(traffic["pool"])
    data_seed = int(traffic["data_seed"])
    ref = make_dataset(config["dataset"], n, seed=data_seed)
    pool = make_queries(config["dataset"], pool_n, length, seed=data_seed + 1)
    offsets: tuple = ()
    noise = float(traffic.get("plant_noise", 0.0))
    if noise > 0:
        stride = n // (pool_n + 1)
        jitter = int(stride * PLANT_JITTER)
        if stride - jitter < length:
            raise ValueError(
                f"{pool_n} planted copies of length {length} do not fit "
                f"apart in a reference of {n} samples"
            )
        rng = np.random.default_rng(data_seed)
        offsets = tuple(
            (i + 1) * stride - int(rng.integers(0, max(jitter, 1)))
            for i in range(pool_n)
        )
        for q, p in zip(pool, offsets):
            ref[p : p + length] = q + rng.normal(0.0, noise * q.std(), q.shape)
    return Workload(
        ref=ref.astype(np.float32), pool=pool.astype(np.float32),
        offsets=offsets, seed=seed,
    )
