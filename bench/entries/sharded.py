"""Entry ``sharded``: ``make_distributed_search`` over a mesh of the cell's
chips.

The reference is replicated on every chip once, at construction; each chip
searches a contiguous range of the candidate windows, and the incumbent is
reconciled across chips every round (``dtw.reconcile``).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec

from benchkit.harness import knobs

AXES = ("data",)


class Entry:
    def __init__(self, cell, ref, devs):
        from repro.search import make_distributed_search

        mesh = jax.make_mesh((len(devs),), AXES, devices=devs)
        self._search = make_distributed_search(mesh, AXES, **knobs(cell))
        self.ref = jax.device_put(ref, NamedSharding(mesh, PartitionSpec()))

    def dispatch(self, query):
        return self._search(self.ref, query)

    @staticmethod
    def fetch(res) -> tuple:
        s, d, r, p = jax.device_get(
            (res.best_start, res.best_dist, res.rounds, res.lb_pruned))
        return int(s), float(d), int(r), int(p)
