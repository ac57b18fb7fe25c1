"""Entry ``single``: ``subsequence_search`` over a reference resident on one
chip."""
from __future__ import annotations

import jax

from benchkit.harness import knobs


class Entry:
    def __init__(self, cell, ref, devs):
        from repro.configs.dtw_search import CONFIG
        from repro.search import subsequence_search

        self._search = subsequence_search
        self._knobs = dict(knobs(cell), variant=CONFIG.variant,
                           rounds=CONFIG.rounds, gather=CONFIG.gather)
        self.ref = jax.device_put(ref, devs[0])

    def dispatch(self, query):
        return self._search(self.ref, query, **self._knobs)

    @staticmethod
    def fetch(res) -> tuple:
        s, d, r, p = jax.device_get(
            (res.best_start, res.best_dist, res.rounds, res.lb_pruned))
        return int(s), float(d), int(r), int(p)
