"""lb_pruned_share: candidate windows the LB cascade spared the DTW, in %.

The program's ``SearchResult.lb_pruned`` summed over the queries, over
their candidate windows.
"""


def read(run):
    if not run.queries:
        return None
    pruned = sum(q.lb_pruned for q in run.queries)
    return 100.0 * pruned / (len(run.queries) * run.n_windows)
