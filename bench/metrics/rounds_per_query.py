"""rounds_per_query: the round driver's rounds, mean over the queries.

The program's own count, ``SearchResult.rounds``.
"""


def read(run):
    if not run.queries:
        return None
    return sum(q.rounds for q in run.queries) / len(run.queries)
