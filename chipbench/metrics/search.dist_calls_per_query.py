"""search.dist_calls_per_query: exact distance calls (``stats.dist_calls``)
per query row, over the requests answered inside the window."""


def read(run):
    done = run.completed(run.t0, run.t_end)
    rows = sum(len(r.rows) for r in done)
    return sum(int(r.result[2].dist_calls.sum()) for r in done) / rows if rows else None
