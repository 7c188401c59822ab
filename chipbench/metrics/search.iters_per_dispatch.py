"""search.iters_per_dispatch: hop-loop iterations (``stats.iters``) per
request answered inside the window; where one request is one dispatch, as in
the bulk mix, that is per dispatch."""


def read(run):
    done = run.completed(run.t0, run.t_end)
    return sum(int(r.result[2].iters) for r in done) / len(done) if done else None
