"""qps: query rows answered inside the window, over the window's seconds."""


def read(run):
    return sum(len(r.rows) for r in run.completed(run.t0, run.t_end)) / run.seconds
