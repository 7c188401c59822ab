"""setup_s: process start to the first scheduled send (data, the host HNSW
build, the angle profile, table placement and the frontend's warm-up)."""


def read(run):
    return run.setup_s
