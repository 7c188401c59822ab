"""The controls: the reference one precision down, and the program's own
search cut short, in the program's place.

    python3 chipbench/control.py --workload sift128.bulk --seeds 5,6,7

For each seed it makes the cell's data, takes as many query rows as a run
of the cell compares (drawn by the seed), and prints the numbers of
``check.compare`` beside the configuration's limits for each of these
answers:

* ``bf16_matmul`` — a brute-force top-k computed in bfloat16 (rows and
  queries rounded to bfloat16, dot products accumulated in float32 on the
  chip: the step below the float32 the configuration states);
* ``bf16_rows`` — float32 arithmetic on rows rounded to bfloat16 on the host
  (inside one XLA program the compiler may drop a float32 -> bfloat16 ->
  float32 round trip): the storage a later change might choose to halve the
  row bytes;
* ``program`` — the program's own search at the configuration's settings,
  on the cell's index, in batches of 128 rows (a sound reading);
* ``short_pool`` — the same search with the candidate pool (``efs``) halved;
* ``short_walk`` — the same search with every query's walk cut at the median
  hop count of ``program``'s queries.

The precision controls and the shortened searches have to come out not
correct.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def answers(queries, base, k: int, kind: str, block: int = 256):
    """Top-k (ids, squared distances) of ``queries`` in a lower precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def top(q, x):
        if kind == "bf16_matmul":
            qb, xb = q.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
            dots = jnp.dot(qb, xb.T, preferred_element_type=jnp.float32)
            qn = jnp.sum(jnp.square(qb.astype(jnp.float32)), axis=1)
            xn = jnp.sum(jnp.square(xb.astype(jnp.float32)), axis=1)
            d = qn[:, None] + xn[None, :] - 2.0 * dots
        else:
            d = jnp.sum(jnp.square(q[:, None, :] - x[None, :, :]), axis=-1)
        neg, idx = jax.lax.top_k(-d, k)
        return idx, -neg

    if kind == "bf16_rows":
        import ml_dtypes

        base = base.astype(ml_dtypes.bfloat16).astype(np.float32)
    x = jnp.asarray(base)
    ids, dists = [], []
    step = block if kind == "bf16_matmul" else 8
    for s in range(0, queries.shape[0], step):
        i, d = top(jnp.asarray(queries[s:s + step]), x)
        ids.append(np.asarray(i))
        dists.append(np.asarray(d))
    return np.concatenate(ids).astype(np.int64), np.concatenate(dists)


def program_answers(index, config, queries, **change):
    """(ids, dists, hops) of the program's search, 128 rows at a time."""
    import numpy as np

    from chipbench import harness

    spec = harness.search_spec(config, **change)
    out = [index.search(queries[s:s + 128], spec=spec) for s in range(0, len(queries), 128)]
    return (np.concatenate([o[0] for o in out]), np.concatenate([o[1] for o in out]),
            np.concatenate([np.asarray(o[2].hops) for o in out]))


def sample_rows(loaded: dict, n_query: int, seed: int):
    import numpy as np

    mix = loaded["mix"]
    n_rows = min(int(mix["sample_requests"]) * int(mix["rows_per_request"]), n_query)
    return np.random.default_rng(seed).choice(n_query, n_rows, replace=False)


def judged(loaded, seed, kind, queries, base, ids, dists) -> dict:
    from chipbench import check

    config = loaded["config"]
    numbers = check.compare(queries, base, ids, dists, int(config["k"]), 0)
    ok, table = check.judge(numbers, config["limits"])
    return {"seed": seed, "kind": kind, "correct": ok, "checks": table}


def readings(loaded: dict, seed: int, kind: str, scale: float = 1.0) -> dict:
    """The reference in bfloat16 (``bf16_matmul``, ``bf16_rows``)."""
    from chipbench import harness

    config = loaded["config"]
    base, queries = harness.cell_data(config, scale)
    q = queries[sample_rows(loaded, queries.shape[0], seed)]
    ids, dists = answers(q, base, int(config["k"]), kind)
    return judged(loaded, seed, kind, q, base, ids, dists)


def program_readings(loaded: dict, seeds, scale: float = 1.0, index=None) -> list:
    """The program's search, sound and cut short, on the same rows."""
    import numpy as np

    from chipbench import harness

    config = loaded["config"]
    base, queries = harness.cell_data(config, scale)
    if index is None:
        index, _ = harness.cached_index(config, base)
    out = []
    for seed in seeds:
        q = queries[sample_rows(loaded, queries.shape[0], seed)]
        ids, dists, hops = program_answers(index, config, q)
        out.append(judged(loaded, seed, "program", q, base, ids, dists))
        walk = max(1, int(np.median(hops)))
        for kind, change in (("short_pool", {"efs": max(int(config["k"]),
                                                        config["search"]["efs"] // 2)}),
                             ("short_walk", {"max_hops": walk})):
            ids, dists, _ = program_answers(index, config, q, **change)
            out.append(dict(judged(loaded, seed, kind, q, base, ids, dists), change=change))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse", type=float, default=None, metavar="SCALE")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from chipbench import harness

    if args.rehearse is None and jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    loaded = harness.load_cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                               args.workload)
    harness.enable_compile_cache(jax)
    scale = 1.0 if args.rehearse is None else args.rehearse
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        for kind in ("bf16_matmul", "bf16_rows"):
            print(json.dumps({"workload": args.workload,
                              **readings(loaded, seed, kind, scale)}), flush=True)
    for r in program_readings(loaded, seeds, scale):
        print(json.dumps({"workload": args.workload, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
