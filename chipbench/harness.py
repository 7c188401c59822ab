"""One run of one cell: set-up, the measured window, metrics, the check.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration file, its traffic file ``chipbench/traffic/<traffic>.json``
and one reader per metric, ``chipbench/metrics/<metric>.py``, whose
``read(run)`` returns the number or None where it finds nothing to read.
A quantity split by the cells that report it (``engine.dispatch_ms.bulk``,
``engine.dispatch_ms.interactive``) has one reader, named without the last
part.

The program's index of a configuration is built by the first run in a
checkout and saved under ``.cache/chipbench_index``, keyed by the
configuration's data and index settings and by the program's source; later
runs load it.  So set-up pays the host build once per checkout, as it pays
compilation once.
"""
from __future__ import annotations

import contextlib
import gc
import hashlib
import importlib.util
import json
import shutil
import sys
import threading
import time
from pathlib import Path

import numpy as np

from chipbench import check, drive, traffic
from chipbench.data import make_base_and_queries

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
INDEX_DIR = ROOT / ".cache" / "chipbench_index"
TRACE_DIR = ROOT / ".cache" / "chipbench_trace"
PENDING_ROWS = 1 << 20          # no request is refused for queue space
TRACE_LEAD_S, TRACE_S = 2.0, 3.0
KERNELS = {"fused_expand": "fused_expand_pallas"}


class NoChip(RuntimeError):
    """The measured path found no TPU, or fewer chips than the cell asks."""


def load_cell(bench: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic and metric names."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = traffic.validate(json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text()))

    def names(group):
        return [m["name"] for m in bench[group]
                if workload in m.get("workloads", [workload])]

    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": names("end_to_end"), "per_layer": names("per_layer"),
            "units": {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}}


def reader(name: str, metrics_dir: Path = HERE / "metrics"):
    path = metrics_dir / f"{name}.py"
    if not path.exists():
        path = metrics_dir / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(devices, chips: int):
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX has {len(devices)}")


def enable_compile_cache(jax) -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_CACHE_DIR``,
    else the fixed ``<checkout>/.cache/jax_compile``).  The program keeps only
    what takes a second or more to compile; the benchmark keeps every program,
    so that a later run of the cell compiles nothing."""
    from repro.launch.compile_cache import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def index_key(config: dict, n_base: int, src: Path = ROOT / "src") -> str:
    """What the built index depends on: the data and index settings, the
    base size, and every source file of the program."""
    h = hashlib.sha256(json.dumps(
        [n_base, config["dim"], config["metric"], config["data"], config["index"]],
        sort_keys=True).encode())
    for f in sorted(src.rglob("*.py")):
        h.update(f.relative_to(src).as_posix().encode())
        h.update(f.read_bytes())
    return h.hexdigest()[:20]


def cached_index(config: dict, base: np.ndarray, index_dir: Path = INDEX_DIR):
    """(the program's index of ``base``, its build seconds or None where it
    was loaded).  A build is saved, older builds of the configuration are
    removed, and the index served is always the one read back."""
    from repro.core.index import AnnIndex

    path = index_dir / f"{config['name']}-{index_key(config, base.shape[0])}.npz"
    build_s = None
    if not path.exists():
        t = time.perf_counter()
        ix = config["index"]
        built = AnnIndex.build(base, graph=ix["graph"], metric=config["metric"],
                               m=ix["m"], efc=ix["efc"],
                               profile_percentile=ix["profile_percentile"],
                               seed=ix["build_seed"])
        for old in index_dir.glob(f"{config['name']}-*.npz"):
            old.unlink()
        built.save(str(path))
        build_s = time.perf_counter() - t
    return AnnIndex.load(str(path)), build_s


class Run:
    """What the metric readers read: the window's requests, the frontend's
    telemetry, the reduced trace, the configuration and the chip's peaks."""

    def __init__(self, config, mix, seconds, setup_s, t0, window, telemetry,
                 trace=None, trace_t=(None, None), peaks=None):
        self.config, self.mix, self.seconds = config, mix, seconds
        self.setup_s, self.t0, self.t_end = setup_s, t0, t0 + seconds
        self.requests = window.requests
        self.telemetry = telemetry
        self.trace, self.trace_t = trace, trace_t
        # the traced interval: the host's, or the device's span where the
        # device ran ops a little before the host read its clock
        self.trace_window_s = None if trace is None else max(
            trace_t[1] - trace_t[0], trace["device_span_s"])
        self._peaks = peaks

    def peak(self, key: str) -> float:
        if self._peaks is None:
            raise KeyError("this device_kind is not in chipbench/peaks.json")
        return float(self._peaks[key])

    def completed(self, t_from: float, t_to: float) -> list:
        """Requests answered (not failed) with t_from <= t_done <= t_to."""
        return [r for r in self.requests if r.error is None and r.t_done is not None
                and t_from <= r.t_done <= t_to]

    def latencies_ms(self) -> np.ndarray:
        """Due-to-resolution latency of every answered request sent in the
        window, however late it resolved."""
        return np.asarray([(r.t_done - r.t_due) * 1e3 for r in self.requests
                           if r.error is None and r.t_done is not None])

    def traced(self) -> list:
        """Requests answered inside the traced interval."""
        if self.trace is None:
            return []
        return self.completed(*self.trace_t)

    def work_bytes(self, requests) -> int:
        from chipbench.work import algorithm_bytes

        calls = sum(int(np.sum(r.result[2].dist_calls)) for r in requests)
        hops = sum(int(np.sum(r.result[2].hops)) for r in requests)
        return algorithm_bytes(calls, hops, self.config["dim"],
                               self.config["index"]["m"])


def _span(jax, tracing: bool):
    if tracing:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


@contextlib.contextmanager
def collector_paused():
    """Python's cyclic garbage collector off for the window, with what set-up
    made frozen out of its passes (as ``timeit`` measures).  The load
    generator keeps every request of the window, with its answer, for the
    check; with those in the heap a full collection stops every thread, the
    sender and the frontend's worker with it, for tens of milliseconds."""
    gc.collect()
    gc.freeze()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        gc.unfreeze()


def _tracer(jax, t_start: float, out: dict) -> threading.Thread:
    """Trace ``TRACE_S`` seconds from ``t_start`` on its own thread."""
    def go():
        time.sleep(max(0.0, t_start - time.perf_counter()))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no per-call Python spans
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        out["t0"] = time.perf_counter()
        time.sleep(TRACE_S)
        out["t1"] = time.perf_counter()
        jax.profiler.stop_trace()

    t = threading.Thread(target=go, name="bench-tracer")
    t.start()
    return t


def search_spec(config: dict, **change):
    from repro.core.spec import SearchSpec

    s = config["search"]
    spec = SearchSpec(k=config["k"], efs=s["efs"], router=s["router"],
                      engine=s["engine"], estimate=s["estimate"],
                      beam_width=s["beam_width"], max_hops=s["max_hops"])
    return spec.replace(**change) if change else spec


def cell_data(config: dict, scale: float = 1.0):
    """(base, queries) of the configuration; the query pool in its fixed order."""
    d = config["data"]
    return make_base_and_queries(
        max(int(config["n_base"] * scale), 64), max(int(config["n_query"] * scale), 16),
        config["dim"], d["n_clusters"], d["cluster_std"], d["sub_spread"], d["seed"])


def build_frontend(config, mix, base):
    """The system under test: the program's HNSW index and angle profile
    (``cached_index``) behind its serving frontend, warmed at the mix's
    buckets.  Returns (index, frontend, build seconds or None)."""
    from repro.serve import ServeFrontend

    index, build_s = cached_index(config, base)
    fe = ServeFrontend(index, search_spec(config), buckets=tuple(mix["buckets"]),
                       max_pending_rows=PENDING_ROWS)
    return index, fe, build_s


def measure(loaded: dict, seed: int, seconds: float, trace: bool, t_process: float,
            *, require_tpu: bool = True, scale: float = 1.0, out=sys.stdout,
            err=sys.stderr, serve=build_frontend):
    """One run; returns the result line's dict.  ``serve`` builds the system
    under test (the tests pass one with a planted fault)."""
    import jax

    config, mix, cell = loaded["config"], loaded["mix"], loaded["cell"]
    devices = jax.devices()
    if require_tpu:
        require_chips(devices, int(cell["chips"]))
    dev = devices[0]

    base, queries = cell_data(config, scale)
    n_base, n_query = base.shape[0], queries.shape[0]
    index, fe, build_s = serve(config, mix, base)
    fe.start()

    tracing = {}
    # the run's seed orders the requests' blocks of query rows: every seed
    # sends the same batches to the same index, in another order
    window = drive.Window(fe.submit, queries, int(mix["rows_per_request"]), seed,
                          _span(jax, trace))
    with collector_paused():
        t0 = time.perf_counter()
        setup_s = t0 - t_process
        tracer = _tracer(jax, t0 + min(TRACE_LEAD_S, seconds / 4), tracing) if trace else None
        if mix["loop"] == "closed":
            drive.run_closed(window, int(mix["clients"]), t0, t0 + seconds)
        else:
            drive.run_open(window, float(mix["rate_per_s"]), seconds, seed, t0)
        unanswered = window.wait_all(t0 + seconds + drive.LATE_S)
    fe.stop()
    if tracer is not None:
        tracer.join()

    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    reduced = None
    if trace:
        from chipbench import trace as tr

        reduced = tr.reduce(tr.load(str(TRACE_DIR)), KERNELS)
    peaks = json.loads((HERE / "peaks.json").read_text()).get(dev.device_kind)
    run = Run(config, mix, seconds, setup_s, t0, window, fe.telemetry,
              trace=reduced, trace_t=(tracing.get("t0"), tracing.get("t1")),
              peaks=peaks)
    wanted = loaded["per_layer"] if trace else loaded["end_to_end"]
    metrics = {}
    for name in wanted:
        value = reader(name)(run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": loaded["units"][name]}

    summ = fe.telemetry.summary()
    del fe, index
    gc.collect()

    answered = [r for r in window.requests if r.error is None and r.t_done is not None]
    failed = sum(1 for r in window.requests if r.error is not None) + unanswered
    numbers = {"recall_miss": 1.0, "dist_gap": 0.0, "bad_rows": 0,
               "unanswered": unanswered}
    if answered:
        rng = np.random.default_rng(seed)
        pick = rng.choice(len(answered), min(int(mix["sample_requests"]), len(answered)),
                          replace=False)
        sample = [answered[i] for i in sorted(pick)]
        rows = np.concatenate([r.rows for r in sample])
        ids = np.concatenate([np.asarray(r.result[0]) for r in sample])
        dists = np.concatenate([np.asarray(r.result[1]) for r in sample])
        numbers = check.compare(queries[rows], base, ids, dists, int(config["k"]),
                                unanswered)
    ok, table = check.judge(numbers, config["limits"])
    late = np.asarray([r.t_sent - r.t_due for r in window.requests]) * 1e3
    print(json.dumps({
        "workload": cell["name"], "seed": seed, "seconds": seconds, "trace": int(trace),
        "n_base": n_base, "n_query": n_query, "index_build_s": build_s,
        "recall_at_k": 1.0 - numbers["recall_miss"],
        "generator_late_p99_ms": float(np.percentile(late, 99)) if late.size else None,
        "generator_late_max_ms": float(late.max()) if late.size else None,
        "recompiles_after_warmup": summ["recompiles_after_warmup"],
        "telemetry_requests": summ["requests"],
        "peak_bytes_in_use": peak_bytes,
        "busy_s": None if reduced is None else reduced["busy_s"],
        "trace_window_s": run.trace_window_s,
    }), file=out, flush=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(ok), "attempted": len(window.requests), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = run.trace_window_s
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = table
    for name, v in table.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})", file=err)
    err.flush()
    return result
