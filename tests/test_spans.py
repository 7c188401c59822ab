"""The serve path's own trace names: host spans (``core/spans.py``) nested in
each frontend dispatch, the search program's named scopes in its lowered
text, and the telemetry's lifetime queue-wait sum."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.index import AnnIndex
from repro.core.search import PHASE_SCOPES, build_search_fn
from repro.core.spec import SearchSpec
from repro.data.vectors import make_dataset
from repro.serve import ServeFrontend
from repro.serve.telemetry import WINDOW, ServeTelemetry

CHILDREN = ("frontend.batch", "index.prepare", "index.launch", "index.fetch",
            "frontend.resolve")


@pytest.fixture(scope="module")
def tiny():
    ds = make_dataset(n_base=300, n_query=8, dim=16, n_clusters=6, seed=3)
    return AnnIndex.build(ds.base, graph="hnsw", m=8, efc=32), ds.queries


def _host_events(trace_dir):
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]


def test_flush_traces_one_dispatch_with_its_spans_nested(tiny, tmp_path):
    index, queries = tiny
    fe = ServeFrontend(index, SearchSpec(k=5, efs=16, router="crouting", engine="jnp"),
                       buckets=(4,))
    fe.submit(queries[:1])
    fe.submit(queries[1:3])
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert fe.flush() == 1
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    (name, s0, e0, meta), = [ev for ev in events if ev[0] == "frontend.dispatch"]
    assert meta["rows"] == 3 and meta["bucket"] == 4
    did = meta["id"]
    for child in CHILDREN:
        (_, s, e, m), = [ev for ev in events if ev[0] == child]
        assert s0 <= s <= e <= e0, child
        assert m["id"] == did, child
    order = sorted(CHILDREN, key=lambda c: next(ev[1] for ev in events if ev[0] == c))
    assert order == list(CHILDREN)


def test_dispatch_ids_count_up_and_search_outside_a_dispatch_has_none(tiny):
    from repro.core import spans

    with spans.dispatch(41):
        with spans.dispatch(42):
            assert spans._DISPATCH_ID.get() == 42
        assert spans._DISPATCH_ID.get() == 41
    assert spans._DISPATCH_ID.get() is None
    index, queries = tiny
    fe = ServeFrontend(index, SearchSpec(k=5, efs=16, router="crouting", engine="jnp"),
                       buckets=(4,))
    first = next(fe._dispatch_ids)
    assert next(fe._dispatch_ids) == first + 1


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_lowered_engine_names_every_phase_scope(tiny, engine):
    index, queries = tiny
    g = index.graph
    spec = SearchSpec(k=5, efs=16, router="crouting", engine=engine, metric=g.metric,
                      use_hierarchy=g.upper_neighbors is not None)
    q = jax.ShapeDtypeStruct((8, g.dim), np.float32)
    ct = jax.ShapeDtypeStruct((), np.float32)
    _, fn = build_search_fn(g, spec)
    text = fn._jitted.lower(fn._tables, q, ct).as_text(debug_info=True)
    # the served engine does nothing after its loop: search.finish holds
    # ops only where a tombstone mask (or the sq8 rerank) follows the loop
    for scope in PHASE_SCOPES:
        assert (scope in text) == (scope != "search.finish"), scope
    _, fn_t = build_search_fn(g, spec, tombstones=True)
    dead = jax.ShapeDtypeStruct((g.n + 1,), np.bool_)
    text_t = fn_t._jitted.lower(fn_t._tables, q, ct, dead).as_text(debug_info=True)
    for scope in PHASE_SCOPES:
        assert scope in text_t, scope


def test_queue_wait_total_covers_every_request_past_the_window():
    t = ServeTelemetry()
    waits = [(i % 7) * 1e-3 for i in range(WINDOW + 500)]
    for w in waits:
        t.observe_request_done(w + 0.01, w)
    assert len(t.queue_wait_s) == WINDOW
    assert t.served == len(waits)
    assert t.queue_wait_s_total == pytest.approx(sum(waits))
    assert t.summary()["queue_wait_mean_ms"] == pytest.approx(
        1e3 * sum(waits) / len(waits), abs=1e-3)
    assert ServeTelemetry().summary()["queue_wait_mean_ms"] is None
