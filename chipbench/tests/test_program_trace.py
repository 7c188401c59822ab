"""The reduction of the program's own scopes and spans, and the readers of
the metrics it feeds, on hand-made traces; the accepted reduction of the
recorded v5e fixture reads as it did."""
import json
import threading
from types import SimpleNamespace

import pytest

from chipbench import harness
from chipbench import program_trace as pt
from chipbench import trace as tr
from chipbench.tests.conftest import ROOT

FIXTURE = ROOT / "chipbench" / "tests" / "fixtures" / "trace_v5e_sift128_bulk.json"
LOOP = "jit(run)/while/body"
DEVICE_READERS = ("engine.fetch_idle_share.bulk", "engine.fetch_idle_share.interactive",
                  "search.merge_share", "search.bookkeeping_share")


def _trace(ops, *host_lines):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": f"t{i}", "events": evs}
                                        for i, evs in enumerate(host_lines)]}]}


# a dispatch from 0 to 100 us: the search program runs 0..40 us, the device
# idles while the worker copies the results (a runtime event nested inside
# index.fetch), then idles again while the worker waits for work
OPS = [["%while.3", 0, 40_000, "while"],                                 # container
       ["%fusion.1", 0, 10_000, f"{LOOP}/hop.select/jit(take_along_axis)/gather"],
       ["%fused_expand_pallas.7", 10_000, 15_000,
        f"{LOOP}/hop.distance/jit(fused_expand_pallas)/pallas_call"],
       ["%fusion.2", 25_000, 5_000, f"{LOOP}/hop.scatter/scatter"],
       ["%pool_merge_pallas.7", 30_000, 8_000, f"{LOOP}/hop.merge/jit(pool_merge_pallas)/pallas_call"],
       ["%copy.4", 38_000, 2_000, ""],                                   # no scope
       ["%fusion.9", 70_000, 1_000, "jit(dynamic_slice)/dynamic_slice"],
       ["%fusion.1", 150_000, 1_000, f"{LOOP}/hop.select/top_k"]]
WORKER = [["frontend.dispatch", 0, 100_000],
          ["index.launch", 1_000, 2_000],
          ["index.fetch", 3_000, 90_000],
          ["np.asarray(jax.Array)", 41_000, 29_000],
          ["frontend.resolve", 93_000, 6_000],
          ["frontend.wait", 100_500, 60_000]]
CLIENT = [["bench.result_wait", 0, 160_000]]


def test_scopes_and_program_idle():
    r = pt.reduce(_trace(OPS, WORKER, CLIENT))
    s = r["scope_s"]
    assert s["hop.select"] == pytest.approx(11e-6)
    assert s["hop.distance"] == pytest.approx(15e-6)
    assert s["hop.scatter"] == pytest.approx(5e-6)
    assert s["hop.merge"] == pytest.approx(8e-6)
    assert s[pt.NO_SCOPE] == pytest.approx(3e-6)             # the copy and the slice
    assert "while" not in s and pt.NO_SCOPE in s
    idle = r["program_idle_s"]
    # 40..70 us: under the runtime event, inside index.fetch; 71..150 us:
    # its midpoint falls in frontend.wait
    assert idle == {"index.fetch": pytest.approx(30e-6),
                    "frontend.wait": pytest.approx(79e-6)}
    assert r["spans"] == ["frontend.dispatch", "frontend.resolve", "frontend.wait",
                          "index.fetch", "index.launch"]
    # the accepted reduction labels the first gap with the runtime event
    old = tr.reduce(_old_form(_trace(OPS, WORKER, CLIENT)), {})
    assert dict(old["breakdown"]["idle_gaps"])["np.asarray(jax.Array)"] == \
        pytest.approx(30e-6)


def test_gap_under_no_program_span_and_scope_paths():
    r = pt.reduce(_trace([["a", 0, 10, "x/hop.merge/y"], ["b", 50_000, 10, ""]], CLIENT))
    assert r["program_idle_s"] == {pt.NO_SPAN: pytest.approx(49_990e-9)}
    assert r["spans"] == []
    assert pt.reduce(_trace([], WORKER)) is None
    assert pt.scope_of("jit(run)/search.descend/vmap()/while/body/hop.select/x") == "hop.select"
    assert pt.scope_of("jit(run)/while/cond/reduce_or") == pt.NO_SCOPE
    assert pt.strip_meta("frontend.dispatch#id=3,rows=8#") == "frontend.dispatch"
    assert pt.strip_meta("np.asarray(jax.Array)") == "np.asarray(jax.Array)"


def test_recorded_fixture_reads_as_before():
    trace = json.loads(FIXTURE.read_text())
    r = tr.reduce(trace, {"fused_expand": "fused_expand_pallas"})
    # the accepted reduction's numbers of this fixture, before any program scope
    assert r["busy_s"] == pytest.approx(0.000993417, rel=1e-9)
    assert r["device_span_s"] == pytest.approx(0.008163975, rel=1e-9)
    assert r["kernel_s"] == {"fused_expand": pytest.approx(0.000379823, rel=1e-9)}
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fusion"] == pytest.approx(0.000462973, rel=1e-9)
    assert ops["pool_merge_pallas"] == pytest.approx(5.3408e-05, rel=1e-9)
    assert dict(r["breakdown"]["idle_gaps"])["CommonPjRtBuffer::ToLiteral"] == \
        pytest.approx(0.007169439, rel=1e-9)
    # the old form has no op_name: every leaf op is under no scope, and the
    # recorder's process had no program span
    p = pt.reduce(trace)
    assert set(p["scope_s"]) == {pt.NO_SCOPE}
    assert p["scope_s"][pt.NO_SCOPE] == pytest.approx(sum(v for _, v in _leaf_ops(trace)))
    assert p["spans"] == [] and set(p["program_idle_s"]) == {pt.NO_SPAN}


def _leaf_ops(trace):
    events = sorted((ev for p in trace["planes"] if tr.DEVICE_PLANE.match(p["name"])
                     for ln in p["lines"] for ev in ln["events"]),
                    key=lambda ev: (ev[1], -ev[2]))
    return [(ev[0], ev[2] * 1e-9) for i, ev in enumerate(events)
            if not (i + 1 < len(events) and events[i + 1][1] < ev[1] + ev[2])]


def _old_form(trace):
    """The trace as ``trace.load`` gives it: device ops without op_name."""
    return {"planes": [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [ev[:3] for ev in ln["events"]]}
        for ln in p["lines"]]} for p in trace["planes"]]}


def _run(trace, window_s=200e-6):
    """What the readers see of a traced run: both reductions."""
    return SimpleNamespace(trace=tr.reduce(_old_form(trace), {}), trace_window_s=window_s,
                           program_trace=pt.reduce(trace))


def test_readers_read_the_program_trace():
    run = _run(_trace(OPS, WORKER, CLIENT))
    busy = run.trace["busy_s"]
    assert harness.reader("engine.fetch_idle_share.bulk")(run) == pytest.approx(15.0)
    assert harness.reader("engine.fetch_idle_share.interactive")(run) == pytest.approx(15.0)
    assert harness.reader("search.merge_share")(run) == pytest.approx(100 * 8e-6 / busy)
    assert harness.reader("search.bookkeeping_share")(run) == pytest.approx(
        100 * 16e-6 / busy)


@pytest.mark.parametrize("name", DEVICE_READERS)
def test_device_readers_read_nothing_without_scopes_or_spans(name):
    bare = _trace([ev[:3] for ev in OPS], [["np.asarray(jax.Array)", 41_000, 29_000]])
    assert harness.reader(name)(_run(bare)) is None
    assert harness.reader(name)(SimpleNamespace(trace=None)) is None


def test_queue_wait_mean_reads_the_lifetime_total_or_nothing():
    read = harness.reader("frontend.queue_wait_mean_ms")
    t = SimpleNamespace(_obs_lock=threading.Lock(), served=4, queue_wait_s_total=0.02)
    assert read(SimpleNamespace(telemetry=t)) == pytest.approx(5.0)
    older = SimpleNamespace(_obs_lock=threading.Lock(), served=4)
    assert read(SimpleNamespace(telemetry=older)) is None
    none = SimpleNamespace(_obs_lock=threading.Lock(), served=0, queue_wait_s_total=0.0)
    assert read(SimpleNamespace(telemetry=none)) is None


HLO = """HloModule jit_run, entry_computation_layout={(f32[8,16]{1,0})->s32[8]{0}}

%fused_computation.3 (param_0: s32[8,4]) -> s32[8] {
  %param_0 = s32[8,4]{1,0} parameter(0)
  ROOT %reduce.1 = s32[8]{0} reduce(s32[8,4]{1,0} %param_0), metadata={op_name="jit(run)/while/body/hop.scatter/reduce_sum" stack_frame_id=3}
}

ENTRY %main.9 (queries: f32[8,16]) -> s32[8] {
  %queries = f32[8,16]{1,0} parameter(0), metadata={op_name="queries"}
  %fusion.3 = s32[8]{0} fusion(s32[8,4]{1,0} %x), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(run)/while/body/hop.select/gather"}
  %pool_merge_pallas.7 = (f32[8,128]{1,0:T(8,128)}, s32[8,128]{1,0:T(8,128)}) custom-call(f32[8,128]{1,0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/hop.merge/jit(pool_merge_pallas)/pallas_call"}
  %sort.4 = s32[8]{0} sort(s32[8]{0} %queries, s32[8]{0} %fusion.3), dimensions={0}
  ROOT %copy.2 = s32[8]{0} copy(s32[8]{0} %sort.4)
}
"""


def test_hlo_op_names_take_a_fusions_root_and_match_name_and_shape():
    m = pt.hlo_op_names(HLO)
    assert m[("%fusion.3", "s32[8]{0}")] == "jit(run)/while/body/hop.scatter/reduce_sum"
    merge = ("%pool_merge_pallas.7",
             "(f32[8,128]{1,0:T(8,128)}, s32[8,128]{1,0:T(8,128)})")
    assert pt.scope_of(m[merge]) == "hop.merge"
    # made by the compiler without an op_name: the nearest operand's, in
    # order (%queries has an op_name, but no program scope)
    assert m[("%sort.4", "s32[8]{0}")] == "queries"
    assert m[("%copy.2", "s32[8]{0}")] == "queries"
    assert m[("%param_0", "s32[8,4]{1,0}")] == ""
    names = pt.OpNames([HLO, HLO.replace("s32[8]{0} fusion", "s32[16]{0} fusion")])
    assert pt.scope_of(names("%fusion.3 = s32[8]{0} fusion(s32[8,4]{1,0} %x), kind=kLoop")) \
        == "hop.scatter"
    # by name alone where the shape is not the module's, as both modules agree
    assert pt.scope_of(names("%pool_merge_pallas.7 = f32[1] custom-call()")) == "hop.merge"
    assert names("%no_such.1 = f32[1] add()") == ""


def test_engine_texts_are_the_served_programs_with_their_scopes(tiny_index):
    from chipbench.tests.conftest import tiny_loaded

    from repro.core.spec import SearchSpec

    c = tiny_loaded()["config"]
    tiny_index.search(tiny_index.graph.vectors[:8], spec=SearchSpec(
        k=10, efs=48, router="crouting", engine="jnp"))
    texts = pt.engine_texts(SimpleNamespace(mix={"buckets": [8]}, config={"dim": c["dim"]}))
    ours = [t for t in texts if "hop.select" in t]
    assert ours
    names = pt.OpNames(ours)
    assert {pt.scope_of(op) for op in names.by_key.values()} >= {
        "search.init", "search.descend", "hop.select", "hop.distance", "hop.merge"}
