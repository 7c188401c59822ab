"""The reduction from a profiler trace to busy time, kernel time, the idle
share and the breakdown, on a hand-made trace and on a small cut of a trace
recorded on a TPU v5e during a ``sift128.bulk`` run."""
import json

import pytest

from chipbench import trace as tr
from chipbench.tests.conftest import ROOT
from chipbench.work import algorithm_bytes

FIXTURE = ROOT / "chipbench" / "tests" / "fixtures" / "trace_v5e_sift128_bulk.json"


def _trace(ops, host):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "worker", "events": host}]}]}


def test_busy_is_the_union_of_op_intervals_and_gaps_are_labelled():
    ops = [["%while.4", 0, 200],                                     # encloses the next two
           ["%fusion.1", 0, 100], ["%fused_expand_pallas.3", 100, 100],
           ["%fusion.2", 200, 50],                                   # touches: ..250
           ["%fused_expand_pallas.3", 30_200, 800]]                  # after a 30 us gap
    host = [["dispatch", 0, 40_000], ["pad batch", 10_000, 15_000]]
    r = tr.reduce(_trace(ops, host), {"fused_expand": "fused_expand_pallas"})
    assert r["busy_s"] == pytest.approx(1050e-9)
    assert r["device_span_s"] == pytest.approx(31_000e-9)
    assert r["kernel_s"] == {"fused_expand": pytest.approx(900e-9)}
    assert r["breakdown"]["idle_gaps"] == [["pad batch", pytest.approx(29_950e-9)]]
    top = r["breakdown"]["device_ops"]
    assert top[0] == ["fused_expand_pallas", pytest.approx(900e-9)]
    assert "while" not in dict(top)
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)


def test_short_gaps_sum_apart_and_no_device_op_reads_nothing():
    ops = [["a", 0, 10], ["b", 15, 10], ["c", 40_000, 10]]
    r = tr.reduce(_trace(ops, []), {})
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps[tr.SHORT_GAPS] == pytest.approx(5e-9)
    assert gaps["no host span"] == pytest.approx(39_975e-9)
    assert tr.reduce(_trace([], [["x", 0, 5]]), {}) is None


def _sweep_busy(events):
    """Busy time by a boundary sweep: an independent count of the union."""
    edges = sorted([(s, 1) for _, s, d in events] + [(s + d, -1) for _, s, d in events],
                   key=lambda e: (e[0], -e[1]))
    busy, depth, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy * 1e-9


def test_recorded_v5e_trace():
    trace = json.loads(FIXTURE.read_text())
    ops = [ev for p in trace["planes"] if tr.DEVICE_PLANE.match(p["name"])
           for ln in p["lines"] if ln["name"] == tr.OPS_LINE for ev in ln["events"]]
    assert ops
    r = tr.reduce(trace, {"fused_expand": "fused_expand_pallas"})
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(_sweep_busy(ops))
    assert 0 < r["busy_s"] <= r["device_span_s"]
    want = sum(d for name, _, d in ops if tr.op_name(name) == "fused_expand_pallas") * 1e-9
    assert want > 0 and r["kernel_s"]["fused_expand"] == pytest.approx(want)
    for key in ("device_ops", "idle_gaps"):
        entries = r["breakdown"][key]
        assert 0 < len(entries) <= tr.TOP
        assert all(isinstance(k, str) and v > 0 for k, v in entries)


def test_algorithm_bytes():
    # 10 float32 rows of 128, and 2 expansions of 32 ids + 32 lengths
    assert algorithm_bytes(10, 2, 128, 16) == 10 * 512 + 2 * 32 * 8
